"""The serve scheduler's spans on the profiler's clock: `monitor.span` is a
`jax.profiler.TraceAnnotation`, so one `jax.profiler` trace of a tiny engine
(CPU, Python tracer off) must hold the span tree of `serving/llm.py` and
`jit/to_static.py` on the scheduler thread's `/host:CPU` line, with the
attributes as event stats; the span counts must equal the counters stamped
at the same boundaries; with the monitor off every site is the shared null
span; and the compiled programs carry stable names."""
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.monitor as monitor
from paddle_tpu import nn
from paddle_tpu.models.gpt import GPTForCausalLM, GPTModel
from paddle_tpu.profiler import Profiler, RecordEvent
from paddle_tpu.serving import LLMConfig, LLMEngine

# a turn dispatches the next step, then reads and emits the one that was
# in flight: the first turn after an empty pipeline has nothing to read, a
# drain (before an admission) and a turn with no sequence going on have
# nothing to dispatch
STEP_CHILDREN = ("llm.decode.dispatch", "llm.decode.read", "llm.emit")
STEP_SHAPES = (STEP_CHILDREN, STEP_CHILDREN[:1], STEP_CHILDREN[1:])
ADMIT_CHILDREN = ("llm.prefill", "llm.slot_write", "llm.emit")
# every site that goes through `monitor.span` (jit.to_static.call takes the
# null span itself, for the `static_program` profiler hook's sake)
SPAN_SITES = ("llm.park", "llm.admit", "llm.prefill", "llm.slot_write",
              "llm.emit", "llm.step", "llm.decode.dispatch",
              "llm.decode.read", "jit.to_static.prepare")


def _build_lm(seed=7):
    paddle.seed(seed)
    gpt = GPTModel(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                   max_seq_len=128, dropout=0.0)
    lm = GPTForCausalLM(gpt)
    lm.eval()
    return lm


def _engine():
    return LLMEngine(_build_lm(), LLMConfig(num_slots=2, max_len=16,
                                            max_new_tokens=6)).start()


def _host_lines(trace_dir):
    """[[(name, start_ns, end_ns, stats)]] per `/host:CPU` line."""
    import glob
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for e in ln.events] for ln in host.lines]


def _trace(trace_dir, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_lines(trace_dir)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced run of a tiny engine with the monitor on: the events of
    the scheduler's line and the monitor's snapshot."""
    monitor.reset()
    paddle.set_flags({"FLAGS_monitor": True})
    eng = _engine()
    try:
        prompts = [[9, 2], [9, 2, 3], [1, 2]]
        assert eng.submit(prompts[0]).result(timeout=120.0)[0] == "done"  # warm

        def body():
            streams = [eng.submit(p) for p in prompts[1:]]
            assert all(s.result(timeout=120.0)[0] == "done" for s in streams)
            time.sleep(0.1)     # the scheduler parks (20 ms naps) meanwhile
        lines = _trace(tmp_path_factory.mktemp("llm_trace"), body)
    finally:
        eng.stop()
        snap = monitor.snapshot()
        paddle.set_flags({"FLAGS_monitor": False})
        monitor.reset()
    holders = [ln for ln in lines if any(e[0] == "llm.step" for e in ln)]
    return {"holders": holders, "snap": snap, "prompts": prompts,
            "pool_rows": eng.config.num_slots * eng.config.max_len,
            "events": [e for e in holders[0]
                       if e[0].startswith(("llm.", "jit."))]}


def _children(events, parent):
    """Events strictly inside `parent`'s interval, not below a deeper one."""
    _, a, b, _ = parent
    inside = [e for e in events if e is not parent and a <= e[1] and e[2] <= b]
    return [e for e in inside
            if not any(o is not e and o[1] <= e[1] and e[2] <= o[2]
                       for o in inside)]


def test_span_tree_is_on_one_line_with_the_documented_nesting(served):
    assert len(served["holders"]) == 1
    events = served["events"]
    names = {e[0] for e in events}
    assert names == {"llm.park", "llm.admit", "llm.step", "llm.prefill",
                     "llm.slot_write", "llm.emit", "llm.decode.dispatch",
                     "llm.decode.read", "jit.to_static.prepare",
                     "jit.to_static.call"}
    steps = [e for e in events if e[0] == "llm.step"]
    admits = [e for e in events if e[0] == "llm.admit"]
    assert steps and admits
    shapes = [tuple(c[0] for c in _children(events, s)) for s in steps]
    assert set(shapes) <= set(STEP_SHAPES)
    assert STEP_CHILDREN in shapes            # it did run ahead
    # what is dispatched alone is read alone: every step is read once
    assert shapes.count(STEP_CHILDREN[:1]) == shapes.count(STEP_CHILDREN[1:])
    for a in admits:
        kids = tuple(c[0] for c in _children(events, a))
        assert kids and kids == ADMIT_CHILDREN * (len(kids) // 3)
    for d in (e for e in events if e[0] in ("llm.decode.dispatch",
                                            "llm.prefill")):
        assert tuple(c[0] for c in _children(events, d)) == (
            "jit.to_static.prepare", "jit.to_static.call")
    # top level: nothing of ours lies outside park / admit / step
    top = [e for e in events
           if not any(o is not e and o[1] <= e[1] and e[2] <= o[2]
                      for o in events)]
    assert {e[0] for e in top} == {"llm.park", "llm.admit", "llm.step"}


def test_read_starts_when_dispatch_has_returned(served):
    events = served["events"]
    whole = [kids for kids in (_children(events, s) for s in events
                               if s[0] == "llm.step") if len(kids) == 3]
    assert whole
    for dispatch, read, emit in whole:
        assert dispatch[2] <= read[1] and read[2] <= emit[1]
        assert read[2] > read[1]


@pytest.mark.parametrize("span, counter", [
    ("llm.decode.dispatch", "llm.decode.steps"),
    ("llm.decode.read", "llm.decode.steps"),
    ("llm.prefill", "llm.prefill.requests"),
    ("llm.slot_write", "llm.prefill.requests"),
    ("jit.to_static.call", "jit.to_static.calls"),
])
def test_span_count_equals_the_counter_of_its_boundary(served, span, counter):
    counters = served["snap"]["counters"]
    assert counters[f"span.{span}.count"] == counters[counter] > 0


def test_kv_row_counters_are_the_live_prefixes_over_the_pool(served):
    """`llm.decode.kv_rows_live`: over every row dispatched, the rows of
    its page the step has to read (the cached prefix and the token being
    written: position + 1); `llm.decode.kv_rows_pool`: the rows the pool
    holds, once a step. No EOS is set, so each of the three requests
    decodes max_new_tokens - 1 = 5 steps from its prompt's length on,
    however the scheduler overlapped them."""
    counters = served["snap"]["counters"]
    assert counters["llm.decode.discarded"] == 0
    assert counters["llm.decode.kv_rows_live"] == sum(
        len(p) + k + 1 for p in served["prompts"] for k in range(5))
    assert counters["llm.decode.kv_rows_pool"] == \
        counters["llm.decode.steps"] * served["pool_rows"]


def test_kv_row_counters_are_absent_under_a_state_pool():
    """A model that keeps recurrent states has no rows to leave unread:
    its step is priced by `llm.decode.state_bytes`."""
    from paddle_tpu.models.brumby import BrumbyForCausalLM, BrumbyModel
    paddle.seed(3)
    lm = BrumbyForCausalLM(BrumbyModel(
        dtype="float32", vocab_size=64, hidden_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, intermediate_size=64,
        gate_bias=3.0))
    lm.eval()
    monitor.reset()
    paddle.set_flags({"FLAGS_monitor": True})
    eng = LLMEngine(lm, LLMConfig(num_slots=2, max_len=16,
                                  max_new_tokens=4)).start()
    try:
        assert eng.submit([9, 2, 3]).result(timeout=120.0)[0] == "done"
    finally:
        eng.stop()
        counters = monitor.snapshot()["counters"]
        paddle.set_flags({"FLAGS_monitor": False})
        monitor.reset()
    assert counters["llm.decode.state_bytes"] > 0
    assert not [k for k in counters if k.startswith("llm.decode.kv_rows")]


def test_step_lasts_at_least_as_long_as_its_three_children(served):
    hist = served["snap"]["histograms"]
    kids = sum(hist[f"span.{n}.dur"]["sum"] for n in ("llm.decode.dispatch",
                                                     "llm.decode.read"))
    # llm.emit is a child of admissions too: take the steps' from the trace
    events = served["events"]
    for s in (e for e in events if e[0] == "llm.step"):
        assert s[2] - s[1] >= sum(c[2] - c[1] for c in _children(events, s))
    assert hist["span.llm.step.dur"]["sum"] >= kids
    # to_static's own duration is the call span's, not a third clock pair
    assert hist["jit.to_static.dur"]["sum"] == pytest.approx(
        hist["span.jit.to_static.call.dur"]["sum"])


def test_attrs_are_the_events_stats(served):
    events = served["events"]
    prefills = [e for e in events if e[0] == "llm.prefill"]
    assert sorted(e[3]["request_id"] for e in prefills) == [2, 3]
    assert {(e[3]["bucket"], e[3]["prompt_len"]) for e in prefills} == {
        (8, 3), (8, 2)}
    writes = [e for e in events if e[0] == "llm.slot_write"]
    assert sorted(e[3]["request_id"] for e in writes) == [2, 3]
    assert all(e[3]["writes"] == 4 for e in writes)      # 2 x 2 layers
    slots = [e[3]["slots"] for e in events if e[0] == "llm.step"]
    assert slots and set(slots) <= {1, 2} and 2 in slots


@pytest.fixture(scope="module")
def unmonitored():
    """The same engine with the monitor off: what `monitor.span` handed
    each site, and the snapshot afterwards."""
    paddle.set_flags({"FLAGS_monitor": False})
    monitor.reset()
    handed = {}
    real = monitor.span

    def spy(name, *a, **kw):
        out = real(name, *a, **kw)
        handed.setdefault(name, []).append(out)
        return out

    monitor.span = spy
    eng = _engine()
    try:
        assert eng.submit([9, 2, 3]).result(timeout=120.0)[0] == "done"
        time.sleep(0.05)
    finally:
        eng.stop()
        monitor.span = real
    return {"handed": handed, "snap": monitor.snapshot()}


@pytest.mark.parametrize("site", SPAN_SITES)
def test_disabled_site_gets_the_shared_null_span(unmonitored, site):
    """The overhead guard (tests/test_monitor.py::TestOverheadGuard) for
    the new sites: off means one attribute check and no allocation."""
    handed = unmonitored["handed"][site]
    assert handed and all(s is monitor._NULL_SPAN for s in handed)
    assert not [k for k in unmonitored["snap"]["counters"]
                if k.startswith("span.")]
    assert not [k for k in unmonitored["snap"]["histograms"]
                if k.startswith("span.")]


def _lowered_name(text):
    head = text.lstrip().split("\n", 1)[0]
    return head.replace("HloModule ", "").replace("module @", "").split(
        ",")[0].split(" ")[0]


@pytest.mark.parametrize("which", ["llm_decode", "llm_prefill",
                                   "llm_slot_write"])
def test_engine_programs_lower_under_their_names(which):
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.functional import split_state
    import jax.numpy as jnp
    eng = LLMEngine(_build_lm(), LLMConfig(num_slots=2, max_len=16,
                                           max_new_tokens=4))
    net = {"llm_decode": eng._decode, "llm_prefill": eng._prefill,
           "llm_slot_write": eng._slot_write}[which]
    if which == "llm_decode":
        inputs = [Tensor(jnp.zeros((2,), jnp.int32)),
                  Tensor(jnp.zeros((2,), jnp.int32)), *eng._pool]
    elif which == "llm_slot_write":
        inputs = [Tensor(jnp.asarray(1, jnp.int32)), *eng._pool,
                  *eng.lm.init_cache(1, 16)]
    else:
        inputs = [Tensor(jnp.zeros((1, 8), jnp.int32)),
                  Tensor(jnp.ones((1,), jnp.int32))]
    # shapes, taken before the call: both writers consume the pool
    specs = [jax.ShapeDtypeStruct(t.shape, t._value.dtype) for t in inputs]
    with paddle.no_grad():
        net(*inputs)
    static = net.forward
    (jitted,) = [v for k, v in static._jit_cache.items() if k[0] == "jit"]
    trainable, frozen = split_state(net)
    text = jitted.lower(*static._call_args(
        [t._value for t in trainable.values()],
        [t._value for t in frozen.values()], jax.random.key(0), specs,
        static._donated(len(specs)))).as_text()
    assert _lowered_name(text) == f"jit_{which}"


def test_train_step_programs_lower_under_their_names():
    net = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(parameters=net.parameters())
    step = paddle.jit.TrainStep(net, lambda out, y: ((out - y) ** 2).mean(),
                                opt)
    x = paddle.to_tensor(np.ones((8, 4), "float32"))
    y = paddle.to_tensor(np.ones((8, 2), "float32"))
    step(x, y)
    assert _lowered_name(step.compiled(x, y).as_text()) == "jit_train_step"
    assert step._jitted_scan.__name__ == "train_step_scan"


def test_spmd_train_step_program_is_named():
    from paddle_tpu.parallel import HybridCommunicateGroup, SPMDTrainStep
    hcg = HybridCommunicateGroup(hybrid_configs={"dp_degree": 8})
    net = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(parameters=net.parameters())
    step = SPMDTrainStep(net, nn.MSELoss(), opt, mesh=hcg.get_mesh(),
                         donate=False)
    step(paddle.to_tensor(np.ones((8, 4), "float32")),
         paddle.to_tensor(np.ones((8, 2), "float32")))
    assert step._jitted.__name__ == "spmd_train_step"


def test_to_static_names_a_program_after_its_layer_function_or_name():
    from paddle_tpu.jit import to_static

    class Tiny(nn.Layer):
        def forward(self, x):
            return x + 1

    def double(x):
        return x * 2

    x = paddle.to_tensor(np.ones((2,), "float32"))
    for static, want in ((to_static(Tiny()).forward, "Tiny"),
                         (to_static(double), "double"),
                         (to_static(double, name="twice"), "twice")):
        static(x)
        (jitted,) = [v for k, v in static._jit_cache.items()
                     if k[0] == "jit"]
        assert jitted.__name__ == want


def test_record_event_is_a_monitor_span_that_ignores_the_flag(tmp_path):
    paddle.set_flags({"FLAGS_monitor": True})
    monitor.reset()
    try:
        assert type(RecordEvent("a")) is type(monitor.span("a")) \
            is monitor.Span
    finally:
        paddle.set_flags({"FLAGS_monitor": False})
    seen = {}

    def body():
        def work():
            paddle.set_flags({"FLAGS_monitor": True})
            try:
                with monitor.span("same.span", step=3):
                    time.sleep(0.001)
            finally:
                paddle.set_flags({"FLAGS_monitor": False})
            with Profiler(timer_only=True) as prof:
                with RecordEvent("same.user"):
                    time.sleep(0.001)
            seen["prof"] = prof.events()
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=60.0)
        assert not t.is_alive()

    lines = _trace(tmp_path, body)
    (line,) = [ln for ln in lines if any(e[0] == "same.span" for e in ln)]
    by_name = {e[0]: e for e in line if e[0].startswith("same.")}
    assert set(by_name) == {"same.span", "same.user"}    # one thread's line
    assert by_name["same.span"][3] == {"step": 3}
    # each slept 1 ms (the bound is loose: three clocks are involved)
    assert all(e[2] - e[1] >= 5e5 for e in by_name.values())
    (ev,) = [e for e in seen["prof"] if e.name == "same.user"]
    assert ev.kind == "user" and ev.dur >= 5e-4
    counters = monitor.snapshot()["counters"]
    assert counters["span.same.span.count"] == 1
    assert "span.same.user.count" not in counters    # the flag was off
    monitor.reset()
