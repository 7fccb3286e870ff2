"""Observability plane: step-timeline attribution + black-box flight
recorder (paddle_tpu.obs).

Acceptance properties (ISSUE 6): timeline phase-sum ≈ wall-step on a jitted
LeNet step; a wedged step (fault-injected watchdog stall) and a SIGTERM
preemption each produce ONE flight-recorder JSON whose last/in-flight
record names the phase it died in; the cross-rank merge names a delayed
rank on the 2-proc store runner; rings stay bounded; the disabled path
costs one module-attribute check (PR-1-style overhead guard); every
guard-plane error type has a registered dump trigger (CI gate for future
error classes); the shipped obs/ package stays tpu-lint --all clean.
"""
import json
import os
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import faults, monitor, obs
from paddle_tpu.core import flags as _flags
from paddle_tpu.guard import (DesyncDetector, DivergedError, GuardConfig,
                              GuardError, PreemptedError, RankDesyncError,
                              StepStalledError, TrainGuard)
from paddle_tpu.jit.train_step import TrainStep
from paddle_tpu.obs import StepTimeline

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "paddle_tpu")


# ---- fixtures / helpers -----------------------------------------------------

@pytest.fixture
def with_obs(tmp_path):
    """Both obs planes on, dumps into tmp, no dump rate-limit."""
    dump_dir = str(tmp_path / "dumps")
    _flags.set_flags({"obs_timeline": True, "obs_flight_recorder": True,
                      "obs_dump_dir": dump_dir,
                      "obs_dump_min_interval_s": 0.0})
    obs.reset()
    yield dump_dir
    _flags.set_flags({"obs_timeline": False, "obs_flight_recorder": False,
                      "obs_dump_dir": "flight_recorder",
                      "obs_dump_min_interval_s": 30.0})
    obs.reset()


# the module-local `_no_obs_leak` autouse fixture moved into conftest's
# unified `_no_thread_leak` teardown (ISSUE 20): the obs-flag assert now
# guards EVERY test file, not just this one


@pytest.fixture
def with_monitor():
    _flags.set_flags({"monitor": True})
    monitor.reset()
    yield
    monitor.reset()
    _flags.set_flags({"monitor": False})


def _make_lenet_step(seed=0, bs=64):
    paddle.seed(seed)
    np.random.seed(seed)
    net = paddle.models.LeNet(num_classes=10)
    opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                learning_rate=1e-3)
    step = TrainStep(net, nn.CrossEntropyLoss(), opt, n_model_inputs=1)
    x = paddle.to_tensor(np.random.rand(bs, 1, 28, 28).astype("float32"))
    y = paddle.to_tensor(np.random.randint(0, 10, (bs,)).astype("int64"))
    return step, x, y


def _make_linear_step(seed=0):
    paddle.seed(seed)
    np.random.seed(seed)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
    opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                learning_rate=1e-2)
    step = TrainStep(net, nn.MSELoss(), opt, n_model_inputs=1)
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.rand(8, 4).astype("float32"))
    y = paddle.to_tensor(rng.rand(8, 1).astype("float32"))
    return step, x, y


def _latest_dump(err):
    path = getattr(err, "dump_path", None)
    assert path and os.path.exists(path), \
        f"no flight-recorder dump on {type(err).__name__}: {err}"
    with open(path) as f:
        return json.load(f)


# ---- step timeline ----------------------------------------------------------

class TestStepTimeline:
    def test_ring_is_bounded(self):
        tl = StepTimeline(capacity=8)
        for _ in range(20):
            with tl.step_record():
                with tl.phase("p"):
                    pass
        recs = tl.records()
        assert len(recs) == 8
        assert recs[-1]["step"] == 20  # newest kept, oldest evicted

    def test_phase_sum_matches_wall_on_jitted_lenet(self, with_obs):
        """THE acceptance invariant: in-window phases must explain the
        measured step wall time to within 10% (median over steady-state
        steps — phases are measured, not inferred, so the gap is only the
        few µs of python between spans)."""
        step, x, y = _make_lenet_step()
        for _ in range(9):
            step(x, y)
        recs = [r for r in obs.timeline().records()
                if "trace_compile" not in r["phases"]
                and "build" not in r["phases"]]
        assert len(recs) >= 6
        coverages = [sum(r["phases"].values()) / r["wall"] for r in recs]
        cov = statistics.median(coverages)
        assert 0.90 <= cov <= 1.02, \
            f"phase sum explains {cov:.1%} of step wall"
        # the fenced compute phase dominates a steady-state training step
        assert all("device_compute" in r["phases"] for r in recs)
        assert all("h2d" in r["phases"] for r in recs)

    def test_phase_sum_bounded_on_novel_signature_step(self, with_obs):
        """Double-accounting regression (ISSUE 11): a novel-signature
        step is where dispatches nest (the step's own booking around
        inner captures/flushes) — before unified booking in
        core/executable.py each level booked its own phase and the same
        wall seconds were counted twice. Even on the trace_compile step,
        phases must not exceed the measured wall."""
        step, x, y = _make_lenet_step()
        step(x, y)
        rec = obs.timeline().records()[0]
        assert "trace_compile" in rec["phases"]
        assert sum(rec["phases"].values()) <= rec["wall"] * 1.02, \
            (f"phases {rec['phases']} sum past wall {rec['wall']:.4f}s "
             f"— a nested dispatch double-booked its wall time")

    def test_first_dispatch_books_trace_compile(self, with_obs):
        step, x, y = _make_linear_step()
        step(x, y)
        first = obs.timeline().records()[0]
        assert "trace_compile" in first["phases"]
        assert "build" in first["phases"]
        # novel signature -> trace_compile again, steady state -> compute
        x2 = paddle.to_tensor(np.random.rand(4, 4).astype("float32"))
        y2 = paddle.to_tensor(np.random.rand(4, 1).astype("float32"))
        step(x2, y2)
        step(x2, y2)
        recs = obs.timeline().records()
        assert "trace_compile" in recs[1]["phases"]
        assert "device_compute" in recs[2]["phases"]
        assert "trace_compile" not in recs[2]["phases"]

    def test_between_steps_work_folds_into_next_record(self, with_obs):
        tl = obs.timeline()
        with tl.phase("data_wait"):
            time.sleep(0.01)
        with tl.step_record():
            with tl.phase("device_compute"):
                pass
        rec = tl.records()[-1]
        # the wait happened BEFORE the step window: between, not phases
        assert rec["between"].get("data_wait", 0) >= 0.009
        assert "data_wait" not in rec["phases"]
        assert sum(rec["phases"].values()) <= rec["wall"] * 1.02

    def test_dataloader_queue_wait_lands_in_timeline(self, with_obs):
        from paddle_tpu.io import DataLoader, Dataset

        class Slow(Dataset):
            def __getitem__(self, i):
                time.sleep(0.002)
                return np.float32(i)

            def __len__(self):
                return 12

        for _ in DataLoader(Slow(), batch_size=4, num_workers=1):
            pass
        with obs.timeline().step_record():
            pass
        rec = obs.timeline().records()[-1]
        assert rec["between"].get("data_wait", 0) > 0

    def test_guard_snapshot_phase_recorded(self, with_obs):
        step, x, y = _make_linear_step()
        with TrainGuard(step, config=GuardConfig(snapshot_interval=1,
                                                 step_timeout_s=0.0)) as g:
            g.set_cursor(0, 0)
            g.step(x, y)
        recs = obs.timeline().records()
        assert any("snapshot" in r["phases"] or "snapshot" in r["between"]
                   for r in recs)

    def test_summary_and_report(self, with_obs):
        step, x, y = _make_linear_step()
        for _ in range(3):
            step(x, y)
        agg = obs.timeline().summary()
        assert agg["device_compute"]["count"] == 2
        assert agg["device_compute"]["mean"] > 0
        rep = obs.timeline().report()
        assert "device_compute" in rep and "step wall" in rep

    def test_chrome_export_merges_profiler_events(self, with_obs, tmp_path):
        from paddle_tpu.profiler import Profiler
        step, x, y = _make_linear_step()
        prof = Profiler(timer_only=True)
        prof._record_op("user_op", time.time(), time.time() + 0.001, "op")
        for _ in range(2):
            step(x, y)
        out = obs.timeline().export_chrome(str(tmp_path / "t.json"),
                                           profiler=prof)
        with open(out) as f:
            data = json.load(f)
        names = {e["name"] for e in data["traceEvents"]}
        assert "device_compute" in names       # timeline phase span
        assert "user_op" in names              # profiler host event
        assert any(e["ph"] == "X" and e["cat"] == "step"
                   for e in data["traceEvents"])
        assert any(e["ph"] == "M" for e in data["traceEvents"])  # monitor

    def test_profiler_export_carries_timeline(self, with_obs, tmp_path):
        from paddle_tpu.profiler import Profiler
        step, x, y = _make_linear_step()
        prof = Profiler(timer_only=True)
        prof.start()
        for _ in range(2):
            step(x, y)
        prof.stop()
        out = str(tmp_path / "prof.json")
        prof.export(out)
        with open(out) as f:
            data = json.load(f)
        assert any(e.get("cat") == "step" for e in data["traceEvents"])


# ---- flight recorder --------------------------------------------------------

class TestFlightRecorder:
    def test_dump_schema_and_rings(self, with_obs):
        step, x, y = _make_linear_step()
        for _ in range(3):
            step(x, y)
        obs.record_event("test.event", detail=1)
        path = obs.dump(reason="unit")
        with open(path) as f:
            doc = json.load(f)
        assert doc["schema"] == obs.DUMP_SCHEMA
        assert doc["reason"] == "unit"
        assert len(doc["steps"]) == 3
        assert doc["events"][-1]["event"] == "test.event"
        assert len(doc["monitor_deltas"]) == 3  # one per closed step
        assert doc["pid"] == os.getpid()

    def test_snapshot_delta_ring_bounded_and_incremental(self, with_monitor,
                                                         with_obs):
        _flags.set_flags({"obs_ring_snapshots": 4})
        try:
            obs.reset()
            tl = obs.timeline()
            for i in range(7):
                with tl.step_record():
                    monitor.count("unit.ticks", 2)
            deltas = obs.recorder().payload()["monitor_deltas"]
            assert len(deltas) == 4  # bounded by FLAGS_obs_ring_snapshots
            # deltas are per-step increments, not cumulative totals
            assert all(d["delta"].get("unit.ticks") == 2 for d in deltas)
        finally:
            _flags.set_flags({"obs_ring_snapshots": 16})

    def test_collective_ring_from_collective_plane(self, with_obs):
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        import paddle_tpu.distributed as dist
        from paddle_tpu.parallel import create_mesh

        mesh = create_mesh({"dp": 8})

        def body(x):
            return dist.all_reduce(paddle.Tensor(x))._value

        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"),
                              out_specs=P("dp"), check_vma=False))
        np.asarray(f(np.ones((8, 4), np.float32)))
        colls = obs.recorder().payload()["collectives"]
        assert any(c[1] == "c_allreduce" for c in colls)
        assert all(c[2] > 0 for c in colls if c[1] == "c_allreduce")

    def test_wedged_step_dump_names_inflight_phase(self, with_obs):
        """Acceptance: a fault-injected watchdog stall produces ONE
        flight-recorder JSON whose in-flight phase names where it hung."""
        step, x, y = _make_linear_step()
        step(x, y)   # compile outside the deadline
        g = TrainGuard(step, config=GuardConfig(step_timeout_s=0.4,
                                                snapshot_interval=0))
        try:
            g.set_cursor(0, 0)
            g.step(x, y)
            with faults.inject("guard.step:delay:delay=1.5:times=1"):
                with pytest.raises(StepStalledError) as ei:
                    g.step(x, y)
            doc = _latest_dump(ei.value)
            assert doc["reason"] == "step_stalled"
            # the wedge sat in the watchdog's "dispatch" phase — the dump
            # names it both as the in-flight phase and in the event ring
            assert doc["inflight_phase"] == "dispatch"
            assert doc["events"][-1]["event"] == "guard.stall"
            assert doc["events"][-1]["phase"] == "dispatch"
            # the step died mid-flight: its record is the OPEN one
            assert doc["open_step"] is not None
            # ...and the error message tells the operator where the box is
            assert "flight recorder" in str(ei.value)
            time.sleep(1.3)  # let the wedged runner drain before close
        finally:
            g.close(grace_s=3.0)

    def test_sigterm_preemption_dumps(self, with_obs, tmp_path):
        """Acceptance: SIGTERM produces one dump (reason=preempted) next
        to the checkpoint, naming the cursor it stopped at."""
        step, x, y = _make_linear_step()
        ckpt = str(tmp_path / "ckpt")
        with TrainGuard(step, ckpt_dir=ckpt,
                        config=GuardConfig(snapshot_interval=0,
                                           step_timeout_s=0.0)) as g:
            g.set_cursor(0, 0)
            g.step(x, y)
            g.set_cursor(0, 1)
            os.kill(os.getpid(), signal.SIGTERM)
            with pytest.raises(PreemptedError) as ei:
                g.step(x, y)
        doc = _latest_dump(ei.value)
        assert doc["reason"] == "preempted"
        ev = doc["events"][-1]
        assert ev["event"] == "guard.preempt"
        assert ev["signum"] == signal.SIGTERM
        assert ev["cursor"] == [0, 2]
        # step 1 closed into the ring; the preempted step 2 was still open
        # when the dump was cut — it IS the open/in-flight record
        assert len(doc["steps"]) == 1
        assert doc["open_step"] is not None
        assert "device_compute" in doc["open_step"]["phases"]

    def test_divergence_dump_and_rollback_events(self, with_obs):
        step, x, y = _make_linear_step()
        step(x, y)
        xnan = paddle.to_tensor(
            np.full((8, 4), np.nan, np.float32))
        g = TrainGuard(step, config=GuardConfig(max_bad_steps=2,
                                                snapshot_interval=0,
                                                step_timeout_s=0.0))
        try:
            g.set_cursor(0, 0)
            g.step(x, y)
            assert g.step(xnan, y) is None      # bad step 1: rolled back
            with pytest.raises(DivergedError) as ei:
                g.step(xnan, y)                 # bad step 2: budget blown
        finally:
            g.close()
        doc = _latest_dump(ei.value)
        assert doc["reason"] == "diverged"
        kinds = [e["event"] for e in doc["events"]]
        assert kinds.count("guard.bad_step") == 2
        assert kinds.count("guard.rollback") == 2

    def test_desync_dump_names_offender(self, with_obs):
        class _DictStore:
            def __init__(self):
                self._d, self._lock = {}, threading.Lock()

            def set(self, key, value):
                with self._lock:
                    self._d[key] = value if isinstance(value, bytes) \
                        else str(value).encode()

            def get(self, key):
                with self._lock:
                    return self._d[key]

        store = _DictStore()
        good = {"w": np.arange(12, dtype="float32")}
        bad = {"w": np.arange(12, dtype="float32") + 1}
        dets = [DesyncDetector(store, r, 3, timeout_s=10.0) for r in range(3)]
        errs = [None] * 3

        def run(r):
            try:
                dets[r].check(1, bad if r == 2 else good)
            except RankDesyncError as e:
                errs[r] = e

        ts = [threading.Thread(target=run, args=(r,)) for r in range(3)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert all(e is not None and e.offenders == [2] for e in errs)
        doc = _latest_dump(errs[0])
        assert doc["reason"] == "rank_desync"
        assert doc["events"][-1]["offenders"] == [2]

    def test_serving_overload_dumps_once(self, with_obs):
        from paddle_tpu.serving import (EngineConfig, ServerOverloadedError,
                                        ServingEngine)
        _flags.set_flags({"obs_dump_min_interval_s": 60.0})  # rate-limit ON
        gate = threading.Event()

        def gated(x):
            gate.wait(10)
            return x

        eng = ServingEngine(gated, EngineConfig(
            max_batch_size=1, batch_timeout_ms=1, queue_depth=2,
            warmup_on_start=False))
        eng.start()
        try:
            eng.submit([np.ones((1, 2), np.float32)])
            time.sleep(0.1)
            queued = [eng.submit([np.ones((1, 2), np.float32)])
                      for _ in range(2)]
            errs = []
            for _ in range(3):   # an overload STORM...
                with pytest.raises(ServerOverloadedError) as ei:
                    eng.submit([np.ones((1, 2), np.float32)])
                errs.append(ei.value)
            gate.set()
            for f in queued:
                f.result(timeout=30)
        finally:
            gate.set()
            eng.stop()
        dumped = [e for e in errs if getattr(e, "dump_path", None)]
        assert len(dumped) == 1  # ...produces ONE dump, not one per reject
        doc = _latest_dump(dumped[0])
        assert doc["reason"] == "serving_overload"
        assert doc["events"][-1]["event"] == "serving.overload"

    def test_auto_dump_rate_limit_and_explicit_bypass(self, with_obs,
                                                      tmp_path):
        _flags.set_flags({"obs_dump_min_interval_s": 60.0})
        assert obs.recorder().dump(reason="r1") is not None
        assert obs.recorder().dump(reason="r1") is None     # limited
        assert obs.recorder().dump(reason="r2") is not None  # other reason
        # explicit path bypasses the limiter
        p = obs.dump(path=str(tmp_path / "explicit.json"), reason="r1")
        assert p and os.path.exists(p)


# ---- dump-trigger CI gate ---------------------------------------------------

def _all_subclasses(cls):
    out = set()
    for sub in cls.__subclasses__():
        out.add(sub)
        out |= _all_subclasses(sub)
    return out


class TestDumpTriggerRegistry:
    def test_every_guard_error_type_has_a_dump_trigger(self):
        """CI gate: a future guard-plane error class shipped without a
        registered flight-recorder dump trigger (directly or inherited
        from a registered ancestor) fails tier-1 — every guard failure
        must leave a black box behind."""
        missing = [cls.__name__ for cls in _all_subclasses(GuardError)
                   if obs.trigger_reason(cls) is None]
        assert not missing, (
            f"guard error types without a flight-recorder dump trigger: "
            f"{missing} — register them via obs.register_dump_trigger")

    def test_known_triggers_registered(self):
        from paddle_tpu.serving import ServerOverloadedError
        assert obs.trigger_reason(StepStalledError) == "step_stalled"
        assert obs.trigger_reason(PreemptedError) == "preempted"
        assert obs.trigger_reason(DivergedError) == "diverged"
        assert obs.trigger_reason(RankDesyncError) == "rank_desync"
        assert obs.trigger_reason(ServerOverloadedError) == "serving_overload"
        # unregistered types never auto-dump
        assert obs.trigger_reason(ValueError) is None


# ---- cross-rank merge -------------------------------------------------------

class TestCrossRankMerge:
    def _records(self, collective_s):
        return [{"step": i + 1, "wall": 0.03 + collective_s,
                 "phases": {"device_compute": 0.02,
                            "collective": collective_s},
                 "between": {"data_wait": 0.001}} for i in range(3)]

    def test_merge_names_straggler_per_phase(self):
        merged = obs.merge_timelines({0: self._records(0.01),
                                      1: self._records(0.01),
                                      2: self._records(0.09)})
        assert merged["world_size"] == 3
        s = merged["stragglers"]["collective"]
        assert s["rank"] == 2
        assert s["skew"] == pytest.approx(9.0, rel=0.01)
        assert merged["slowest_rank"] == 2
        # non-straggled phase does not finger rank 2's compute
        assert merged["stragglers"]["device_compute"]["skew"] == \
            pytest.approx(1.0)
        rep = obs.straggler_report(merged)
        assert "rank 2" in rep and "collective" in rep

    def test_gather_through_store(self):
        class _DictStore(dict):
            def set(self, k, v):
                self[k] = v if isinstance(v, bytes) else str(v).encode()

            def get(self, k):
                return self[k]

        store = _DictStore()
        recs = self._records(0.01)
        outs = [None, None]

        def run(r):
            outs[r] = obs.gather_timelines(store, r, 2, recs,
                                           key="t", timeout_s=10.0)

        ts = [threading.Thread(target=run, args=(r,)) for r in range(2)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert outs[0] == outs[1]
        assert set(outs[0]) == {0, 1}
        # spans were slimmed away before the exchange
        assert "spans" not in outs[0][0][0]

    def test_two_process_merge_names_delayed_rank(self):
        from paddle_tpu import _native
        if not _native.available():
            pytest.skip("native TCPStore unavailable")
        runner = os.path.join(os.path.dirname(__file__),
                              "obs_merge_2proc_runner.py")
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "JAX_", "XLA_", "PALLAS_",
                                    "TPU_", "PYTHONPATH"))}
        procs = [subprocess.Popen(
            [sys.executable, runner, str(r), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True) for r in range(2)]
        outs = []
        for p in procs:
            try:
                out, err = p.communicate(timeout=150)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("2-process merge runner timed out")
            assert p.returncode == 0, f"runner failed:\n{err[-2000:]}"
            outs.append(json.loads(out.strip().splitlines()[-1]))
        for o in outs:   # BOTH ranks reach the same straggler verdict
            assert o["world_size"] == 2
            assert o["collective_straggler"] == 1
            # 2-rank median averages both ranks, so a 9x delay shows as
            # ~1.8x skew — still unambiguous
            assert o["collective_skew"] > 1.4
            assert o["slowest_rank"] == 1
            assert o["report_names_rank1"]
            assert o["steps_rank0"] == 4 and o["steps_rank1"] == 4

    def test_train_guard_timeline_report_single_rank(self, with_obs):
        step, x, y = _make_linear_step()
        with TrainGuard(step, config=GuardConfig(snapshot_interval=0,
                                                 step_timeout_s=0.0)) as g:
            for b in range(3):
                g.set_cursor(0, b)
                g.step(x, y)
            merged, report = g.timeline_report()
        assert merged["world_size"] == 1
        assert "device_compute" in merged["ranks"][0]["phases"]
        assert "pod timeline" in report

    def test_timeline_report_disabled_explains(self):
        step, x, y = _make_linear_step()
        with TrainGuard(step, config=GuardConfig(snapshot_interval=0,
                                                 step_timeout_s=0.0)) as g:
            merged, report = g.timeline_report()
        assert merged is None
        assert "FLAGS_obs_timeline" in report


# ---- XLA cost analysis ------------------------------------------------------

class TestCostAnalysis:
    def test_train_step_attributed_flops(self):
        step, x, y = _make_lenet_step(bs=16)
        step(x, y)
        cost = step.cost_analysis(x, y)
        assert cost.get("flops", 0) > 1e6   # a conv net step is >1 MFLOP
        assert cost.get("bytes_accessed", 0) > 0
        # attributed MFU arithmetic
        mfu = obs.attributed_mfu(cost["flops"], step_time_s=1e-3,
                                 peak_flops=1e12)
        assert mfu == pytest.approx(cost["flops"] / 1e9)
        gap = obs.roofline_gap(cost, 1e-3, 1e12, hbm_bytes_per_s=1e12)
        assert set(gap) >= {"mfu", "hbm_frac", "bound"}


# ---- monitor CLI (the CI-artifact inspection tool) -------------------------

class TestMonitorCLI:
    def test_show_snapshot(self, with_monitor, tmp_path, capsys):
        monitor.count("cli.ticks", 3)
        p = monitor.export_json(str(tmp_path / "snap.json"))
        assert monitor._main(["show", p]) == 0
        out = capsys.readouterr().out
        assert "cli.ticks" in out and "3" in out

    def test_diff_two_snapshots(self, with_monitor, tmp_path, capsys):
        monitor.count("cli.steps", 5)
        monitor.observe("cli.dur", 0.1)
        a = monitor.export_json(str(tmp_path / "a.json"))
        monitor.count("cli.steps", 7)
        monitor.observe("cli.dur", 0.1)
        b = monitor.export_json(str(tmp_path / "b.json"))
        assert monitor._main(["diff", a, b]) == 0
        out = capsys.readouterr().out
        assert "cli.steps" in out and "+7" in out
        assert "cli.dur" in out and "+1" in out  # histogram count delta

    def test_show_flight_dump(self, with_obs, tmp_path, capsys):
        step, x, y = _make_linear_step()
        for _ in range(2):
            step(x, y)
        obs.record_event("unit.marker", k=1)
        p = obs.dump(path=str(tmp_path / "d.json"), reason="cli_test")
        assert monitor._main(["show", p]) == 0
        out = capsys.readouterr().out
        assert "cli_test" in out and "unit.marker" in out
        assert "step records: 2" in out

    def test_trace_conversion(self, with_obs, tmp_path, capsys):
        step, x, y = _make_linear_step()
        for _ in range(2):
            step(x, y)
        p = obs.dump(path=str(tmp_path / "d.json"), reason="trace_test")
        out_path = str(tmp_path / "d.trace.json")
        assert monitor._main(["trace", p, "-o", out_path]) == 0
        with open(out_path) as f:
            trace = json.load(f)
        evs = trace["traceEvents"]
        assert any(e["ph"] == "X" and e["cat"] == "step" for e in evs)
        assert any(e["ph"] == "X" and e["cat"] == "phase" for e in evs)

    def test_trace_rejects_non_dump(self, with_monitor, tmp_path):
        p = monitor.export_json(str(tmp_path / "snap.json"))
        assert monitor._main(["trace", p]) == 2

    def test_cli_subprocess_entrypoint(self, with_obs, tmp_path):
        """`python -m paddle_tpu.monitor` — the actual CI invocation."""
        p = obs.dump(path=str(tmp_path / "d.json"), reason="sub")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("XLA_", "JAX_"))}
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.dirname(PKG)
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.monitor", "show", p],
            capture_output=True, text=True, timeout=120, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "sub" in proc.stdout


# ---- overhead + lint gates --------------------------------------------------

class TestOverheadGuard:
    def test_disabled_path_is_one_attribute_check(self):
        """PR-1-style guard: with both flags off the instrumentation entry
        points allocate nothing and stay within noise of a no-op call."""
        assert not _flags.flag("obs_timeline")
        assert not _flags.flag("obs_flight_recorder")
        obs.reset()
        assert obs.phase("x") is obs.NULL_CTX      # shared, no allocation
        assert obs.step_record() is obs.NULL_CTX

        def loop_gated():
            t0 = time.perf_counter()
            for _ in range(100_000):
                obs.phase("x")
                obs.add_phase("x", 0.0)
                obs.mark("x")
                obs.record_collective("c", 0)
            return time.perf_counter() - t0

        noop = (lambda *_: None)

        def loop_base():
            t0 = time.perf_counter()
            for _ in range(100_000):
                noop("x")
                noop("x", 0.0)
                noop("x")
                noop("c", 0)
            return time.perf_counter() - t0

        loop_gated(), loop_base()  # warm both
        t_gate = min(loop_gated() for _ in range(3))
        t_base = min(loop_base() for _ in range(3))
        # generous: anything near this bound means the disabled path grew
        # a lookup/allocation (same guard style as faults/monitor/lint)
        assert t_gate < 3.0 * t_base + 0.05, (t_gate, t_base)
        # and nothing was recorded anywhere
        assert obs.timeline().records() == []

    def test_disabled_step_has_no_fence_or_record(self):
        step, x, y = _make_linear_step()
        for _ in range(3):
            step(x, y)
        assert obs._TIMELINE is None or obs.timeline().records() == []


class TestSelfLint:
    def test_obs_package_is_lint_clean(self):
        """CI gate: the shipped obs/ package stays `tpu-lint --all`-clean —
        a trace hazard added to the observability plane fails tier-1."""
        from paddle_tpu import analysis
        findings, n_files = analysis.lint_paths(
            [os.path.join(PKG, "obs")], all_functions=True)
        assert n_files >= 5
        assert findings == [], "\n".join(f.format() for f in findings)
