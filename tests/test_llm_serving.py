"""Continuous-batching LLM serving (serving/llm.py): cached-forward
bit-identity vs the full-sequence forward, the slot-paged KV pool's
zero-steady-state-compile + throughput claims, int8 weight-only / int8 KV
quality, the 'PDSQ'/'PDST' streaming wire protocol, fault containment at
the llm.decode site, and the observability surface."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.monitor as monitor
from paddle_tpu import faults
from paddle_tpu.models.ernie import DECODE_BLOCK
from paddle_tpu.models.gpt import GPTForCausalLM, GPTModel
from paddle_tpu.serving import (EngineStoppedError, LLMConfig, LLMEngine,
                                ServerOverloadedError, ServingError)
from paddle_tpu.serving.llm import _prefill_ladder


def _build_lm(vocab=64, hidden=32, layers=2, heads=4, seed=7):
    paddle.seed(seed)
    gpt = GPTModel(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                   num_heads=heads, max_seq_len=128, dropout=0.0)
    lm = GPTForCausalLM(gpt)
    lm.eval()
    return lm


def _ref_generate(lm, prompt, max_new, with_margins=False):
    """Sequential full-recompute greedy decode — the run_batch-style
    baseline the continuous engine must beat AND match token for token.
    `with_margins` also returns each decision's top-2 margin as a share of
    that step's logit spread (std): how close the reference itself was."""
    toks = list(prompt)
    out, margins = [], []
    for _ in range(max_new):
        logits = lm(paddle.to_tensor(np.asarray([toks], np.int32)))
        last = np.asarray(logits.numpy())[0, -1]
        top2 = np.sort(last)[-2:]
        margins.append(float((top2[1] - top2[0]) / last.std()))
        nxt = int(last.argmax())
        out.append(nxt)
        toks.append(nxt)
    return (out, margins) if with_margins else out


def _serial_generate(eng, prompt, max_new, slot=0, eos=None):
    """The strictly serial loop the scheduler ran before it ran ahead,
    over the engine's own two programs (the engine is not started yet, or
    stopped): the prompt prefilled into `slot`, then one `_decode_pool` a
    token, each step's tokens read before the next is dispatched. A row
    depends on nothing but its own slot, so one sequence at a time is the
    reference for any mix of them."""
    cfg = eng.config
    max_new = min(max_new, max(cfg.max_len - len(prompt), 1))
    with paddle.no_grad():
        toks = [eng._prefill_slot(np.asarray(prompt, np.int32), slot)[0]]
        pos = len(prompt)
        while len(toks) < max_new and toks[-1] != eos and pos < cfg.max_len:
            t = np.zeros(cfg.num_slots, np.int32)
            at = np.zeros(cfg.num_slots, np.int32)
            t[slot], at[slot] = toks[-1], pos
            outs, _ = eng._decode_pool(t, at)
            toks.append(int(np.asarray(outs[0].numpy())[slot]))
            pos += 1
    return toks


def _engine_of(kind, **cfg):
    """An engine over K/V pages ("fp32", "kv_int8"), over a small Brumby
    model's recurrent states ("brumby"), over a small Ling model's mixed
    pool ("ling": states, convolution rows and a latent page) or over a
    small Dots model's latent pages, one a layer ("dots"), not started."""
    if kind == "dots":
        from paddle_tpu.models.dots import DotsForCausalLM, DotsModel
        paddle.seed(7)
        lm = DotsForCausalLM(DotsModel(
            layers=[0, 3, 4], vocab_size=64, hidden_size=32,
            num_attention_heads=2, intermediate_size=48,
            moe_intermediate_size=24, n_routed_experts=16,
            num_experts_per_tok=4, n_group=4, topk_group=2, held=(4, 8),
            q_lora_rank=20, kv_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, router_bias_std=0.1,
            rope_scaling=dict(type="yarn", factor=40, beta_fast=32,
                              beta_slow=1, mscale=1, mscale_all_dim=1,
                              original_max_position_embeddings=16)))
        lm.eval()
    elif kind == "ling":
        from paddle_tpu.models.ling import LingForCausalLM, LingModel
        paddle.seed(5)
        lm = LingForCausalLM(LingModel(
            layers=[0, 4, 5], vocab_size=64, hidden_size=32,
            num_attention_heads=2, head_dim=16, intermediate_size=48,
            moe_intermediate_size=24, num_experts=16, num_experts_per_tok=4,
            n_group=4, topk_group=2, moe_shared_expert_intermediate_size=24,
            held=(4, 8), first_k_dense_replace=1, layer_group_size=3,
            kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, router_bias_std=0.1))
        lm.eval()
    elif kind == "brumby":
        from paddle_tpu.models.brumby import BrumbyForCausalLM, BrumbyModel
        paddle.seed(3)
        lm = BrumbyForCausalLM(BrumbyModel(
            dtype="float32", vocab_size=64, hidden_size=32, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=8, intermediate_size=64,
            gate_bias=3.0))
        lm.eval()
    else:
        lm = _build_lm()
    return LLMEngine(lm, LLMConfig(kv_int8=kind == "kv_int8", **cfg))


def _hold_admissions(eng):
    """Nothing is admitted until the returned event is set, so that
    requests submitted meanwhile are admitted by ONE `_admit` call and no
    later admission drains the pipeline under them."""
    gate, admit = threading.Event(), eng._admit
    eng._admit = lambda: gate.wait(30.0) and admit()
    return gate


@pytest.fixture()
def monitored():
    monitor.reset()
    paddle.set_flags({"FLAGS_monitor": True})
    yield monitor
    paddle.set_flags({"FLAGS_monitor": False})
    monitor.reset()


class TestPrefillLadder:
    def test_powers_of_two_default(self):
        assert _prefill_ladder(64) == [8, 16, 32, 64]
        assert _prefill_ladder(48) == [8, 16, 32, 48]
        assert _prefill_ladder(8) == [8]

    def test_declared_buckets_clamped(self):
        assert _prefill_ladder(32, (16, 64, 32)) == [16, 32]
        # all-invalid declarations fall back to the default ladder
        assert _prefill_ladder(16, (99,)) == [8, 16]


# float32 agreement of the cached path with the full forward. The two run
# the same einsums over different extents (the cache attends its whole
# page under a mask, the full forward only the live prefix), so XLA is free
# to accumulate in another order: a few ulp on logits of magnitude ~10.
# Seen on the installed XLA-CPU: 2.4e-6 absolute. 1e-4 is forty times that
# and four orders below the spread of the logits, so a wrong cache row, a
# wrong position or a stale page (errors of order 1) cannot hide in it.
_F32_ATOL = 1e-4


class TestCachedForwardBitIdentity:
    """The tentpole's correctness anchor: prefill + N cached decode steps
    produce the logits of one full-sequence forward — inside `_F32_ATOL`,
    with the same arg-max at every step. (The class and test keep their
    names from when XLA-CPU happened to make the two bitwise equal at a
    decode block of 2, `models.ernie.DECODE_BLOCK`; jax 0.9's does not,
    and nothing may lean on it.) Both sides of the cache contract
    (`init_cache` / `forward_cached(..., lengths=)`) are called as
    `LLMEngine` calls them."""

    def test_decode_through_the_ragged_kernel_is_the_full_forward(
            self, monkeypatch):
        """The same agreement with the decode step's read routed through
        `kernels.decode_attention` (its backend test answers "a TPU"; the
        kernel itself still sees a CPU and interprets, operands in
        float32; like Brumby's state update it has no derivative, and the
        serve path asks for none). The prompts stay on the dense einsums:
        `engages` is asked with T = the prompt's length, past a decode
        block."""
        import importlib
        da = importlib.import_module("paddle_tpu.kernels.decode_attention")
        asked = []
        engages = da.engages
        monkeypatch.setattr(da, "_on_tpu", lambda: True)
        monkeypatch.setattr(da, "engages", lambda t, dtype: asked.append(
            (t, engages(t, dtype))) or asked[-1][1])
        with paddle.no_grad():      # as the engine runs it: no tape
            self.test_decode_bit_identical_to_full_forward(False, (12, 2, 57))
        assert set(asked) == {(12, False), (57, False), (2, True)}

    @pytest.mark.parametrize("lengths", [(4,), (2, 57)],
                             ids=["one_row", "rows_near_0_and_near_the_end"])
    @pytest.mark.parametrize("lazy", [False, True],
                             ids=["eager", "lazy_eager"])
    def test_decode_bit_identical_to_full_forward(self, lazy, lengths):
        """`lengths`: the prompt length of each cache row. With two rows
        one decode step writes at two different positions — one near 0,
        one near the end of the page — and each row must still be its own
        sequence's full forward."""
        lm = _build_lm()
        paddle.set_flags({"FLAGS_lazy_eager": lazy,
                          "FLAGS_eager_auto_jit": False})
        try:
            rng = np.random.default_rng(5)
            seqs = [rng.integers(0, 64, n).tolist() for n in lengths]
            caches, nxt = [], []
            for prompt in seqs:         # prefill row by row
                ids = paddle.to_tensor(np.asarray([prompt], np.int32))
                logits, cache = lm.forward_cached(
                    ids, lm.init_cache(1, 62),
                    paddle.to_tensor(np.zeros((1,), np.int32)),
                    lengths=paddle.to_tensor(
                        np.asarray([len(prompt)], np.int32)))
                got = np.asarray(logits.numpy())[0]
                # the prefill's logits ARE the full forward's last ones
                np.testing.assert_allclose(
                    got, np.asarray(lm(ids).numpy())[0, -1],
                    rtol=0, atol=_F32_ATOL)
                caches.append(cache)
                nxt.append(int(got.argmax()))
            cache = [paddle.to_tensor(np.concatenate(
                [np.asarray(row[i].numpy()) for row in caches]))
                for i in range(len(caches[0]))]
            for _ in range(4):
                # one token a row; the model widens it to its decode block
                # (row 0 real, the rest junk that the next step overwrites
                # before any mask admits it)
                positions = paddle.to_tensor(
                    np.asarray([len(s) for s in seqs], np.int32))
                logits, cache = lm.forward_cached(
                    paddle.to_tensor(np.asarray(nxt, np.int32)[:, None]),
                    cache, positions)
                for r, seq in enumerate(seqs):
                    seq.append(nxt[r])
                    full = lm(paddle.to_tensor(np.asarray([seq], np.int32)))
                    got = np.asarray(logits.numpy())[r]
                    want = np.asarray(full.numpy())[0, -1]
                    np.testing.assert_allclose(got, want, rtol=0,
                                               atol=_F32_ATOL)
                    assert got.argmax() == want.argmax()
                    nxt[r] = int(got.argmax())
        finally:
            paddle.set_flags({"FLAGS_lazy_eager": False,
                              "FLAGS_eager_auto_jit": False})


class _OnlyTheContract(paddle.nn.Layer):
    """Neither GPT nor Brumby: a layer that answers `cache_tag`,
    `init_cache` and `forward_cached` and has nothing else `LLMEngine`
    could ask for (no `.gpt`, no `.brumby`, no class it knows)."""

    cache_tag = "kv_pool"

    def __init__(self, inner):
        super().__init__()
        self.inner = inner

    def init_cache(self, batch_size, max_len, dtype="float32"):
        return self.inner.init_cache(batch_size, max_len, dtype)

    def forward_cached(self, tokens, cache, positions, lengths=None):
        return self.inner.forward_cached(tokens, cache, positions, lengths)


class TestCacheContractSeam:
    """The engine holds a model that answers the cache contract and
    nothing else: no adapter, no dispatch on the model's class, no block
    width or scales of its own."""

    @pytest.mark.parametrize("kv_int8", [False, True],
                             ids=["fp32", "kv_int8"])
    def test_engine_serves_any_model_that_answers_the_contract(self,
                                                               kv_int8):
        lm = _build_lm()
        cfg = dict(num_slots=2, max_len=24, max_new_tokens=6,
                   kv_int8=kv_int8)
        prompts = [[5, 9, 2], [7, 1, 1, 8, 3, 4, 6, 2, 9]]
        own = LLMEngine(lm, LLMConfig(**cfg))
        want = [_serial_generate(own, p, 6) for p in prompts]
        eng = LLMEngine(_OnlyTheContract(lm), LLMConfig(**cfg)).start()
        try:
            streams = [eng.submit(p) for p in prompts]
            got = [s.result(timeout=120.0) for s in streams]
        finally:
            eng.stop()
        assert got == [("done", toks) for toks in want]
        # the pool is what the model said a sequence keeps, and only that
        assert [t.shape for t in eng._pool] \
            == [t.shape for t in lm.init_cache(2, 24, eng._dtype)]
        assert eng.stats()["page_len"] == 24 + DECODE_BLOCK

    def test_engine_source_knows_no_model_and_no_block_width(self):
        import dataclasses
        import inspect
        import re

        from paddle_tpu.serving import llm
        fields = {f.name for f in dataclasses.fields(LLMConfig)}
        assert "decode_block" not in fields
        assert not hasattr(LLMConfig, "from_flags")
        src = inspect.getsource(llm)
        code = src.replace(llm.__doc__, "")
        assert not re.search(r"isinstance\(\s*model", code)
        assert not re.search(r"^\s*(from|import)\s+[.\w]*models", code,
                             re.M), "the engine imports no model"
        for gone in ("_PagedKV", "decode_block", "from_flags", "_scales",
                     "llm_scale_write"):
            assert gone not in src, gone
        # a config is optional, and the default is the dataclass's own
        assert LLMEngine(_build_lm()).config == LLMConfig()


class TestContinuousBatching:
    def test_zero_steady_state_compiles_and_obs(self, monitored):
        """THE acceptance scenario: 8 concurrent variable-length requests
        through one warmed engine — exact greedy tokens, ZERO steady-state
        compiles (retrace counters flat), and the metrics/census surface
        populated. No speed is asserted: a ratio of CPU wall times under
        six workers is a count, never a speed; `benchmarks/` measures."""
        paddle.set_flags({"FLAGS_mem_census": True})
        lm = _build_lm()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 64, size=int(n)).tolist()
                   for n in rng.integers(2, 14, size=8)]
        max_new = 16
        refs = [_ref_generate(lm, p, max_new) for p in prompts]

        eng = LLMEngine(lm, LLMConfig(num_slots=8, max_len=32,
                                      max_new_tokens=max_new)).start()
        try:
            c0 = {k: v for k, v in monitor.snapshot()["counters"].items()
                  if "compile" in k or "retrace" in k}
            streams = [eng.submit(p) for p in prompts]
            results = [s.result(timeout=120.0) for s in streams]
            c1 = {k: v for k, v in monitor.snapshot()["counters"].items()
                  if "compile" in k or "retrace" in k}

            for (status, toks), ref in zip(results, refs):
                assert status == "done"
                assert toks == ref  # greedy path is bit-exact -> equal
            assert c1 == c0, f"steady-state compiles: {c0} -> {c1}"

            snap = monitor.snapshot()
            assert snap["counters"]["llm.requests"] == 8
            assert snap["counters"]["llm.tokens_generated"] == 8 * max_new
            # one step deep: a request's 15 decode rows ride 15 steps,
            # shared with whoever else is live, and since every stream
            # ends on its budget, which the host knows ahead, no row is
            # computed in vain
            steps = snap["counters"]["llm.decode.steps"]
            assert max_new - 1 <= steps <= 8 * (max_new - 1)
            assert 0 < snap["counters"]["llm.decode.ahead"] < steps
            assert snap["counters"]["llm.decode.discarded"] == 0
            assert snap["counters"]["llm.evictions.length"] == 8
            assert "llm.slots_active" in snap["gauges"]
            assert snap["histograms"]["llm.ttft_ms"]["count"] == 8
            assert snap["histograms"]["llm.inter_token_ms"]["count"] > 0

            # pool bytes flow through the memory census under the
            # kv_pool tag and out as the mem.kv_pool.bytes gauge
            from paddle_tpu.obs import memory as mem
            rec = mem.census()
            assert rec["tags"].get("kv_pool", {}).get("bytes", 0) \
                == eng.kv_pool_bytes() > 0
            assert monitor.snapshot()["gauges"]["mem.kv_pool.bytes"] \
                == eng.kv_pool_bytes()
        finally:
            eng.stop()
            paddle.set_flags({"FLAGS_mem_census": False})

    def test_monitor_show_renders_llm_metrics(self, monitored, tmp_path,
                                              capsys):
        lm = _build_lm()
        eng = LLMEngine(lm, LLMConfig(num_slots=2, max_len=16,
                                      max_new_tokens=4)).start()
        try:
            assert eng.submit([3, 1, 4]).result(timeout=60.0)[0] == "done"
        finally:
            eng.stop()
        p = monitor.export_json(str(tmp_path / "llm_snap.json"))
        assert monitor._main(["show", p]) == 0
        out = capsys.readouterr().out
        assert "llm.tokens_generated" in out
        assert "llm.ttft_ms" in out

    def test_interleaving_later_short_request_finishes_first(self):
        lm = _build_lm()
        eng = LLMEngine(lm, LLMConfig(num_slots=2, max_len=64,
                                      max_new_tokens=48)).start()
        done_at = {}
        try:
            long_s = eng.submit([1, 2, 3], max_new_tokens=40)
            while not long_s.tokens:  # admitted and producing
                time.sleep(0.005)
            short_s = eng.submit([4, 5], max_new_tokens=3)
            for name, s in (("long", long_s), ("short", short_s)):
                threading.Thread(
                    target=lambda n=name, st=s: done_at.__setitem__(
                        n, (st.result(timeout=120.0), time.monotonic())),
                    daemon=True).start()
            deadline = time.monotonic() + 120.0
            while len(done_at) < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert done_at["short"][0][0] == "done"
            assert done_at["long"][0][0] == "done"
            # admitted later, finished first: continuous batching, not FIFO
            assert done_at["short"][1] < done_at["long"][1]
        finally:
            eng.stop()

    def test_submit_validation_and_shedding(self, monkeypatch):
        lm = _build_lm()
        eng = LLMEngine(lm, LLMConfig(num_slots=1, max_len=16,
                                      max_new_tokens=4)).start()
        try:
            with pytest.raises(ServingError):
                eng.submit(list(range(17)))  # beyond the largest bucket
            with pytest.raises(ServingError):
                eng.submit([])
            from paddle_tpu.obs import slo as slo_mod
            monkeypatch.setattr(slo_mod, "_ENABLED", True)
            monkeypatch.setattr(slo_mod, "should_shed", lambda: True)
            with pytest.raises(ServerOverloadedError):
                eng.submit([1, 2])
        finally:
            eng.stop()
        with pytest.raises(EngineStoppedError):
            eng.submit([1, 2])

    def test_stop_releases_model_and_pool(self):
        """stop() must break the StaticFunction <-> jax.jit reference
        cycle: once the engine is dropped, the model weights and KV pool
        are collectable — a leaked engine would silently pin a model's
        worth of HBM per deploy cycle (and poison the census)."""
        import gc
        import weakref
        lm = _build_lm()
        eng = LLMEngine(lm, LLMConfig(num_slots=2, max_len=16,
                                      max_new_tokens=4)).start()
        assert eng.submit([1, 2, 3]).result(timeout=60.0)[0] == "done"
        eng.stop()
        ref = weakref.ref(lm)
        del lm, eng
        gc.collect()
        assert ref() is None, "model survived engine teardown"


class TestRunAhead:
    """The scheduler dispatches step n+1 on step n's tokens, still on the
    device, before it reads step n: the same tokens as the serial loop,
    none after an EOS, none lost to an admission, no row past a budget."""

    @pytest.mark.parametrize("kind", ["fp32", "kv_int8", "brumby", "dots"])
    def test_streams_are_the_serial_loops_token_for_token(self, kind):
        """More requests than slots, prompts and budgets of mixed lengths,
        submitted while the engine decodes: slots are reused, admissions
        drain the pipeline, sequences end on different steps."""
        eng = _engine_of(kind, num_slots=3, max_len=32,
                         prefill_buckets=(16,), max_new_tokens=8)
        rng = np.random.default_rng(11)
        asks = [(rng.integers(0, 64, int(n)).tolist(), int(m))
                for n, m in zip((2, 14, 5, 9, 3, 11, 7), (12, 3, 9, 1, 7, 5, 10))]
        refs = [_serial_generate(eng, p, m, slot=i % 3)
                for i, (p, m) in enumerate(asks)]
        assert [len(r) for r in refs] == [m for _, m in asks]
        eng.start()
        try:
            streams = []
            for p, m in asks:
                streams.append(eng.submit(p, max_new_tokens=m))
                time.sleep(0.01)        # arrive between steps, not at once
            for s, ref in zip(streams, refs):
                assert s.result(timeout=120.0) == ("done", ref)
        finally:
            eng.stop()

    def test_nothing_after_eos_reaches_a_stream(self, monitored):
        """An EOS is learnt one dispatch late: the row already in flight
        for that sequence is discarded and counted, its slot is free, and
        the next request in that slot is served correctly."""
        cfg = dict(num_slots=4, max_len=48, prefill_buckets=(16,),
                   max_new_tokens=16)
        eng = _engine_of("fp32", **cfg)
        prompts = [[3, 1, 4], [9], [7, 7, 2, 50], [11, 2]]
        free_run = [_serial_generate(eng, p, 16) for p in prompts]
        # the token that ends most streams after their first token and
        # before their budget: the model does emit it
        eos = max(range(64), key=lambda t: sum(
            t in r[1:-1] and t != r[0] for r in free_run))
        refs = [_serial_generate(eng, p, 16, eos=eos) for p in prompts]
        ran_on = sum(1 < len(r) < 16 for r in refs)   # ended by EOS, early
        assert ran_on >= 1 and all(eos not in r[:-1] for r in refs)
        eng = _engine_of("fp32", eos_token_id=eos, **cfg)
        # all four are admitted by one call, so that no admission's drain
        # reads an EOS (a drained step has no successor to discard)
        gate = _hold_admissions(eng)
        eng.start()
        try:
            streams = [eng.submit(p) for p in prompts]
            gate.set()
            for s, ref in zip(streams, refs):
                assert s.result(timeout=120.0) == ("done", ref)
            snap = monitor.snapshot()["counters"]
            assert snap["llm.decode.discarded"] == ran_on
            assert snap["llm.evictions.eos"] == \
                sum(r[-1] == eos for r in refs)
            assert eng.stats()["free"] == 4
            # a slot that took a discarded row serves the next sequence
            again = [eng.submit(p) for p in prompts]
            for s, ref in zip(again, refs):
                assert s.result(timeout=120.0) == ("done", ref)
        finally:
            eng.stop()

    def test_admission_under_a_step_in_flight_and_the_share_ahead(
            self, monitored):
        """50 decode steps with one admission in the middle. The admitted
        request starts from its prefill's first token, not from the junk
        row its slot rode along as; the admission drains the pipeline
        once; every dispatch but the two that follow an empty pipeline is
        made ahead of the read."""
        eng = _engine_of("fp32", num_slots=2, max_len=64,
                         prefill_buckets=(16,), max_new_tokens=51)
        long_ref = _serial_generate(eng, [1, 2, 3], 51)
        short_ref = _serial_generate(eng, [4, 5], 5, slot=1)
        eng.start()
        try:
            long_s = eng.submit([1, 2, 3])
            deadline = time.monotonic() + 60.0
            while len(long_s.tokens) < 10:       # steps are in flight
                assert time.monotonic() < deadline
                time.sleep(0.001)
            short_s = eng.submit([4, 5], max_new_tokens=5)
            assert short_s.result(timeout=120.0) == ("done", short_ref)
            assert long_s.result(timeout=120.0) == ("done", long_ref)
        finally:
            eng.stop()
        snap = monitor.snapshot()["counters"]
        assert snap["llm.decode.steps"] == 50
        assert snap["llm.decode.drains"] == 1
        assert snap["llm.decode.ahead"] == 48
        assert snap["llm.decode.ahead"] / snap["llm.decode.steps"] >= 0.8
        assert snap["llm.decode.discarded"] == 0

    def test_a_stop_that_does_not_wait_reads_the_step_in_flight(
            self, monitored):
        """`stop(drain=False)` under a decoding stream: the step in flight
        is read and emitted (a drain), then the stream is told "stopped";
        what it got is a prefix of the serial loop's tokens."""
        eng = _engine_of("fp32", num_slots=2, max_len=64,
                         prefill_buckets=(16,), max_new_tokens=60)
        ref = _serial_generate(eng, [1, 2, 3], 60)
        eng.start()
        stream = eng.submit([1, 2, 3])
        deadline = time.monotonic() + 60.0
        while len(stream.tokens) < 5:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        eng.stop(drain=False)
        status, toks = stream.result(timeout=10.0)
        assert status == "stopped" and eng._flight is None
        assert 5 <= len(toks) < 60 and toks == ref[:len(toks)]
        snap = monitor.snapshot()["counters"]
        assert snap["llm.decode.drains"] == 1
        # every dispatched step was read: the tokens are all accounted for
        assert snap["llm.tokens_generated"] == len(toks) \
            == snap["llm.decode.steps"] + 1

    def test_a_sequence_at_max_len_is_not_dispatched_past_it(self,
                                                            monitored):
        """A prompt of 12 in a page of 16 gets 4 tokens whatever it asks
        for: rows at positions 12, 13, 14, then its slot rides along at
        position 0 while its neighbour decodes on."""
        eng = _engine_of("fp32", num_slots=2, max_len=16,
                         prefill_buckets=(16,), max_new_tokens=32)
        seen, real = [], eng._decode_pool

        def watched(tokens, positions):
            seen.append(np.array(positions))
            return real(tokens, positions)

        eng._decode_pool = watched
        gate = _hold_admissions(eng)
        eng.start()
        del seen[:]                           # the warm-up's two steps
        try:
            a = eng.submit(list(range(12)))
            b = eng.submit([5], max_new_tokens=10)
            gate.set()
            assert a.result(timeout=60.0)[0] == "done"
            assert b.result(timeout=60.0)[0] == "done"
        finally:
            eng.stop()
        assert len(a.tokens) == 4 and len(b.tokens) == 10
        assert len(seen) == 9                 # b's rows; a rode three
        assert max(int(p.max()) for p in seen) == 14 < eng.config.max_len
        slot = int(np.argmax(seen[0]))
        assert [int(p[slot]) for p in seen] == [12, 13, 14] + [0] * 6
        snap = monitor.snapshot()["counters"]
        assert snap["llm.decode.steps"] == 9
        assert snap["llm.decode.discarded"] == 0


class TestQuantizedDecode:
    def test_int8_weight_only_and_kv_top1_agreement(self):
        """quant="int8" + kv_int8 against the fp32 full-recompute reference
        on fixed prompts, judged per DECISION, not per position.

        Greedy decoding cascades: one flipped arg-max changes the context
        of every later token of that request, so position-wise agreement
        measures where the first flip fell, not how often int8 flips (one
        flip at step 1 of a 10-token request reads as 10% agreement). What
        int8 can be held to is each decision it made on the SAME prefix as
        the reference: every token up to and including a request's first
        divergence. Threshold: >= 95% of those decisions agree, and a
        divergence is only admitted at a near-tie — where the fp32
        reference's own top-2 margin is under 5% of its logit spread.
        Symmetric 8-bit grids (127 steps per scale, on the weights and on
        K/V) move this model's last-position logits by 0.5-0.9% of their
        spread rms and 2.8% at the worst element (measured against fp32 on
        five prompts, installed XLA-CPU): a margin inside about twice that
        is a coin toss for ANY such quantizer, a larger one that flips is
        a bug. Here one of the 42 decisions flips (97.6%), at a margin of
        1.9% of the spread."""
        lm_ref = _build_lm(seed=11)
        prompts = [[5, 17, 3], [11, 2, 9, 4, 44, 7], [1], [23, 8, 30, 2],
                   [9, 9, 1, 63]]
        refs = [_ref_generate(lm_ref, p, 10, with_margins=True)
                for p in prompts]
        lm_q = _build_lm(seed=11)  # same weights, quantized in-engine
        eng = LLMEngine(lm_q, LLMConfig(num_slots=4, max_len=32,
                                        max_new_tokens=10, quant="int8",
                                        kv_int8=True)).start()
        try:
            agree = decisions = 0
            for p, (ref, margins) in zip(prompts, refs):
                status, toks = eng.submit(p).result(timeout=120.0)
                assert status == "done" and len(toks) == len(ref)
                first = next((i for i, (a, b) in enumerate(zip(toks, ref))
                              if a != b), None)
                if first is None:
                    agree += len(ref)
                    decisions += len(ref)
                    continue
                agree += first
                decisions += first + 1
                assert margins[first] < 0.05, (
                    f"int8 flipped a clear decision: prompt {p}, step "
                    f"{first}, fp32 top-2 margin {margins[first]:.3f} of "
                    "the logit spread")
            assert agree / decisions >= 0.95, \
                f"top-1 agreement {agree}/{decisions} decisions"
            # the int8 pool really is ~4x smaller than the fp32 one
            fp32_pool = 2 * 2 * 4 * eng.stats()["page_len"] * 4 * 8 * 4
            assert eng.kv_pool_bytes() < fp32_pool / 2
        finally:
            eng.stop()

    def test_quant_weight_only_storage_swap(self):
        from paddle_tpu import nn
        from paddle_tpu.parallel.mp_layers import (ColumnParallelLinear,
                                                   RowParallelLinear)
        from paddle_tpu.quantization import quant_weight_only

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                paddle.seed(3)
                self.fc1 = nn.Linear(16, 32)
                self.col = ColumnParallelLinear(32, 32, gather_output=True)
                self.row = RowParallelLinear(32, 8,
                                             input_is_parallel=False)

            def forward(self, x):
                return self.row(self.col(self.fc1(x)))

        net = Net()
        x = paddle.to_tensor(np.random.default_rng(0).normal(
            size=(2, 16)).astype(np.float32))
        want = np.asarray(net(x).numpy())
        quant_weight_only(net)
        for layer in (net.fc1, net.col, net.row):
            assert "weight" not in layer._parameters
            assert str(layer.wo_weight_q._value.dtype) == "int8"
        # mp sharding annotations survive on the quantized storage
        sd = net.state_dict()
        assert any(k.endswith("wo_weight_q") for k in sd)
        got = np.asarray(net(x).numpy())
        np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)
        # the transient dequant weight did not leak into the layer
        assert "weight" not in net.fc1._parameters

    def test_quant_weight_only_rejects_weightless_model(self):
        from paddle_tpu import nn
        from paddle_tpu.quantization import quant_weight_only
        with pytest.raises(ValueError):
            quant_weight_only(nn.LayerNorm(8))


class TestStreamingWire:
    def test_socket_streaming_interleaving_and_legacy_verbs(self):
        """e2e over the wire: a client receives tokens incrementally
        ('PDST' frames) while generation is still running; a short
        request admitted later finishes first; the pre-streaming verbs on
        the same server are untouched."""
        from paddle_tpu.inference.server import (PredictorClient,
                                                 PredictorServer)
        lm = _build_lm()
        eng = LLMEngine(lm, LLMConfig(num_slots=2, max_len=64,
                                      max_new_tokens=48))
        srv = PredictorServer(lambda x: x * 2.0, llm_engine=eng).start()
        out = {}
        first_tok = threading.Event()

        def on_long_token(i, t):
            first_tok.set()
            out.setdefault("arrivals", []).append(time.monotonic())
            if i == 0:
                time.sleep(0.05)  # hold the stream so short overlaps

        def run_long():
            cli = PredictorClient(srv.host, srv.port)
            status, toks = cli.generate([1, 2, 3], max_new_tokens=36,
                                        on_token=on_long_token)
            out["long"] = (status, toks, time.monotonic())
            cli.close()

        def run_short():
            # long is mid-generation: its first token has streamed
            assert first_tok.wait(timeout=60.0)
            cli = PredictorClient(srv.host, srv.port)
            status, toks = cli.generate([4, 5], max_new_tokens=3)
            out["short"] = (status, toks, time.monotonic())
            cli.close()

        try:
            t_long = threading.Thread(target=run_long, daemon=True)
            t_long.start()
            t_short = threading.Thread(target=run_short, daemon=True)
            t_short.start()
            t_long.join(timeout=120.0)
            t_short.join(timeout=120.0)
            assert out["long"][0] == 0 and out["short"][0] == 0
            assert len(out["long"][1]) == 36 and len(out["short"][1]) == 3
            # tokens arrived over time, not in one terminal burst
            arrivals = out["arrivals"]
            assert arrivals[-1] - arrivals[0] > 0.01
            # interleaving: the later short request completed first
            assert out["short"][2] < out["long"][2]

            cli = PredictorClient(srv.host, srv.port)
            st, payload = cli.run([np.ones((1, 4), np.float32)])
            assert st == 0
            np.testing.assert_allclose(payload[0], 2.0)
            assert cli.health()["llm"]["slots"] == 2
            cli.close()
        finally:
            srv.stop()

    def test_stream_without_llm_engine_is_clean_error(self):
        from paddle_tpu.inference.server import (PredictorClient,
                                                 PredictorServer)
        from paddle_tpu.utils.net import STATUS_ERROR
        srv = PredictorServer(lambda xs: xs).start()
        try:
            cli = PredictorClient(srv.host, srv.port)
            status, msg = cli.generate([1, 2, 3])
            assert status == STATUS_ERROR
            assert "llm" in msg
            cli.close()
        finally:
            srv.stop()


class TestFaultContainment:
    def test_decode_error_evicts_only_injected_sequence(self, monitored):
        """Chaos drill at llm.decode: an injected mid-decode error takes
        down exactly ONE in-flight sequence; its slot is reclaimed and
        the other streams finish with their exact reference tokens."""
        lm = _build_lm()
        prompts = [[3, 1], [7, 7, 2], [9]]
        refs = [_ref_generate(lm, p, 12) for p in prompts]
        eng = LLMEngine(lm, LLMConfig(num_slots=3, max_len=32,
                                      max_new_tokens=12)).start()
        try:
            streams = [eng.submit(p) for p in prompts]
            deadline = time.monotonic() + 30.0
            while eng.stats()["active"] < 3:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            with faults.inject("llm.decode:error:times=1"):
                results = [s.result(timeout=120.0) for s in streams]
            statuses = [r[0] for r in results]
            assert statuses.count("error") == 1
            assert statuses.count("done") == 2
            for (status, toks), ref in zip(results, refs):
                if status == "done":
                    assert toks == ref  # survivors unperturbed, bit-exact
            assert eng.stats()["free"] == 3  # all slots reclaimed
            snap = monitor.snapshot()["counters"]
            assert snap["llm.evictions.error"] == 1
        finally:
            eng.stop()

    def test_deadline_eviction_mid_decode(self, monitored):
        lm = _build_lm()
        eng = LLMEngine(lm, LLMConfig(num_slots=2, max_len=64,
                                      max_new_tokens=48)).start()
        try:
            with faults.inject("llm.decode:delay:delay=0.03"):
                status, toks = eng.submit(
                    [5, 6], deadline_ms=150.0).result(timeout=120.0)
            assert status == "deadline"
            assert 0 < len(toks) < 48  # some tokens streamed, then cut
            snap = monitor.snapshot()["counters"]
            assert snap["llm.evictions.deadline"] == 1
        finally:
            eng.stop()


@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp32", "kv_int8"])
class TestDonatedPool:
    """The decode program takes the KV pool donated (it updates the pages
    in place and aliases them out): the engine must never be left holding
    a page the program consumed."""

    def _engine(self, kv_int8, **kw):
        cfg = dict(num_slots=3, max_len=32, max_new_tokens=8,
                   kv_int8=kv_int8)
        cfg.update(kw)
        return LLMEngine(_build_lm(), LLMConfig(**cfg))

    @staticmethod
    def _live(eng):
        return not any(t._value.is_deleted() for t in eng._pool)

    def test_pool_is_live_after_warmup_admission_and_step(self, kv_int8,
                                                          monitored):
        eng = self._engine(kv_int8)
        before = [t._value for t in eng._pool]
        eng._warmup()
        assert self._live(eng)
        assert all(a.is_deleted() for a in before)  # consumed, not copied
        eng.start()
        try:
            stream = eng.submit([3, 1, 4])
            stream.result(timeout=60.0)
            assert self._live(eng)                   # admission + steps
            assert eng.submit([2, 7]).result(timeout=60.0)[0] == "done"
            assert self._live(eng)
        finally:
            eng.stop()
        snap = monitor.snapshot()["counters"]
        assert snap["llm.decode.steps"] > 0
        assert snap["llm.decode.pool_donated"] == snap["llm.decode.steps"]
        assert snap["span.llm.decode.dispatch.count"] == \
            snap["llm.decode.steps"]

    def test_introspection_from_another_thread_never_sees_a_dead_page(
            self, kv_int8, monitored):
        """Over decode steps AND admissions, which give the pool away
        too: three streams of different lengths, then three more that are
        admitted as slots come free under the streams still live."""
        eng = self._engine(kv_int8, max_new_tokens=24).start()
        want = eng.kv_pool_bytes()
        errors, stop = [], threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    assert eng.kv_pool_bytes() == want
                    assert eng.stats()["kv_pool_bytes"] == want
                except Exception as e:   # noqa: BLE001 - the test's catch
                    errors.append(repr(e))
                    return

        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        try:
            streams = [eng.submit(p, max_new_tokens=m) for p, m in
                       (([5, 6], 6), ([1], 12), ([9, 9, 9], 24))]
            streams += [eng.submit(p) for p in ([2, 4], [8] * 9, [3])]
            assert all(s.result(timeout=120.0)[0] == "done"
                       for s in streams)
        finally:
            stop.set()
            t.join(timeout=10.0)
            eng.stop()
        assert not errors
        snap = monitor.snapshot()["counters"]
        assert snap["llm.slot_write.donated"] == 6
        assert snap["llm.decode.pool_donated"] == snap["llm.decode.steps"]

    def test_failure_at_the_read_leaves_a_serving_engine(self, kv_int8):
        """The error surfaces where the host reads a step's tokens, one
        dispatch later than it used to: the step after it is in flight
        and unread, the sequences are lost, the next request is served."""
        from paddle_tpu.core.tensor import Tensor

        class Unreadable(Tensor):
            def numpy(self):
                raise RuntimeError("device fell over under the read")

        eng = self._engine(kv_int8)
        ref = _serial_generate(eng, [4, 2], 8)
        real = eng._decode
        armed = {"n": 0}

        def poisoned(*a, **kw):
            out = list(real(*a, **kw))
            if armed["n"]:
                armed["n"] -= 1
                out[0] = Unreadable(out[0]._value)
            return out

        eng._decode = poisoned
        eng.start()
        armed["n"] = 1
        try:
            status, toks = eng.submit([4, 2]).result(timeout=60.0)
            assert status == "error" and toks == ref[:1]
            assert self._live(eng) and eng._flight is None
            assert eng.stats()["free"] == 3
            assert eng.submit([4, 2]).result(timeout=60.0) == ("done", ref)
        finally:
            eng._decode = real
            eng.stop()

    def test_failure_inside_the_dispatch_leaves_a_serving_engine(
            self, kv_int8):
        """The program consumed the pool and the dispatch then raised:
        the sequences in flight are lost, the next request is served."""
        eng = self._engine(kv_int8)
        ref = _ref_generate(eng.lm, [4, 2], 8) if not kv_int8 else None
        real = eng._decode
        armed = {"n": 0}

        def failing(*a, **kw):
            out = real(*a, **kw)
            if armed["n"]:
                armed["n"] -= 1
                raise RuntimeError("device fell over after the dispatch")
            return out

        eng._decode = failing
        eng.start()
        armed["n"] = 1
        try:
            status, _ = eng.submit([4, 2]).result(timeout=60.0)
            assert status == "error"
            assert self._live(eng)
            assert eng.stats()["free"] == 3
            status, toks = eng.submit([4, 2]).result(timeout=60.0)
            assert status == "done" and len(toks) == 8
            if ref is not None:
                assert toks == ref
        finally:
            eng._decode = real
            eng.stop()


@pytest.mark.parametrize("kind", ["fp32", "kv_int8", "brumby", "ling",
                                  "dots"])
class TestSlotWriteProgram:
    """An admission writes its slot through ONE program,
    `jit_llm_slot_write`, that takes the whole pool donated: whatever the
    model keeps a sequence (pages, int8 pages and their scales, states, a
    mixed pool), whichever slot and whichever bucket."""

    CFG = dict(num_slots=3, max_len=32, max_new_tokens=6,
               prefill_buckets=(8, 16, 32))

    @staticmethod
    def _read(pool):
        return [np.asarray(t.numpy()) for t in pool]

    @pytest.mark.parametrize("slot", [0, 2])
    def test_an_admission_writes_its_slot_and_no_other(self, kind, slot,
                                                       monitored):
        import jax.numpy as jnp

        from paddle_tpu.core.tensor import Tensor
        eng = _engine_of(kind, warmup_on_start=False, **self.CFG)
        n = len(eng._pool)
        # a pool in which every slot holds something of its own (copied
        # to the device: a host view of a CPU buffer would pin it)
        rng = np.random.default_rng(11)
        before = [rng.integers(-100, 100, size=t.shape).astype(
            t._value.dtype) for t in eng._pool]
        eng._pool = [Tensor(jnp.array(a)) for a in before]
        old = [t._value for t in eng._pool]
        prompt = np.asarray([5, 9, 2, 7, 1, 1, 8, 3, 4, 6, 2], np.int32)
        with paddle.no_grad():
            padded = np.zeros((1, 16), np.int32)
            padded[0, :prompt.size] = prompt
            fresh = self._read(eng._prefill(
                Tensor(jnp.asarray(padded)),
                Tensor(jnp.full((1,), prompt.size, jnp.int32)))[2:2 + n])
            _, bucket, *_ = eng._prefill_slot(prompt, slot)
        assert bucket == 16 and len(eng._pool) == n
        others = [s for s in range(3) if s != slot]
        for was, row, now in zip(before, fresh, self._read(eng._pool)):
            assert row.shape == (1,) + was.shape[1:] and row.dtype == was.dtype
            assert np.array_equal(now[slot], row[0])
            assert np.array_equal(now[others], was[others])
        # the old pool was given away, not copied
        assert all(a.is_deleted() for a in old)
        snap = monitor.snapshot()["counters"]
        assert snap["llm.slot_write.donated"] == 1
        assert snap["span.llm.slot_write.count"] == 1

    def test_one_executable_serves_every_slot_and_bucket(self, kind,
                                                         monitored):
        """Warmed at start, the first admission compiles nothing; slots
        and buckets are data; no eager op is left in an admission."""
        eng = _engine_of(kind, **self.CFG).start()
        ledger = eng._slot_write.forward._ledger
        assert len(ledger.seen_sigs()) == 1          # `_warmup` ran it
        watched = lambda: {
            k: v for k, v in monitor.snapshot()["counters"].items()
            if "compile" in k or "trace" in k or k.startswith("dispatch.op")}
        c0 = watched()
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, 64, size=m).tolist()
                   for m in (3, 12, 20, 9, 5)]      # buckets 8, 16, 32
        try:
            done = [s.result(timeout=120.0)
                    for s in [eng.submit(p) for p in prompts]]
            assert watched() == c0
        finally:
            eng.stop()
        assert [status for status, _ in done] == ["done"] * 5
        assert len(ledger.seen_sigs()) == 0          # released with the rest
        snap = monitor.snapshot()["counters"]
        assert snap["llm.prefill.requests"] == 5
        assert snap["llm.slot_write.donated"] == 5
        assert snap["span.llm.slot_write.count"] == 5
        assert "dispatch.op.llm_slot_write" not in snap
        assert snap["llm.warmup_runs"] == len(eng.buckets) + 3
        # what the streams hold is what the serial loop makes of each prompt
        own = _engine_of(kind, warmup_on_start=False, **self.CFG)
        assert [toks for _, toks in done] == [
            _serial_generate(own, p, 6, slot=i % 3)
            for i, p in enumerate(prompts)]
        assert len(own._slot_write.forward._ledger.seen_sigs()) == 1

    def test_a_write_that_raises_leaves_a_serving_engine_and_a_zero_pool(
            self, kind):
        """The write consumed the pool and the dispatch then raised: the
        sequence being admitted and every live one are lost, the pool is
        zeros again, the next request is served."""
        eng = _engine_of(kind, warmup_on_start=False,
                         **{**self.CFG, "max_new_tokens": 24})
        ref = _serial_generate(eng, [4, 2], 24)
        real = eng._slot_write
        armed = {"n": 0}

        def failing(*a, **kw):
            out = real(*a, **kw)
            if armed["n"]:
                armed["n"] -= 1
                raise RuntimeError("device fell over after the write")
            return out

        eng._slot_write = failing
        eng.start()
        try:
            live = eng.submit([7, 7, 1])
            assert next(live.iter(timeout=60.0)) is not None  # it is live
            armed["n"] = 1
            status, toks = eng.submit([4, 2]).result(timeout=60.0)
            assert (status, toks) == ("error", [])
            assert live.result(timeout=60.0)[0] == "error"
            assert eng.stats()["free"] == 3 and eng.stats()["active"] == 0
            assert TestDonatedPool._live(eng)
            assert all(np.array_equal(a, z) for a, z in zip(
                self._read(eng._pool), self._read(eng._zero_pool())))
            assert eng.submit([4, 2]).result(timeout=60.0) == ("done", ref)
        finally:
            eng._slot_write = real
            eng.stop()
