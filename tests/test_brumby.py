"""Brumby (power-retention layers): the kernel's three forms, the model
against the plain reference, and a tiny model through `LLMEngine`'s
recurrent-state path. CPU, small sizes; the Pallas step kernel runs
interpreted here and is compiled for a described v5e in
tests/test_tpu_compile.py."""
import importlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import brumby as reference
from paddle_tpu import monitor
from paddle_tpu.models.brumby import BrumbyForCausalLM, BrumbyModel
from paddle_tpu.models.ernie import DECODE_BLOCK
from paddle_tpu.models.gpt import GPTForCausalLM, GPTModel
from paddle_tpu.serving import LLMConfig, LLMEngine
from paddle_tpu.serving.engine import ServingError

pr = importlib.import_module("paddle_tpu.kernels.power_retention")

B, T, H, G, D = 2, 37, 4, 2, 8


def _qkvg(seed=0, t=T, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, t, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, t, G, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, t, G, D)), dtype)
    log_g = jnp.asarray(-np.abs(rng.normal(size=(B, t, G))) * 0.1,
                        jnp.float32)
    return q, k, v, log_g


def _steps(q, k, v, log_g, impl="jnp", upto=None, held=lambda a: a):
    """Token by token from an empty state: y [B, t, H, d], final state.
    `impl` "pallas": the kernel, interpreted (off a TPU the public function
    takes `_step_jnp`); `held`: what a state is rounded to between steps."""
    rows = pr.state_rows(q.shape[-1])
    state = (jnp.zeros((B, G, q.shape[-1], rows)), jnp.zeros((B, G, rows)))
    ys = []
    step = {"jnp": pr._step_jnp, "pallas": pr._step_pallas}[impl]
    with mock.patch.object(pr, "_step_jnp", step):
        for t in range(upto or q.shape[1]):
            y, state = pr.power_retention_step(q[:, t], k[:, t], v[:, t],
                                               log_g[:, t], state)
            state = tuple(held(a) for a in state)
            ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("d", [4, 8, 16, 128])
def test_phi_is_the_feature_map_of_the_squared_dot_product(d):
    rng = np.random.default_rng(d)
    a = jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
    got = (pr.phi(a) * pr.phi(b)).sum(-1)
    want = (a * b).sum(-1) ** 2
    np.testing.assert_allclose(got, want, atol=1e-5 * float(want.max()))
    rows = pr.state_rows(d)
    assert pr.phi(a).shape == (5, rows) and rows % 128 == 0
    assert rows - d * (d + 1) // 2 < 128
    # the rows past d(d+1)/2 hold nothing
    assert not np.any(np.asarray(pr.phi(a))[:, d * (d + 1) // 2:])


def test_state_rows_at_the_published_head_size():
    assert pr.state_rows(128) == 8320        # 8256 rounded up to 65 x 128
    assert pr._tile_rows(8320, 128) == 1664  # 13 x 128 lanes a tile


@pytest.mark.parametrize("chunk", [None, 10, 16, 37, 64],
                         ids=lambda c: f"chunk{c}")
def test_chunked_form_is_the_attention_form(chunk):
    """Grouped heads, a gate, and chunks that do not divide the length."""
    q, k, v, log_g = _qkvg()
    want = reference.retention(q, k, v, log_g)
    got, _ = pr.power_retention_chunked(q, k, v, log_g, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_step_form_is_the_attention_form_and_builds_the_same_state(impl):
    q, k, v, log_g = _qkvg(1)
    want = reference.retention(q, k, v, log_g)
    got, state = _steps(q, k, v, log_g, impl)
    np.testing.assert_allclose(got, want, atol=1e-4)
    _, (s1, z1) = pr.power_retention_chunked(q, k, v, log_g, chunk=16)
    np.testing.assert_allclose(state[0], s1, atol=5e-5)
    np.testing.assert_allclose(state[1], z1, atol=5e-5)


def test_row_blocks_inside_a_chunk(monkeypatch):
    """Blocks of rows and of tokens smaller than the chunk."""
    monkeypatch.setattr(pr, "ROW_BLOCK", 8)
    monkeypatch.setattr(pr, "STATE_BLOCK", 12)
    q, k, v, log_g = _qkvg(2)
    want = reference.retention(q, k, v, log_g)
    got, (s1, _) = pr.power_retention_chunked(q, k, v, log_g, chunk=20)
    np.testing.assert_allclose(got, want, atol=2e-5)
    _, state = _steps(q, k, v, log_g)
    np.testing.assert_allclose(state[0], s1, atol=5e-5)


@pytest.mark.parametrize("chunk", [None, 16])
def test_length_mask_keeps_bucket_padding_out_of_the_state(chunk):
    """Rows of different lengths in one padded bucket: the state is the
    one after `lengths` tokens, the outputs left of it are untouched."""
    q, k, v, log_g = _qkvg(3)
    lengths = jnp.asarray([20, T])
    want = reference.retention(q, k, v, log_g)
    got, (s, z) = pr.power_retention_chunked(q, k, v, log_g, lengths,
                                             chunk=chunk)
    np.testing.assert_allclose(got[0, :20], want[0, :20], atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)
    _, short = _steps(q, k, v, log_g, upto=20)
    _, full = _steps(q, k, v, log_g)
    np.testing.assert_allclose(s[0], short[0][0], atol=5e-5)
    np.testing.assert_allclose(z[0], short[1][0], atol=5e-5)
    np.testing.assert_allclose(s[1], full[0][1], atol=5e-5)
    # without the mask the padding is folded in: the test can tell
    _, (bad, _) = pr.power_retention_chunked(q, k, v, log_g, chunk=chunk)
    assert float(jnp.abs(bad[0] - short[0][0]).max()) > 1e-2


def test_the_gate_and_the_normaliser_matter():
    """What the benchmark's tolerance has to catch, at unit size."""
    q, k, v, log_g = _qkvg(4)
    want = reference.retention(q, k, v, log_g)
    no_gate, _ = pr.power_retention_chunked(q, k, v, jnp.zeros_like(log_g))
    assert float(jnp.abs(no_gate - want).max()) > 1e-2
    got, _ = pr.power_retention_chunked(q, k, v, log_g, eps=1.0)
    assert float(jnp.abs(got - want).max()) > 1e-2


def test_a_bfloat16_state_is_told_from_a_float32_one():
    """The benchmark's control at unit size: a state rounded to bfloat16
    between steps (`benchmarks/state_precision_control.py`)."""
    q, k, v, log_g = _qkvg(5, t=64)
    want = reference.retention(q, k, v, log_g)
    errs = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        got, state = _steps(
            q, k, v, log_g,
            held=lambda a: a.astype(dtype).astype(jnp.float32))
        assert state[0].dtype == state[1].dtype == jnp.float32
        errs[dtype] = float(jnp.abs(got - want).max())
    assert errs[jnp.float32] < 1e-4 < 1e-3 < errs[jnp.bfloat16]


def test_rotary_embedding_half_split():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, 3, 8)), jnp.float32)
    pos = np.array([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]], np.int32)
    got = np.asarray(paddle.nn.functional.rotary_embedding(
        paddle.to_tensor(x), paddle.to_tensor(pos), theta=1e6).numpy())
    np.testing.assert_allclose(got[0, 0], x[0, 0], atol=1e-6)  # position 0
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    # the reference's rotation, row 0 (positions 0..T-1)
    np.testing.assert_allclose(got[0], reference.rope(x, 1e6)[0], atol=1e-5)
    # a dot product depends on the distance only
    q = jnp.asarray(rng.normal(size=(1, 1, 1, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 1, 8)), jnp.float32)
    rot = lambda a, p: np.asarray(paddle.nn.functional.rotary_embedding(
        paddle.to_tensor(a), paddle.to_tensor(np.array([[p]], np.int32)),
        theta=1e4).numpy())
    near = (rot(q, 3) * rot(k, 1)).sum()
    far = (rot(q, 103) * rot(k, 101)).sum()
    assert near == pytest.approx(far, rel=1e-4, abs=1e-4)
    with pytest.raises(ValueError):
        paddle.nn.functional.rotary_embedding(
            paddle.to_tensor(np.zeros((1, 1, 1, 7), np.float32)),
            paddle.to_tensor(np.zeros((1, 1), np.int32)))


def test_rms_norm_keeps_the_dtype_and_sums_in_float32():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 256)) * 30,
                    jnp.bfloat16)
    w = paddle.to_tensor(jnp.ones((256,), jnp.bfloat16))
    got = paddle.nn.functional.rms_norm(paddle.to_tensor(x), w)
    assert got._value.dtype == jnp.bfloat16
    x32 = np.asarray(x, np.float32)
    want = x32 / np.sqrt((x32 ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(got.numpy(), np.float32), want,
                               rtol=1e-2)


# ---- the model against the plain reference ---------------------------------

SIZES = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
             num_kv_heads=2, head_dim=8, intermediate_size=64, gate_bias=3.0)
REF = dict(n_layers=2, heads=4, kv_heads=2, theta=1e6, eps=1e-6)


def _lm(dtype="float32", seed=3):
    paddle.seed(seed)
    lm = BrumbyForCausalLM(BrumbyModel(dtype=dtype, **SIZES))
    lm.eval()
    return lm


def _named(lm):
    return {n: p._value for n, p in lm.named_parameters()}


def _ids(rows=3, t=21, seed=0):
    return np.random.default_rng(seed).integers(0, 97, (rows, t)
                                                ).astype(np.int32)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 5e-2)])
def test_full_forward_against_the_reference(dtype, tol):
    lm, ids = _lm(dtype), _ids()
    assert all(str(p._value.dtype) == dtype for p in lm.parameters())
    at = np.tile(np.arange(ids.shape[1]), (len(ids), 1))
    want = np.asarray(reference.logits_at(_named(lm), ids, at, **REF))
    with paddle.no_grad():
        got = np.asarray(lm(paddle.to_tensor(ids)).numpy())
        last = np.asarray(lm(paddle.to_tensor(ids), paddle.to_tensor(
            np.array([4, 20, 11], np.int32))).numpy())
    assert got.dtype == np.float32          # the head's logits, any weights
    scale = np.abs(want).max()
    assert np.abs(got - want).max() / scale < tol
    assert np.abs(last - want[np.arange(3), [4, 20, 11]]).max() / scale < tol
    at = np.array([[0, 3], [20, 7], [11, 12]], np.int32)
    with paddle.no_grad():
        some = np.asarray(lm(paddle.to_tensor(ids), paddle.to_tensor(at)
                             ).numpy())
    assert some.shape == (3, 2, 97)
    assert np.abs(some - want[np.arange(3)[:, None], at]).max() / scale < tol


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 5e-2)])
def test_cached_path_against_the_reference(dtype, tol):
    """The prompt form inside a padded bucket, then one-token steps
    through the state, teacher-forced: logits at every position."""
    lm, ids = _lm(dtype), _ids()
    rows, t = ids.shape
    at = np.tile(np.arange(t), (rows, 1))
    want = np.asarray(reference.logits_at(_named(lm), ids, at, **REF))
    scale = np.abs(want).max()
    lens = np.array([9, 14, 5], np.int32)
    padded = np.zeros((rows, 24), np.int32)
    for r in range(rows):
        padded[r, :lens[r]] = ids[r, :lens[r]]
    with paddle.no_grad():
        cache = lm.init_cache(rows, None)
        assert len(cache) == 2 * 2 and cache[0].shape == [rows, 2, 8, 128]
        logits, cache = lm.forward_cached(
            paddle.to_tensor(padded), cache,
            paddle.to_tensor(np.zeros(rows, np.int32)),
            paddle.to_tensor(lens))
        got = np.asarray(logits.numpy())
        assert got.shape == (rows, 97)      # never [bucket, vocab]
        assert np.abs(got - want[np.arange(rows), lens - 1]).max() / scale \
            < tol
        for i in range(7):
            pos = lens + i
            logits, cache = lm.forward_cached(
                paddle.to_tensor(ids[np.arange(rows), pos][:, None]), cache,
                paddle.to_tensor(pos))
            got = np.asarray(logits.numpy())
            assert np.abs(got - want[np.arange(rows), pos]).max() / scale \
                < tol, i
        with pytest.raises(ValueError):     # a step is one token wide
            lm.forward_cached(paddle.to_tensor(padded[:, :2]), cache,
                              paddle.to_tensor(lens))


def test_parameters_are_drawn_straight_into_their_dtype():
    """Xavier for the linear maps, N(0, initializer_range) for the table,
    one program a draw (no float32 copy of a 1.6 GB table on the way)."""
    paddle.seed(11)
    m = BrumbyModel(vocab_size=4096, hidden_size=256, num_layers=1,
                    num_heads=4, num_kv_heads=2, head_dim=64,
                    intermediate_size=512, initializer_range=0.05,
                    dtype="bfloat16")
    table = np.asarray(m.embed_tokens.weight._value, np.float32)
    assert abs(table.std() - 0.05) < 2e-3 and abs(table.mean()) < 1e-3
    w = np.asarray(m.layers[0].mlp.up_proj.weight._value, np.float32)
    assert abs(w.std() - (2.0 / (256 + 512)) ** 0.5) < 2e-3
    bias = np.asarray(m.layers[0].retention.g_proj.bias._value, np.float32)
    assert np.all(bias == 5.0)
    assert paddle.get_default_dtype() == np.dtype("float32")   # restored


def test_init_cache_contract_of_both_models():
    lm = _lm()
    cache = lm.init_cache(5, 999, dtype="bfloat16")
    assert [c.shape for c in cache] == [[5, 2, 8, 128], [5, 2, 128]] * 2
    assert all(str(c._value.dtype) == "bfloat16" for c in cache)
    gpt = GPTForCausalLM(GPTModel(vocab_size=64, hidden_size=32, num_layers=3,
                                  num_heads=4, max_seq_len=32, dropout=0.0))
    # the pages add the decode block's rows to max_len themselves
    pages = gpt.init_cache(5, 16)
    assert [p.shape for p in pages] == [[5, 16 + DECODE_BLOCK, 32]] * 6
    assert all(str(p._value.dtype) == "float32" for p in pages)
    # int8: the pages, then their dequantisation scales, slot on axis 0
    cache = gpt.init_cache(5, 16, dtype="int8")
    assert [c.shape for c in cache] == [[5, 18, 32]] * 6 + [[5]] * 6
    assert [str(c._value.dtype) for c in cache] \
        == ["int8"] * 6 + ["float32"] * 6
    assert all(np.all(np.asarray(c.numpy()) == 1.0) for c in cache[6:])
    assert GPTForCausalLM.cache_tag == "kv_pool"
    assert BrumbyForCausalLM.cache_tag == "state_pool"


# ---- through LLMEngine ------------------------------------------------------

@pytest.fixture
def monitored():
    paddle.set_flags({"FLAGS_monitor": True})
    monitor.reset()
    yield
    paddle.set_flags({"FLAGS_monitor": False})


def _greedy(lm, prompt, n):
    ids = list(prompt)
    with paddle.no_grad():
        for _ in range(n):
            logits = np.asarray(lm(paddle.to_tensor(
                np.asarray(ids, np.int32)[None])).numpy())[0, -1]
            ids.append(int(logits.argmax()))
    return ids[len(prompt):]


def _compiles():
    c = monitor.snapshot()["counters"]
    return sum(v for k, v in c.items()
               if k.endswith((".traces", ".retraces", ".cache_miss")))


def test_engine_streams_the_greedy_tokens_of_the_full_forward(monitored):
    """Prompts of different lengths share a bucket (the padding must not
    reach a state), more prompts than slots (a slot is reused: the state a
    finished sequence left must not leak), the pool donated every step and
    nothing compiled after the warm-up."""
    lm = _lm()
    eng = LLMEngine(lm, LLMConfig(num_slots=3, max_len=64,
                                  prefill_buckets=(16, 32),
                                  max_new_tokens=6))
    before = [t._value for t in eng._pool]
    eng.start()                                        # warms both programs
    assert all(a.is_deleted() for a in before)         # consumed, not copied
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 97, n).astype(np.int32)
                   for n in (5, 9, 13, 16, 20, 31, 3)]
        eng.submit(prompts[0]).result(timeout=120.0)   # warm the slot write
        c0 = _compiles()
        streams = [eng.submit(p) for p in prompts]
        for p, s in zip(prompts, streams):
            status, toks = s.result(timeout=120.0)
            assert status == "done" and toks == _greedy(lm, p, 6)
        assert _compiles() == c0, "compiled in steady state"
        assert not any(t._value.is_deleted() for t in eng._pool)
    finally:
        eng.stop()
    snap = monitor.snapshot()["counters"]
    assert snap["llm.decode.steps"] > 0
    assert snap["llm.decode.pool_donated"] == snap["llm.decode.steps"]
    assert snap["llm.decode.state_bytes"] == \
        snap["llm.decode.steps"] * eng.kv_pool_bytes()
    assert snap["llm.prefill.tokens_real"] == 5 + sum(map(len, prompts))
    assert snap["llm.prefill.tokens_bucket"] == 16 + 16 * 4 + 32 * 2 + 16
    assert eng.kv_pool_bytes() == 2 * 3 * 2 * 128 * (8 + 1) * 4


def test_engine_state_path_never_builds_bucket_logits():
    """The prefill's logits are [1, vocab] and a decode step's [slots,
    vocab]: read off the programs' own outputs. (That no junk token
    reaches a state is what the greedy equality above would show.)"""
    eng = LLMEngine(_lm(), LLMConfig(num_slots=2, max_len=32,
                                     prefill_buckets=(16,), max_new_tokens=4))
    eng._warmup()
    with paddle.no_grad():
        outs = eng._prefill(paddle.to_tensor(np.zeros((1, 16), np.int32)),
                            paddle.to_tensor(np.ones((1,), np.int32)))
        assert outs[1].shape == [1, 97] and len(outs) == 2 + len(eng._pool)
        assert [o.shape for o in outs[2:]] == \
            [[1, 2, 8, 128], [1, 2, 128]] * 2
        step, donated = eng._decode_pool(np.zeros((2,), np.int32),
                                         np.zeros((2,), np.int32))
    assert donated and step[1].shape == [2, 97]
    assert len(step) == 2 + len(eng._pool)


def test_engine_tags_a_state_pool_in_the_census(monitored):
    from paddle_tpu.obs import memory
    paddle.set_flags({"FLAGS_mem_census": True})
    try:
        eng = LLMEngine(_lm(), LLMConfig(num_slots=2, max_len=32,
                                         prefill_buckets=(16,),
                                         max_new_tokens=3)).start()
        try:
            assert eng.submit([3, 1, 4]).result(timeout=60.0)[0] == "done"
            rec = memory.census()
            assert rec["tags"].get("state_pool", {}).get("bytes", 0) \
                == eng.kv_pool_bytes() > 0
            assert "kv_pool" not in rec["tags"]
        finally:
            eng.stop()
    finally:
        paddle.set_flags({"FLAGS_mem_census": False})


def test_engine_refuses_int8_pages_for_a_recurrent_state():
    with pytest.raises(ServingError, match="state_pool"):
        LLMEngine(_lm(), LLMConfig(num_slots=2, max_len=32, kv_int8=True))


def test_engine_refuses_a_model_without_a_cache_contract():
    with pytest.raises(ServingError, match="init_cache"):
        LLMEngine(paddle.nn.Linear(4, 4), LLMConfig(num_slots=2, max_len=32))
