"""LFM2-MoE (`models/lfm2.py`): the gated short convolution's two forms,
the router, the page read at a head of 64 over 8 K/V heads, the model
against the plain reference (`benchmarks/reference/lfm2.py`), a tiny
LFM2-MoE through `LLMEngine` with its mixed pool, and the counts a decode
step reports of the experts it reached."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import lfm2 as reference
from paddle_tpu import monitor, nn
from paddle_tpu.models.lfm2 import (
    STEP_COUNTS, Lfm2MoeForCausalLM, Lfm2MoeModel, Lfm2ShortConv, layer_kinds,
)
from paddle_tpu.serving import LLMConfig, LLMEngine

da = importlib.import_module("paddle_tpu.kernels.decode_attention")
routed = importlib.import_module("paddle_tpu.nn.layer.routed_experts")

TYPES = ["conv", "conv"] + ["conv" if i % 4 else "full_attention"
                            for i in range(8)]
TINY = dict(vocab_size=96, hidden_size=32, num_hidden_layers=10,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=48, moe_intermediate_size=24, num_experts=16,
            num_experts_per_tok=4, router_bias_std=0.1)
DIMS = dict(heads=4, kv_heads=2, first=0, top_k=4, scaling=1.0, theta=1e6,
            eps=1e-5, taps=3)


def _tiny(seed=0, **over):
    paddle.seed(seed)
    lm = Lfm2MoeForCausalLM(Lfm2MoeModel(**dict(TINY, **over)))
    lm.eval()
    return lm


def _reference(lm, ids, at, rows_at=None):
    return reference.forward(
        {k: p._value for k, p in lm.named_parameters()}, ids, at,
        kinds=layer_kinds(TYPES, lm.model.layer_ids, 2), rows_at=rows_at,
        **DIMS)


# ---- the gated short convolution -------------------------------------------

def _conv(seed):
    paddle.seed(seed)
    return Lfm2ShortConv(16, 3)


@pytest.mark.parametrize("form", ["tap_loop", "steps"])
def test_gated_short_conv_prompt_form_matches(form):
    """The prompt form (rows of two lengths in one padded call) against the
    reference's loop over the taps, or against the same rows fed one token
    at a time through the kept rows; either way what is kept after a row's
    length is v's last two rows there."""
    layer = _conv(1)
    rng = np.random.default_rng(2)
    u = paddle.to_tensor(rng.normal(size=(2, 11, 16)).astype("float32"))
    n = np.array([11, 6])
    with paddle.no_grad():
        y, rows = layer.forward_cached(u, None, paddle.to_tensor(n), False)
    y, rows = y.numpy(), rows.numpy()
    w = {k: p._value for k, p in layer.named_parameters()}
    if form == "tap_loop":
        want, kept = reference.conv(jnp.asarray(u.numpy()), w,
                                    jnp.asarray(n - 1), 3)
        for r, length in enumerate(n):
            np.testing.assert_allclose(y[r, :length],
                                       np.asarray(want)[r, :length],
                                       atol=2e-5)
        np.testing.assert_allclose(rows, kept, atol=2e-6)
        return
    state = paddle.zeros([2, 2, 16])
    with paddle.no_grad():
        for t in range(11):
            out, state = layer.forward_cached(u[:, t:t + 1], state, None, True)
            live = t < n
            np.testing.assert_allclose(out.numpy()[live, 0], y[live, t],
                                       atol=2e-5)
            if t == 5:
                np.testing.assert_allclose(state.numpy()[1], rows[1],
                                           atol=1e-6)
    np.testing.assert_allclose(state.numpy()[0], rows[0], atol=1e-6)


# ---- the router --------------------------------------------------------------

def test_router_chooses_by_biased_scores_and_weighs_by_unbiased_ones():
    """The top 4 of s + b, weighed by s normalised: a bias that lifts an
    expert into the choice does not lift its weight."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.5, 16), jnp.float32)
    experts, w, biased = routed.route(logits, bias, 4, 1, 1, 1.0)
    s = jax.nn.sigmoid(logits)
    want = np.argsort(-(np.asarray(s) + np.asarray(bias)), axis=-1)[:, :4]
    assert np.array_equal(np.sort(experts, -1), np.sort(want, -1))
    picked = np.take_along_axis(np.asarray(s), np.asarray(experts), axis=1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(biased, s + bias, atol=1e-7)
    # against the reference's own weights, which add the published 1e-6
    m = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    named = {"router": router, "router_bias": bias,
             **{k: jnp.zeros((16, 8, 8))
                for k in ("gate_proj", "up_proj", "down_proj")}}
    _, ref_experts, *_ = reference.moe(m, named, first=0, top_k=4,
                                       scaling=1.0)
    with jax.default_matmul_precision("highest"):
        mine = routed.route(m @ router, bias, 4, 1, 1, 1.0)[0]
    assert np.array_equal(np.sort(mine, -1), np.sort(ref_experts, -1))


# ---- the page read at a head of 64 over 8 K/V heads -------------------------

@pytest.mark.parametrize("wide", [True, False], ids=["wide", "sliced"])
@pytest.mark.parametrize("block", [16, 64])
def test_page_read_at_head_64_over_8_kv_heads_matches_dense(monkeypatch,
                                                            block, wide):
    """The cell's geometry at a short page: 32 query heads of 64 over 8 K/V
    heads, a page row of 512 lanes, the kernel interpreted in both forms
    (the query laid over the whole row, the default for a head narrower
    than a lane tile; each head's lanes sliced, the form of 128-lane
    heads); a slot at position 0 reads its one row."""
    monkeypatch.setattr(da, "GQA_BLOCK_K", block)
    rng = np.random.default_rng(block)
    b, heads, kv, d, length = 3, 32, 8, 64, 80
    q = rng.normal(size=(b, heads, d)).astype(np.float32)
    k = rng.normal(size=(b, length, kv * d)).astype(np.float32)
    v = rng.normal(size=(b, length, kv * d)).astype(np.float32)
    pos = np.array([0, 37, 79])
    got = np.asarray(da._gqa_read(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pos),
        wide=wide))
    grow = lambda a: np.repeat(a.reshape(b, length, kv, d), heads // kv, 2)
    s = np.einsum("bhd,blhd->bhl", q, grow(k)) / np.sqrt(d)
    s = np.where(np.arange(length)[None, None] <= pos[:, None, None], s,
                 -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhl,blhd->bhd", p / p.sum(-1, keepdims=True), grow(v))
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---- the model ---------------------------------------------------------------

def test_layer_kinds_follow_the_published_types():
    assert layer_kinds(TYPES, range(10), 2) == [
        ("conv", "dense"), ("conv", "dense"), ("attention", "moe"),
        ("conv", "moe"), ("conv", "moe"), ("conv", "moe"),
        ("attention", "moe"), ("conv", "moe"), ("conv", "moe"),
        ("conv", "moe")]
    with pytest.raises(ValueError, match="not built"):
        layer_kinds(["sliding_attention"], [0], 0)
    lm = _tiny()
    assert lm.cache_tag == ("state_pool",) * 2 + ("kv_pool",) * 2 \
        + ("state_pool",) * 3 + ("kv_pool",) * 2 + ("state_pool",) * 3
    shapes = [tuple(c.shape) for c in lm.init_cache(3, 20)]
    assert shapes[:4] == [(3, 2, 32), (3, 2, 32), (3, 20, 16), (3, 20, 16)]
    assert lm.step_counts == STEP_COUNTS
    assert _tiny(layers=[0, 1]).step_counts == ()


def test_full_forward_and_cached_path_match_the_reference():
    """The full forward, then a padded prompt through the cache and five
    steps, against the reference's full forward; the kept convolution rows
    are the reference's v at each row's last two positions."""
    lm = _tiny()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 96, (2, 40)).astype(np.int32)
    n = np.array([33, 9], np.int32)
    at = n[:, None] - 1 + np.arange(6)[None, :]
    want, routing, rows = _reference(lm, ids, at, rows_at=n + 4)
    choices = []
    with paddle.no_grad():
        got = lm(paddle.to_tensor(ids), paddle.to_tensor(at),
                 choices=choices).numpy()
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert len(choices) == len(routing) == 8
        for (mine, scores), ref in zip(choices, routing):
            assert np.array_equal(np.sort(mine.numpy(), -1),
                                  np.sort(ref["experts"], -1))
            np.testing.assert_allclose(scores.numpy(),
                                       np.asarray(ref["biased"]), atol=1e-5)
        logits, out = lm.forward_cached(
            paddle.to_tensor(ids[:, :34]), lm.init_cache(2, 48),
            paddle.zeros([2], dtype="int32"), paddle.to_tensor(n))
        np.testing.assert_allclose(logits.numpy(), want[:, 0], atol=2e-5)
        own = len(lm.cache_tag)
        cache, routes = out[:own], out[own:]
        assert len(routes) == 8           # a prompt reports no counts
        for i in range(5):
            logits, out = lm.forward_cached(
                paddle.to_tensor(ids[np.arange(2), n + i][:, None]), cache,
                paddle.to_tensor(n + i))
            np.testing.assert_allclose(logits.numpy(), want[:, i + 1],
                                       atol=2e-5)
            cache, routes = out[:own], out[own:own + 8]
            assert len(out) == own + 9
            for mine, ref in zip(routes, routing):
                assert np.array_equal(
                    np.sort(mine.numpy()[:, 0], -1),
                    np.sort(ref["experts"][np.arange(2), n + i], -1))
    convs = [c for c, tag in zip(cache, lm.cache_tag) if tag == "state_pool"]
    assert len(convs) == len(rows) == 8
    for mine, ref in zip(convs, rows):
        np.testing.assert_allclose(mine.numpy(), ref, atol=2e-5)


def test_a_padded_prompt_keeps_the_rows_and_pages_of_the_unpadded_one():
    """Rows of different lengths in one padded prompt: each row's logits,
    convolution rows and pages (up to its length) are those of the row
    alone; a step in which a row sits at position 0 leaves the live rows as
    they are."""
    lm = _tiny(seed=2)
    own = len(lm.cache_tag)
    pages = [tag == "kv_pool" for tag in lm.cache_tag]
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 96, (3, 24)).astype(np.int32)
    n = np.array([17, 5, 24], np.int32)
    with paddle.no_grad():
        logits, out = lm.forward_cached(
            paddle.to_tensor(ids), lm.init_cache(3, 32),
            paddle.zeros([3], dtype="int32"), paddle.to_tensor(n))
        for r in range(3):
            alone, out1 = lm.forward_cached(
                paddle.to_tensor(ids[r:r + 1, :n[r]]), lm.init_cache(1, 32),
                paddle.zeros([1], dtype="int32"),
                paddle.to_tensor(n[r:r + 1]))
            np.testing.assert_allclose(logits.numpy()[r], alone.numpy()[0],
                                       atol=2e-5)
            for a, b, page in zip(out[:own], out1[:own], pages):
                a, b = a.numpy()[r], b.numpy()[0]
                if page:
                    a, b = a[:n[r]], b[:n[r]]
                np.testing.assert_allclose(a, b, atol=2e-5)
        cache = out[:own]
        tokens = paddle.to_tensor(rng.integers(0, 96, (3, 1)).astype(np.int32))
        every, out_e = lm.forward_cached(tokens, cache, paddle.to_tensor(n))
        at = n.copy()
        at[1] = 0
        some, out_s = lm.forward_cached(tokens, cache, paddle.to_tensor(at))
    for r in (0, 2):
        np.testing.assert_allclose(some.numpy()[r], every.numpy()[r],
                                   atol=2e-5)
        for a, b, page in zip(out_s[:own], out_e[:own], pages):
            a, b = a.numpy()[r], b.numpy()[r]
            if page:
                a, b = a[:n[r] + 1], b[:n[r] + 1]
            np.testing.assert_allclose(a, b, atol=2e-5)


# ---- the counts of the experts a step reaches --------------------------------

def _hand_count(routes, live, count):
    """Of each expert block's routes [B, 1, top_k], those of the live rows:
    (distinct held experts reached, routes) summed over the blocks."""
    reached = sum(len({int(e) for e in r[live].ravel() if e < count})
                  for r in routes)
    return reached, sum(int((r[live] < count).sum()) for r in routes)


@pytest.mark.parametrize("held", [None, (4, 8)], ids=["all", "share"])
def test_a_step_counts_the_experts_its_live_rows_reach(held):
    """The step's counts are the group sizes of its layouts: the held
    experts the live rows' routes reach, the most a step could reach, the
    routes that land on held experts; a row at position 0 reaches none."""
    lm = _tiny(seed=5, held=held)
    first, count = held or (0, 16)
    own = len(lm.cache_tag)
    rng = np.random.default_rng(6)
    ids = rng.integers(0, 96, (4, 12)).astype(np.int32)
    n = np.array([12, 7, 3, 9], np.int32)
    with paddle.no_grad():
        _, out = lm.forward_cached(paddle.to_tensor(ids), lm.init_cache(4, 24),
                                   paddle.zeros([4], dtype="int32"),
                                   paddle.to_tensor(n))
        pos = n.copy()
        pos[2] = 0
        tokens = paddle.to_tensor(rng.integers(0, 96, (4, 1)).astype(np.int32))
        _, out = lm.forward_cached(tokens, out[:own], paddle.to_tensor(pos))
    routes = [r.numpy() - first for r in out[own:own + 8]]
    routes = [np.where(r >= 0, r, count) for r in routes]
    reached, landed = _hand_count(routes, pos > 0, count)
    assert out[-1].numpy().tolist() == [reached, 8 * count, landed]
    assert 0 < reached <= 8 * count and landed <= 8 * 3 * 4


@pytest.fixture
def monitored():
    was = monitor.enabled()
    paddle.set_flags({"FLAGS_monitor": True})
    monitor.reset()
    yield
    paddle.set_flags({"FLAGS_monitor": was})


def _engine_against_full_forward(lm, prompts, new, slots):
    eng = LLMEngine(lm, LLMConfig(num_slots=slots, max_len=32,
                                  prefill_buckets=(8, 16))).start()
    try:
        before = monitor.snapshot()["counters"]
        streams = [eng.submit(p, max_new_tokens=new) for p in prompts]
        got = [s.result(timeout=120)[1] for s in streams]
        after = monitor.snapshot()["counters"]
    finally:
        eng.stop(drain=False)
    width = max(len(p) for p in prompts) + new
    ids = np.zeros((len(prompts), width), np.int32)
    for r, (p, toks) in enumerate(zip(prompts, got)):
        assert len(toks) == new
        ids[r, :len(p) + new] = list(p) + toks
    with paddle.no_grad():
        full = lm(paddle.to_tensor(ids)).numpy()
    for r, (p, toks) in enumerate(zip(prompts, got)):
        assert toks == np.argmax(full[r, len(p) - 1:len(p) - 1 + new],
                                 -1).tolist()
    return eng, {k: after.get(k, 0) - before.get(k, 0) for k in after}


def test_engine_streams_the_full_forwards_greedy_tokens(monitored):
    """Five prompts over two slots: every slot is reused after an eviction,
    and each stream is the full forward's greedy continuation; the steps'
    counts are stamped off the read of their tokens."""
    lm = _tiny(seed=3)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32)
               for n in (5, 13, 8, 3, 11)]
    eng, delta = _engine_against_full_forward(lm, prompts, 6, 2)
    assert {k: v for k, v in delta.items()
            if "compile" in k or "retrace" in k} == {
        k: 0 for k in delta if "compile" in k or "retrace" in k}
    steps = delta["llm.decode.steps"]
    assert steps > 0 and delta["llm.decode.pool_donated"] == steps
    assert delta["moe.decode.experts_read_max"] == steps * 8 * 16
    rows = delta["llm.decode.rows"]
    assert delta["moe.decode.routes_reached"] == rows * 8 * 4
    assert rows * 0 < delta["moe.decode.experts_reached"] <= min(
        rows * 8 * 4, steps * 8 * 16)
    assert delta["llm.decode.state_bytes"] == steps * 8 * 2 * 2 * 32 * 4
    assert eng.kv_pool_bytes("kv_pool") == 4 * 2 * 32 * 16 * 4


def test_a_model_without_step_counts_reads_its_tokens_alone(monitored):
    """Without expert blocks the decode program hands out no joined
    readout and no counter is stamped; the streams are the same greedy
    continuations."""
    lm = _tiny(seed=3, layers=[0, 1])
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (5, 9)]
    eng, delta = _engine_against_full_forward(lm, prompts, 4, 2)
    assert eng._counts == () and "moe.decode.experts_reached" not in delta


def test_the_read_span_carries_the_steps_counts(monitored, tmp_path):
    """In a profiler trace the `llm.decode.read` span of a step carries its
    counts as the event's stats, so a trace reader takes the bytes a step
    read from the same slice as its kernels' time."""
    lm = _tiny(seed=3)
    eng = LLMEngine(lm, LLMConfig(num_slots=2, max_len=32,
                                  prefill_buckets=(8, 16))).start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
        eng.stop(drain=False)
    import glob
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    found = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "llm.decode.read":
                    found.append(dict(e.stats))
    assert len(found) == 3
    for stats in found:
        assert int(stats["experts_read_max"]) == 8 * 16
        assert int(stats["routes_reached"]) == 8 * 4
        assert 4 <= int(stats["experts_reached"]) <= 8 * 4
