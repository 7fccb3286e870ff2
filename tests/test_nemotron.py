"""Nemotron-H (`models/nemotron.py`): the state-space scan's two forms
(`kernels/ssd.py`), the page read over grouped K/V heads, relu^2 routed
experts and the share a rank holds, the model against the plain reference
(`benchmarks/reference/nemotron.py`), and a tiny Nemotron-H through
`LLMEngine` with its mixed pool."""
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import nemotron as reference
from paddle_tpu import monitor, nn
from paddle_tpu.models.nemotron import (
    NemotronHForCausalLM, NemotronHModel, layer_kinds,
)
from paddle_tpu.serving import LLMConfig, LLMEngine

ssd = importlib.import_module("paddle_tpu.kernels.ssd")
da = importlib.import_module("paddle_tpu.kernels.decode_attention")
routed = importlib.import_module("paddle_tpu.nn.layer.routed_experts")

ROUTER = dict(top_k=4, n_group=1, topk_group=1, scaling=2.5)


# ---- the state-space scan ---------------------------------------------------

def _ssd_inputs(b=2, t=45, h=4, p=8, g=2, n=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    delta = jnp.log1p(jnp.exp(f(b, t, h) - 1.0))
    a = -jnp.asarray(rng.uniform(1.0, 16.0, h), jnp.float32)
    return f(b, t, h, p), delta, a, f(b, t, g, n), f(b, t, g, n)


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_ssd_chunked_matches_the_recurrence_across_chunks(form):
    """Chunks of 16 over 45 positions (two boundaries and a ragged tail),
    rows of different lengths: the outputs before each length and the
    state at each length are the sequential recurrence's."""
    x, delta, a, b, c = _ssd_inputs()
    lengths = np.array([45, 21])
    want_y, want_s = reference.scan(x, delta, a, b, c,
                                    jnp.asarray(lengths - 1))
    y, s = ssd.ssd_chunked(x, delta, a, b, c, jnp.asarray(lengths), chunk=16,
                           form=form)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(y)[r, :n],
                                   np.asarray(want_y)[r, :n], atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


@pytest.mark.parametrize("h, g, p, n", [
    (8, 2, 8, 16),          # the head block does not divide: one block
    (128, 8, 64, 128),      # two head blocks of four groups each
    (64, 8, 64, 128),       # the cell's heads: one block, the whole slot
], ids=["h8-g2", "h128-g8", "h64-g8"])
def test_ssd_step_kernel_matches_its_jnp_form(h, g, p, n):
    """The kernel, interpreted, against its `jax.numpy` form at head
    structures that take one or several head blocks and groups."""
    x, delta, a, b, c = (v[:, 0] if v.ndim > 1 else v
                         for v in _ssd_inputs(b=3 if h == 8 else 2, h=h, p=p,
                                              g=g, n=n))
    s = jnp.asarray(np.random.default_rng(1).normal(
        size=(x.shape[0], h, p, n)), jnp.float32)
    y, new = ssd._step_jnp(x, delta, a, b, c, s)
    y_k, new_k = ssd._step_pallas(x, delta, a, b, c, s, interpret=True)
    np.testing.assert_allclose(y_k, y, atol=1e-5)
    np.testing.assert_allclose(new_k, new, atol=1e-6)


def test_ssd_step_continues_the_chunked_form():
    """One more position after a chunked prompt: the step's y is the
    chunked form's at that position."""
    a = _ssd_inputs(b=3, h=8, g=2)[2]
    xs, ds, _, bs, cs = _ssd_inputs(b=3, h=8, g=2, t=10)
    _, s9 = ssd.ssd_chunked(xs[:, :9], ds[:, :9], a, bs[:, :9], cs[:, :9])
    y10, _ = ssd.ssd_chunked(xs, ds, a, bs, cs)
    y_step, _ = ssd.ssd_step(xs[:, 9], ds[:, 9], a, bs[:, 9], cs[:, 9], s9)
    np.testing.assert_allclose(y_step, np.asarray(y10)[:, 9], atol=2e-5)


def test_conv_with_its_bias_keeps_the_rows_before_the_length():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 9, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    y, rows = ssd.conv_prompt(x, w, bias, jnp.asarray([9, 5]))
    want = np.asarray(reference.short_conv(x, w)) + np.asarray(bias)
    np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_array_equal(rows[1], x[1, 2:5])
    y1, _ = ssd.conv_step(x[1:2, 5], w, bias, rows[1:2])
    y6, _ = ssd.conv_prompt(x[1:2, :6], w, bias)
    np.testing.assert_allclose(y1[0], y6[0, 5], atol=1e-5)


# ---- the page read over grouped K/V heads ----------------------------------

@pytest.mark.parametrize("heads,kv", [(8, 2), (4, 4)])
def test_grouped_page_read_matches_dense_attention(monkeypatch, heads, kv):
    """The kernel (interpreted, blocks of 16 rows) against softmax over the
    keys up to each slot's position, each K/V head repeated for its
    group; a slot at position 0 reads its one row."""
    monkeypatch.setattr(da, "GQA_BLOCK_K", 16)
    rng = np.random.default_rng(heads)
    b, d, length = 3, 16, 40
    q = rng.normal(size=(b, heads, d)).astype(np.float32)
    k = rng.normal(size=(b, length, kv * d)).astype(np.float32)
    v = rng.normal(size=(b, length, kv * d)).astype(np.float32)
    pos = np.array([0, 17, 39])
    got = np.asarray(da.decode_attention_gqa(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pos)))
    grow = lambda a: np.repeat(a.reshape(b, length, kv, d), heads // kv, 2)
    s = np.einsum("bhd,blhd->bhl", q, grow(k)) / np.sqrt(d)
    s = np.where(np.arange(length)[None, None] <= pos[:, None, None], s,
                 -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhl,blhd->bhd", p / p.sum(-1, keepdims=True), grow(v))
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---- relu^2 routed experts -------------------------------------------------

def _experts(held=None, seed=0):
    paddle.seed(seed)
    layer = nn.RoutedExperts(32, 24, 16, ROUTER["top_k"], held=held,
                             scaling=ROUTER["scaling"], shared_width=40,
                             activation="relu2")
    layer.router_bias.set_value(
        np.random.default_rng(seed).normal(0, 0.1, 16).astype("float32"))
    return layer


def _named(layer):
    return {k: p._value for k, p in layer.named_parameters()}


def _rows(t=40, seed=1):
    return np.random.default_rng(seed).normal(size=(t, 32)).astype("float32")


def test_relu2_experts_match_a_loop_over_experts():
    layer, m = _experts(held=(4, 8)), _rows()
    assert layer.gate_proj is None and layer.shared_gate is None
    assert sorted(_named(layer)) == ["down_proj", "router", "router_bias",
                                     "shared_down.weight", "shared_up.weight",
                                     "up_proj"]
    got, chosen, scores = layer(paddle.to_tensor(m), return_choice=True)
    want, experts, _, _, biased = reference.moe(
        jnp.asarray(m), _named(layer), first=4, **ROUTER)
    np.testing.assert_allclose(got.numpy(), want, atol=2e-5)
    assert np.array_equal(np.sort(chosen.numpy(), -1), np.sort(experts, -1))
    np.testing.assert_allclose(scores.numpy(), np.asarray(biased), atol=1e-6)
    # the pass itself, a route at a time against its expert alone
    w = np.random.default_rng(3).uniform(size=(40, 4)).astype(np.float32)
    local = np.random.default_rng(4).integers(0, 9, (40, 4)).astype(np.int32)
    up, down = (np.asarray(p._value) for p in (layer.up_proj, layer.down_proj))
    y = np.asarray(routed.experts_pass(jnp.asarray(m), jnp.asarray(local),
                                       jnp.asarray(w), None,
                                       layer.up_proj._value,
                                       layer.down_proj._value))
    loop = np.zeros_like(y)
    for t in range(40):
        for j in range(4):
            e = local[t, j]
            if e < 8:
                loop[t] += w[t, j] * (np.maximum(m[t] @ up[e], 0) ** 2
                                      @ down[e])
    np.testing.assert_allclose(y, loop, atol=2e-4, rtol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight ranks share the 16 experts, two each (the configuration's
    deployment): their outputs, the shared expert counted once, sum to the
    reference's uncut layer."""
    whole, m = _experts(), _rows(64)
    named = _named(whole)
    want = np.asarray(reference.moe(jnp.asarray(m), named, first=0,
                                    **ROUTER)[0])
    x = paddle.to_tensor(m)
    shared = whole.shared_down(nn.functional.relu(whole.shared_up(x)) ** 2
                               ).numpy()
    total = np.zeros_like(want)
    for rank in range(8):
        part = _experts(held=(2 * rank, 2))
        for name, p in part.named_parameters():
            src = named[name]
            p.set_value(src[2 * rank:2 * rank + 2]
                        if name in ("up_proj", "down_proj") else src)
        mine = part(x).numpy()
        np.testing.assert_allclose(mine, reference.moe(
            jnp.asarray(m), _named(part), first=2 * rank, **ROUTER)[0],
            atol=2e-5)
        total += mine - shared
    np.testing.assert_allclose(total + shared, want, atol=5e-5)


# ---- the model -------------------------------------------------------------

PATTERN = "MEMEM*EME"
TINY = dict(vocab_size=96, hidden_size=32, num_hidden_layers=9,
            hybrid_override_pattern=PATTERN, mamba_num_heads=4,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
            n_routed_experts=16, num_experts_per_tok=4, held=(4, 8),
            router_bias_std=0.1)
DIMS = dict(heads=4, kv_heads=2, head_dim=8, mamba_heads=4, mamba_head_dim=8,
            groups=2, state=16, first=4, top_k=4, n_group=1, topk_group=1,
            scaling=2.5, eps=1e-5)


def _tiny(seed=0):
    paddle.seed(seed)
    lm = NemotronHForCausalLM(NemotronHModel(**TINY))
    lm.eval()
    return lm


def _reference(lm, ids, at, state_at=None):
    return reference.forward(
        {k: p._value for k, p in lm.named_parameters()}, ids, at,
        kinds=layer_kinds(PATTERN, range(9)), state_at=state_at, **DIMS)


def test_layer_kinds_follow_the_published_pattern():
    assert layer_kinds(PATTERN, range(9)) == [
        "mamba", "moe", "mamba", "moe", "mamba", "attention", "moe", "mamba",
        "moe"]
    with pytest.raises(ValueError, match="not built"):
        layer_kinds("M-M", [1])
    lm = _tiny()
    assert lm.cache_tag == ("state_pool",) * 6 + ("kv_pool",) * 2 \
        + ("state_pool",) * 2
    assert [tuple(c.shape) for c in lm.init_cache(3, 20)] == \
        [(3, 4, 8, 16), (3, 3, 96)] * 3 + [(3, 20, 16)] * 2 \
        + [(3, 4, 8, 16), (3, 3, 96)]


def test_mamba_starts_as_mamba2_does():
    """A in [1, 16], the softplus of dt_bias in [1e-3, 0.1], D = 1."""
    paddle.seed(7)
    layer = NemotronHModel(**dict(TINY, mamba_num_heads=256,
                                  n_groups=4)).layers[0].mixer
    a = np.exp(layer.A_log.numpy())
    dt = np.log1p(np.exp(layer.dt_bias.numpy()))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 3.0
    assert 1e-3 - 1e-7 <= dt.min() and dt.max() <= 0.1 + 1e-6
    assert np.log(dt).std() > 1.0
    assert (layer.D.numpy() == 1.0).all()


def test_full_forward_and_cached_path_match_the_reference():
    """The full forward, then a padded prompt through the cache and five
    steps, against the reference's full forward; the cached states are the
    reference's at each row's last position."""
    lm = _tiny()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 96, (2, 40)).astype(np.int32)
    n = np.array([33, 9], np.int32)
    at = n[:, None] - 1 + np.arange(6)[None, :]
    want, routing, states = _reference(lm, ids, at, state_at=n + 4)
    choices = []
    with paddle.no_grad():
        got = lm(paddle.to_tensor(ids), paddle.to_tensor(at),
                 choices=choices).numpy()
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert len(choices) == len(routing) == 4
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
        assert len(routes) == 4
        for i in range(5):
            logits, out = lm.forward_cached(
                paddle.to_tensor(ids[np.arange(2), n + i][:, None]), cache,
                paddle.to_tensor(n + i))
            np.testing.assert_allclose(logits.numpy(), want[:, i + 1],
                                       atol=2e-5)
            cache, routes = out[:own], out[own:]
            for mine, ref in zip(routes, routing):
                assert np.array_equal(
                    np.sort(mine.numpy()[:, 0], -1),
                    np.sort(ref["experts"][np.arange(2), n + i], -1))
    mamba = [c for c, tag in zip(cache, lm.cache_tag)
             if tag == "state_pool" and len(c.shape) == 4]
    assert len(mamba) == len(states) == 4
    for mine, ref in zip(mamba, states):
        np.testing.assert_allclose(mine.numpy(), ref, atol=2e-5)
    assert [str(c.dtype) for c in cache] == ["float32"] * own


def test_a_padded_prompt_keeps_the_state_and_rows_of_the_unpadded_one():
    """Rows of different lengths in one padded prompt: each row's logits,
    Mamba states, convolution rows and pages (up to its length) are those
    of the row alone, unpadded; the padding reaches no expert. A step in
    which a row sits at position 0 leaves the live rows as they are."""
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


@pytest.fixture
def monitored():
    was = monitor.enabled()
    paddle.set_flags({"FLAGS_monitor": True})
    monitor.reset()
    yield
    paddle.set_flags({"FLAGS_monitor": was})


def test_engine_streams_the_full_forwards_greedy_tokens(monitored):
    lm = _tiny(seed=3)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (5, 13, 8)]
    new = 6
    eng = LLMEngine(lm, LLMConfig(num_slots=4, max_len=32,
                                  prefill_buckets=(8, 16))).start()
    try:
        before = monitor.snapshot()["counters"]
        streams = [eng.submit(p, max_new_tokens=new) for p in prompts]
        got = [s.result(timeout=120)[1] for s in streams]
        after = monitor.snapshot()["counters"]
    finally:
        eng.stop(drain=False)
    ids = np.zeros((3, 13 + new), np.int32)
    for r, (p, toks) in enumerate(zip(prompts, got)):
        assert len(toks) == new
        ids[r, :len(p) + new] = list(p) + toks
    with paddle.no_grad():
        full = lm(paddle.to_tensor(ids)).numpy()
    for r, (p, toks) in enumerate(zip(prompts, got)):
        assert toks == np.argmax(full[r, len(p) - 1:len(p) - 1 + new],
                                 -1).tolist()
    delta = lambda name: after.get(name, 0) - before.get(name, 0)
    assert {k: delta(k) for k in after
            if "compile" in k or "retrace" in k} == {
        k: 0 for k in after if "compile" in k or "retrace" in k}
    steps = delta("llm.decode.steps")
    assert steps > 0 and delta("llm.decode.pool_donated") == steps
    assert delta("llm.decode.rows_dead") == steps * 4 - 3 * (new - 1) > 0
    state = sum(int(np.prod(s)) * 4 for s in [(4, 4, 8, 16), (4, 3, 96)] * 4)
    assert delta("llm.decode.state_bytes") == steps * state
    assert delta("llm.decode.kv_rows_pool") == steps * 4 * 32
    assert eng.kv_pool_bytes("kv_pool") == 2 * 4 * 32 * 16 * 4
