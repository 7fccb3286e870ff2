"""Dots (`models/dots.py`): latent attention as the only mixer (the
low-rank query, YaRN), its two kernels' forms interpreted against their
`jax.numpy` references, the model against the plain reference
(`benchmarks/reference/dots.py`), and a tiny Dots through `LLMEngine` with
a pool of latent pages."""
import importlib
import math

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import dots as reference
from paddle_tpu import monitor
from paddle_tpu.models._decoder import LatentAttention
from paddle_tpu.models.dots import DotsForCausalLM, DotsModel
from paddle_tpu.nn import functional as F
from paddle_tpu.serving import LLMConfig, LLMEngine

attention = importlib.import_module("paddle_tpu.nn.functional.attention")
kernel = importlib.import_module("paddle_tpu.kernels.mla_decode")
routed = importlib.import_module("paddle_tpu.nn.layer.routed_experts")

# the published group, at an original length the tiny rows pass
YARN = dict(type="yarn", factor=40, beta_fast=32, beta_slow=1, mscale=1,
            mscale_all_dim=1, original_max_position_embeddings=16)
PUBLISHED = dict(YARN, original_max_position_embeddings=4096)


# ---- YaRN ------------------------------------------------------------------

def test_yarn_frequencies_and_scale_follow_the_closed_form():
    """The published parameters: 4096 positions make 32 turns at dimension
    index 10.47 and one turn at 22.5, so frequency indices up to 10 keep
    theta's powers, from 23 on they are divided by 40, and between the two
    the ramp blends them; m(1) = 0.1 ln 40 + 1."""
    inv = F.yarn_inv_freq(64, 10000.0, 40, 4096, 32, 1)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    turns = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) \
        / (2 * math.log(10000.0))
    assert (math.floor(turns(32)), math.ceil(turns(1))) == (10, 23)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13.0
    np.testing.assert_allclose(
        inv[11:23], plain[11:23] * (ramp / 40 + 1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(inv, np.asarray(reference.yarn(
        64, 10000.0, PUBLISHED)[0]), rtol=1e-6)
    assert abs(F.yarn_mscale(40, 1) - 1.36888794) < 1e-7
    assert F.yarn_mscale(1, 1) == 1.0
    paddle.seed(0)
    layer = LatentAttention(32, 2, 24, 128, 64, 16, 10000.0, 1e-6,
                            q_lora_rank=20, rope_scaling=PUBLISHED)
    assert layer.rope_mscale == 1.0
    assert abs(layer.scale - 192 ** -0.5 * 1.36888794 ** 2) < 1e-7
    np.testing.assert_array_equal(layer.inv_freq, inv)
    plain_layer = LatentAttention(32, 2, 24, 128, 64, 16, 10000.0, 1e-6)
    assert plain_layer.inv_freq is None
    assert abs(plain_layer.scale - 192 ** -0.5) < 1e-12


# ---- the layer -------------------------------------------------------------

@pytest.mark.parametrize("q_lora,scaling", [(None, None), (20, None),
                                            (None, YARN), (20, YARN)])
def test_latent_attention_layer_matches_the_reference(q_lora, scaling):
    """With and without the low-rank query and YaRN: a prompt, then every
    position again as a decode step through the page."""
    paddle.seed(1)
    layer = LatentAttention(32, 3, 24, 16, 8, 16, 10000.0, 1e-6,
                            q_lora_rank=q_lora, rope_scaling=scaling)
    names = [n for n, _ in layer.named_parameters()]
    assert ("q_down.weight" in names) == bool(q_lora)
    assert ("q_proj.weight" in names) == (not q_lora)
    w = {k: p._value for k, p in layer.named_parameters()}
    rng = np.random.default_rng(0)
    u = rng.normal(size=(2, 23, 32)).astype(np.float32)
    want = np.asarray(reference.mla(
        jnp.asarray(u), w, heads=3, nope=16, rope_dim=8, theta=10000.0,
        scaling=scaling, eps=1e-6))
    with paddle.no_grad():
        got, rows = layer.forward_cached(paddle.to_tensor(u), None, None,
                                         None, False)
        np.testing.assert_allclose(got.numpy(), want, atol=2e-5)
        assert tuple(rows.shape) == (2, 23, 32)
        page = paddle.to_tensor(np.zeros((2, 30, layer.page_width),
                                         np.float32))
        for i in range(23):
            y, page = layer.forward_cached(
                paddle.to_tensor(u[:, i:i + 1]), page,
                paddle.to_tensor(np.full((2,), i, np.int32)), None, True)
            np.testing.assert_allclose(y.numpy()[:, 0], want[:, i],
                                       atol=2e-5)
    np.testing.assert_allclose(page.numpy()[:, :23, :32], rows.numpy(),
                               atol=1e-6)


# ---- the decode kernel ------------------------------------------------------

@pytest.mark.parametrize("block,length", [(128, 300), (512, 300), (64, 40),
                                          (128, 128)])
def test_mla_decode_matches_the_dense_read_over_ragged_lengths(block, length):
    """Interpreted: a free slot (position 0), a full page, a length on a
    block's edge and inside one, heads not in whole 8s, a page shorter than
    a block and one that is no whole number of blocks."""
    rng = np.random.default_rng(block + length)
    q = jnp.asarray(rng.normal(size=(5, 3, 128)), jnp.float32)
    page = jnp.asarray(rng.normal(size=(5, length, 128)), jnp.float32)
    pos = jnp.asarray([0, length - 1, min(127, length - 1),
                       min(128, length - 1), length // 3], jnp.int32)
    want = attention._latent_read_dense(q, page, pos, 0.3)
    got = kernel.mla_decode(q, page, pos, 0.3, block_rows=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # rows past a length never reach the result: poisoned, it is bit-equal
    poisoned = np.array(page)
    for i, p in enumerate(np.asarray(pos)):
        poisoned[i, p + 1:] = np.nan
    again = kernel.mla_decode(q, jnp.asarray(poisoned), pos, 0.3,
                              block_rows=block)
    assert np.isfinite(np.asarray(again)).all()
    assert np.array_equal(np.asarray(again), np.asarray(got))


def test_the_decode_step_reads_its_page_through_the_kernel_when_it_engages(
        monkeypatch):
    """`F.latent_attention_decode` on both reads (off a TPU the dense one;
    steered through the kernel, interpreted, as a TPU takes it): the same
    numbers, and statistics held in bfloat16 (the control's) are not."""
    rng = np.random.default_rng(3)
    qn = rng.normal(size=(3, 4, 16)).astype(np.float32)
    qr = rng.normal(size=(3, 4, 8)).astype(np.float32)
    page = rng.normal(size=(3, 700, 128)).astype(np.float32)
    page[..., 32:] = 0.0
    w = (rng.normal(size=(24, 4 * 32)) / 4).astype(np.float32)
    pos = np.array([699, 0, 350], np.int32)
    dense = F.latent_attention_decode(qn, qr, page, pos, w, scale=0.4).numpy()
    monkeypatch.setattr(kernel, "engages", lambda dtype: True)
    through = F.latent_attention_decode(qn, qr, page, pos, w,
                                        scale=0.4).numpy()
    np.testing.assert_allclose(through, dense, atol=1e-5)
    absorbed = jnp.zeros((3, 4, 128)).at[..., :32].set(1.0)
    low = kernel.mla_decode(absorbed, jnp.asarray(page), jnp.asarray(pos),
                            0.4, stats=jnp.bfloat16)
    high = kernel.mla_decode(absorbed, jnp.asarray(page), jnp.asarray(pos),
                             0.4)
    assert float(jnp.abs(low - high).max()) > 1e-3


# ---- the prompt form --------------------------------------------------------

@pytest.mark.parametrize("t", [200, 640])
def test_flash_prompt_form_matches_the_row_block_form_at_192_and_128(t):
    """Score width 128 + 64, value width 128 (the published head), T a
    whole number of blocks or not: the flash kernel, interpreted, against
    the form that holds a block of scores."""
    rng = np.random.default_rng(t)
    b, h, nope, rope, lat, vd = 1, 2, 128, 64, 48, 128
    qn = jnp.asarray(rng.normal(size=(b, t, h, nope)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(b, t, h, rope)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, t, lat)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(b, t, rope)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(lat, h * (nope + vd))) / 7, jnp.float32)
    want = F.latent_attention_prompt(qn, qr, c, kr, w, scale=0.07).numpy()
    got = attention.latent_prompt_flash(qn, qr, c, kr, w, 0.07)
    assert got.shape == (b, t, h, vd)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


# ---- the model -------------------------------------------------------------

TINY = dict(vocab_size=96, hidden_size=32, num_attention_heads=4,
            intermediate_size=48, moe_intermediate_size=24,
            n_routed_experts=16, num_experts_per_tok=4, n_group=4,
            topk_group=2, held=(4, 8), first_k_dense_replace=3,
            q_lora_rank=20, kv_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, rope_scaling=YARN,
            router_bias_std=0.1)
LAYERS = [0, 3, 4]             # one leading dense layer, two expert layers


def _tiny(seed=0):
    paddle.seed(seed)
    lm = DotsForCausalLM(DotsModel(layers=LAYERS, **TINY))
    lm.eval()
    return lm


def _reference(lm, ids, at):
    return reference.forward(
        {k: p._value for k, p in lm.named_parameters()}, ids, at,
        dense_layers=[l < 3 for l in LAYERS], heads=4, first=4, top_k=4,
        n_group=4, topk_group=2, scaling=2.5, nope=16, rope_dim=8,
        theta=1e4, rope_scaling=YARN, eps=1e-6)


def test_the_cache_is_a_latent_page_a_layer():
    lm = _tiny()
    assert lm.cache_tag == "kv_pool"
    assert [layer.ffn_kind for layer in lm.dots.layers] == \
        ["dense", "moe", "moe"]
    assert [tuple(c.shape) for c in lm.init_cache(3, 20)] == [(3, 20, 128)] * 3
    names = {n for n, _ in lm.named_parameters()}
    assert {"dots.layers.0.mixer.q_down.weight",
            "dots.layers.0.mixer.q_norm.weight",
            "dots.layers.0.mlp.gate_proj.weight",
            "dots.layers.1.mlp.router", "dots.layers.2.mlp.shared_up.weight",
            "lm_head.weight"} <= names


def test_full_forward_and_cached_path_match_the_reference():
    """The full forward; then a prompt through the cache and 8 decode
    steps, against the reference's full forward: logits and choices."""
    lm = _tiny()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 96, (2, 34)).astype(np.int32)
    n = np.array([21, 9], np.int32)
    at = n[:, None] - 1 + np.arange(9)[None, :]
    want, routing = _reference(lm, ids, at)
    choices = []
    with paddle.no_grad():
        got = lm(paddle.to_tensor(ids), paddle.to_tensor(at),
                 choices=choices).numpy()
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert len(choices) == len(routing) == 2
        for (mine, scores), ref in zip(choices, routing):
            assert np.array_equal(np.sort(mine.numpy(), -1),
                                  np.sort(ref["experts"], -1))
            np.testing.assert_allclose(scores.numpy(),
                                       np.asarray(ref["biased"]), atol=1e-5)
        logits, out = lm.forward_cached(
            paddle.to_tensor(ids[:, :24]), lm.init_cache(2, 40),
            paddle.zeros([2], dtype="int32"), paddle.to_tensor(n))
        np.testing.assert_allclose(logits.numpy(), want[:, 0], atol=2e-5)
        cache, routes = out[:3], out[3:]
        for mine, ref in zip(routes, routing):
            for r in range(2):
                assert np.array_equal(
                    np.sort(mine.numpy()[r, :n[r]], -1),
                    np.sort(ref["experts"][r, :n[r]], -1))
        for i in range(8):
            logits, out = lm.forward_cached(
                paddle.to_tensor(ids[np.arange(2), n + i][:, None]), cache,
                paddle.to_tensor(n + i))
            np.testing.assert_allclose(logits.numpy(), want[:, i + 1],
                                       atol=2e-5)
            cache, routes = out[:3], out[3:]
            assert len(routes) == 2
            for mine, ref in zip(routes, routing):
                assert np.array_equal(
                    np.sort(mine.numpy()[:, 0], -1),
                    np.sort(ref["experts"][np.arange(2), n + i], -1))
    assert [str(c.dtype) for c in cache] == ["float32"] * 3


@pytest.mark.parametrize("form", ["step", "prompt"])
def test_rows_without_a_sequence_change_nothing_for_the_live_rows(
        monkeypatch, form):
    """A step in which a row sits at position 0 (a free slot's) gives the
    other rows the logits and cache rows of the all-live step; a prompt
    padded to its bucket gives each row the logits and cache rows of the
    row alone, unpadded. The expert layers were handed the dead rows as
    routes to nowhere, and report every row's choice all the same."""
    lm = _tiny(seed=2)
    own, count = len(lm.dots.layers), TINY["held"][1]
    pages = [tag == "kv_pool" for tag in ["kv_pool"] * own]
    seen = []
    grouped = routed.experts_pass
    monkeypatch.setattr(routed, "experts_pass", lambda m, local, *a: seen.append(
        np.asarray(local)) or grouped(m, local, *a))
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 96, (3, 16)).astype(np.int32)
    n = np.array([11, 5, 16], np.int32)

    def same_rows(got, want, r, alone, upto):
        for a, b, page in zip(got[:own], want[:own], pages):
            a, b = a.numpy()[r], b.numpy()[0 if alone else r]
            if page:
                a, b = a[:upto], b[:upto]
            np.testing.assert_allclose(a, b, atol=2e-5)

    with paddle.no_grad():
        logits, out = lm.forward_cached(
            paddle.to_tensor(ids), lm.init_cache(3, 24),
            paddle.zeros([3], dtype="int32"), paddle.to_tensor(n))
        padded = seen[:]
        if form == "prompt":
            for local in padded:
                dead = (np.arange(16)[None, :] >= n[:, None]).reshape(-1)
                assert (local[dead] == count).all()
                assert (local[~dead] < count).any()
            for r in range(3):
                alone, out1 = lm.forward_cached(
                    paddle.to_tensor(ids[r:r + 1, :n[r]]),
                    lm.init_cache(1, 24), paddle.zeros([1], dtype="int32"),
                    paddle.to_tensor(n[r:r + 1]))
                np.testing.assert_allclose(logits.numpy()[r],
                                           alone.numpy()[0], atol=2e-5)
                same_rows(out, out1, r, True, n[r])
                for a, b in zip(out[own:], out1[own:]):
                    assert np.array_equal(a.numpy()[r, :n[r]], b.numpy()[0])
            return
        cache = out[:own]
        tokens = paddle.to_tensor(rng.integers(0, 96, (3, 1)).astype(np.int32))
        every, out_e = lm.forward_cached(tokens, cache, paddle.to_tensor(n))
        del seen[:]
        at = n.copy()
        at[1] = 0
        some, out_s = lm.forward_cached(tokens, cache, paddle.to_tensor(at))
    assert len(seen) == len(out_s) - own > 0
    for local in seen:
        assert (local[1] == count).all() and (local[[0, 2]] < count).any()
    for r in (0, 2):
        np.testing.assert_allclose(some.numpy()[r], every.numpy()[r],
                                   atol=2e-5)
        same_rows(out_s, out_e, r, False, n[r] + 1)
    for a, b in zip(out_s[own:], out_e[own:]):
        assert a.shape == b.shape == [3, 1, 4]
        assert np.array_equal(a.numpy()[[0, 2]], b.numpy()[[0, 2]])


@pytest.fixture
def monitored():
    was = monitor.enabled()
    paddle.set_flags({"FLAGS_monitor": True})
    monitor.reset()
    yield
    paddle.set_flags({"FLAGS_monitor": was})


def test_engine_streams_the_full_forwards_greedy_tokens(monitored):
    from paddle_tpu.obs import memory as mem
    lm = _tiny(seed=3)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (5, 13, 8)]
    new = 6
    paddle.set_flags({"FLAGS_mem_census": True})
    eng = LLMEngine(lm, LLMConfig(num_slots=4, max_len=32,
                                  prefill_buckets=(8, 16))).start()
    try:
        before = monitor.snapshot()["counters"]
        streams = [eng.submit(p, max_new_tokens=new) for p in prompts]
        got = [s.result(timeout=120)[1] for s in streams]
        after = monitor.snapshot()["counters"]
        stats = eng.stats()
        assert [mem.tag_of(t._value)[0] for t in eng._pool] == ["kv_pool"] * 3
    finally:
        eng.stop(drain=False)
        paddle.set_flags({"FLAGS_mem_census": False})
    # one padded full forward of prompts and streamed tokens (causal: the
    # padding changes nothing to its left): every streamed token is the
    # arg-max over the prefix it was produced from
    ids = np.zeros((3, 13 + new), np.int32)
    for r, (p, toks) in enumerate(zip(prompts, got)):
        assert len(toks) == new
        ids[r, :len(p) + new] = list(p) + toks
    with paddle.no_grad():
        full = lm(paddle.to_tensor(ids)).numpy()
    for r, (p, toks) in enumerate(zip(prompts, got)):
        want = np.argmax(full[r, len(p) - 1:len(p) - 1 + new], -1)
        assert toks == want.tolist()
    delta = lambda name: after.get(name, 0) - before.get(name, 0)
    # zero steady-state compiles: the warm-up compiled every program
    assert {k: delta(k) for k in after
            if "compile" in k or "retrace" in k} == {
        k: 0 for k in after if "compile" in k or "retrace" in k}
    steps = delta("llm.decode.steps")
    assert steps > 0 and delta("llm.decode.pool_donated") == steps
    assert delta("llm.decode.rows") == 3 * (new - 1)
    # 4 slots, 3 streams: every step has rows that carry no sequence, and
    # the programs that ran were traced with the mask that routes them
    # nowhere (the tokens above are the full forward's all the same)
    assert delta("llm.decode.rows_dead") == steps * 4 - 3 * (new - 1) > 0
    assert after["moe.masked_traces"] > 0
    # pages only: the page counters run, the state counter does not
    assert "llm.decode.state_bytes" not in after
    assert delta("llm.decode.kv_rows_pool") == steps * 4 * 32
    assert delta("llm.decode.kv_rows_live") == sum(
        len(p) + i + 1 for p in prompts for i in range(new - 1))
    # a page's row is [latent 24; rotary key 8] in whole 128 lanes
    assert stats["kv_pool_bytes"] == 3 * 4 * 32 * 128 * 4
    assert eng.kv_pool_bytes("kv_pool") == stats["kv_pool_bytes"]
    assert eng.kv_pool_bytes("state_pool") == 0


def test_engine_programs_hand_the_routes_out_after_the_pool():
    lm = _tiny(seed=5)
    eng = LLMEngine(lm, LLMConfig(num_slots=3, max_len=24,
                                  prefill_buckets=(8,),
                                  warmup_on_start=False))
    prompt = np.arange(1, 6, dtype=np.int32)
    with paddle.no_grad():
        first, bucket, logits, routes = eng._prefill_slot(prompt, 1)
        assert bucket == 8 and len(eng._pool) == 3
        assert [tuple(r.shape) for r in routes] == [(1, 8, 4)] * 2
        outs, donated = eng._decode_pool(
            np.array([0, first, 0], np.int32), np.array([0, 5, 0], np.int32))
    assert donated and len(eng._pool) == 3 and len(outs) == 2 + 3 + 2
    assert [tuple(r.shape) for r in outs[5:]] == [(3, 1, 4)] * 2
