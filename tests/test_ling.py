"""Ling (`models/ling.py`): the routed expert layer that is told which
experts it holds, KDA's two forms, latent attention's two paths, the model
against the plain reference (`benchmarks/reference/ling.py`), and a tiny
Ling through `LLMEngine` with its mixed pool."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import ling as reference
from paddle_tpu import monitor, nn
from paddle_tpu.models.ling import LingForCausalLM, LingModel, layer_kinds
from paddle_tpu.nn import functional as F
from paddle_tpu.serving import LLMConfig, LLMEngine

kda = importlib.import_module("paddle_tpu.kernels.kda")
gm = importlib.import_module("paddle_tpu.kernels.grouped_matmul")
routed = importlib.import_module("paddle_tpu.nn.layer.routed_experts")

ROUTER = dict(top_k=4, n_group=4, topk_group=2, scaling=2.5)


def _experts(held=None, seed=0, bias_std=0.1, shared=24):
    paddle.seed(seed)
    layer = nn.RoutedExperts(32, 24, 16, ROUTER["top_k"], ROUTER["n_group"],
                             ROUTER["topk_group"], ROUTER["scaling"],
                             held=held, shared_width=shared)
    layer.router_bias.set_value(
        np.random.default_rng(seed).normal(0, bias_std, 16).astype("float32"))
    return layer


def _named(layer):
    return {k: p._value for k, p in layer.named_parameters()}


def _rows(t=40, seed=1):
    return np.random.default_rng(seed).normal(size=(t, 32)).astype("float32")


# ---- the routed expert layer ----------------------------------------------

def test_routed_experts_match_a_loop_over_experts():
    layer, m = _experts(), _rows()
    got, chosen, scores = layer(paddle.to_tensor(m), return_choice=True)
    want, experts, _, _, biased = reference.moe(jnp.asarray(m), _named(layer),
                                        first=0, **ROUTER)
    np.testing.assert_allclose(got.numpy(), want, atol=2e-5)
    assert np.array_equal(np.sort(chosen.numpy(), -1), np.sort(experts, -1))
    np.testing.assert_allclose(scores.numpy(), np.asarray(biased), atol=1e-6)


def test_the_choice_is_group_limited_and_the_bias_only_chooses():
    """Expert 5's score is the largest of all but its group's two best
    together are not among the two best groups: it is not chosen. A bias
    moves the choice and never a weight."""
    logits = np.full((1, 16), -3.0, np.float32)
    logits[0, 5] = 4.0                      # group 1: one high score
    logits[0, [0, 1, 2, 3]] = 2.0           # group 0
    logits[0, [8, 9, 10]] = 1.0             # group 2
    bias = np.zeros(16, np.float32)
    experts, w, _ = routed.route(jnp.asarray(logits), jnp.asarray(bias), 4, 4,
                              2, 2.5)
    assert sorted(np.asarray(experts)[0].tolist()) == [0, 1, 2, 3]
    np.testing.assert_allclose(np.asarray(w).sum(), 2.5, rtol=1e-6)
    bias[12:16] = 5.0                       # group 3 now wins on the bias
    experts, w, _ = routed.route(jnp.asarray(logits), jnp.asarray(bias), 4, 4,
                              2, 2.5)
    assert sorted(np.asarray(experts)[0].tolist()) == [12, 13, 14, 15]
    s = 1 / (1 + np.exp(3.0))               # the weights come from s, not s'
    np.testing.assert_allclose(np.asarray(w)[0], 2.5 * s / (4 * s), rtol=1e-5)


def test_no_token_is_dropped_when_every_row_picks_the_same_experts():
    layer = _experts()
    m = np.repeat(_rows(1), 300, axis=0)           # 300 rows, one choice
    got = layer(paddle.to_tensor(m)).numpy()
    want = reference.moe(jnp.asarray(m[:1]), _named(layer), first=0,
                         **ROUTER)[0]
    np.testing.assert_allclose(got, np.repeat(want, 300, 0), atol=2e-5)


@pytest.mark.parametrize("ranks", [4, 8, 16])
def test_the_shares_add_up_to_the_uncut_layer(ranks):
    """`ranks` ranks share the 16 experts (four each: a whole group, Ling's
    deployment; two: half a group, Dots'; one): their outputs, the shared
    expert counted once, sum to the reference's uncut layer."""
    whole, m = _experts(), _rows(64)
    named = _named(whole)
    want = np.asarray(reference.moe(jnp.asarray(m), named, first=0,
                                    **ROUTER)[0])
    shared = np.asarray(whole.shared_down(
        F.silu(whole.shared_gate(paddle.to_tensor(m)))
        * whole.shared_up(paddle.to_tensor(m))).numpy())
    total = np.zeros_like(want)
    each = 16 // ranks
    for rank in range(ranks):
        part = _experts(held=(each * rank, each))
        for name, p in part.named_parameters():
            src = named[name]
            p.set_value(src[each * rank:each * (rank + 1)] if name in (
                "gate_proj", "up_proj", "down_proj") else src)
        mine = part(paddle.to_tensor(m)).numpy()
        ref_part = reference.moe(jnp.asarray(m), _named(part),
                                 first=each * rank, **ROUTER)[0]
        np.testing.assert_allclose(mine, ref_part, atol=2e-5)
        total += mine - shared
    np.testing.assert_allclose(total + shared, want, atol=5e-5)


@pytest.mark.parametrize("t", [40, 8])
def test_rows_that_carry_no_token_are_routed_nowhere(monkeypatch, t):
    """`live`: a live row's output is the unmasked call's to the bit, a dead
    row's is the shared expert's alone, the grouped layout holds the tiles
    of the live rows' routes and no other (at 8 rows: fewer tiles than all
    rows' routes take, so fewer experts read), and the router still
    reports every row's choice."""
    layer, m = _experts(held=(4, 8)), _rows(t)
    live = np.arange(t) % 2 == 0
    seen = []
    grouped = routed.experts_pass
    monkeypatch.setattr(routed, "experts_pass", lambda m, local, *a: seen.append(
        np.asarray(local)) or grouped(m, local, *a))
    x = paddle.to_tensor(m)
    full, chosen, scores = layer(x, return_choice=True)
    got, chosen_m, scores_m = layer(x, return_choice=True,
                                    live=paddle.to_tensor(live))
    assert np.array_equal(got.numpy()[live], full.numpy()[live])
    shared = layer.shared_down(F.silu(layer.shared_gate(x))
                               * layer.shared_up(x)).numpy()
    assert np.array_equal(got.numpy()[~live], shared[~live])
    assert not np.array_equal(full.numpy()[~live], shared[~live])
    # the choice is every row's, dead or live
    assert np.array_equal(chosen_m.numpy(), chosen.numpy())
    assert np.array_equal(scores_m.numpy(), scores.numpy())
    # what went into the grouped layout: the live rows' routes to the 8
    # held experts (4..11), everything else in the group that goes nowhere
    local = chosen.numpy() - 4
    local = np.where((local >= 0) & (local < 8), local, 8)
    assert np.array_equal(seen[0], local)
    assert np.array_equal(seen[1], np.where(live[:, None], local, 8))
    tile = gm.tile_rows_for(seen[1].size)
    tiles_of = gm.layout(jnp.asarray(seen[1].reshape(-1)), 8, tile)[3]
    want = [-(-int((local[live] == e).sum()) // tile) for e in range(8)]
    assert np.asarray(tiles_of).tolist() == want
    every = int(gm.layout(jnp.asarray(local.reshape(-1)), 8, tile)[3].sum())
    assert sum(want) <= every and (t != 8 or sum(want) < every)


@pytest.mark.parametrize("rows,tile", [(24, 16), (700, 128)])
def test_grouped_matmul_kernel_matches_ragged_dot(rows, tile):
    rng = np.random.default_rng(rows)
    groups, k, n = 5, 32, 256
    group_of = rng.integers(0, groups + 1, rows).astype(np.int32)
    group_of[group_of == 3] = 0                    # an empty group
    place, tile_group, active, tiles_of = gm.layout(jnp.asarray(group_of),
                                                    groups, tile)
    total = gm.padded_rows(rows, groups, tile)
    assert int(active[0]) == int(tiles_of.sum()) <= total // tile
    here = group_of < groups
    assert len(set(np.asarray(place)[here].tolist())) == here.sum()
    assert np.all(np.asarray(place)[~here] == total)
    # a row's place lies in a tile of its group
    assert np.array_equal(
        np.asarray(tile_group)[np.asarray(place)[here] // tile],
        group_of[here])
    x = jnp.asarray(rng.normal(size=(total, k)).astype(np.float32))
    ws = tuple(jnp.asarray(rng.normal(size=(groups, k, n)).astype(np.float32)
                           / 6) for _ in range(2))
    live = int(active[0]) * tile
    for w in (ws, ws[:1]):
        got = gm._pallas(x, tile_group, active, w, tile, True)
        want = gm._ragged(x, tiles_of, w, tile)
        np.testing.assert_allclose(np.asarray(got)[:live],
                                   np.asarray(want)[:live], atol=1e-4)


# ---- KDA -------------------------------------------------------------------

def _kda_inputs(b=2, t=150, h=3, dk=32, dv=16, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(b, t, h, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(b, t, h, dk)) + 0.5)
    v = rng.normal(size=(b, t, h, dv))
    g = -5 / (1 + np.exp(-3 * rng.normal(size=(b, t, h, dk))))
    beta = 1 / (1 + np.exp(-rng.normal(size=(b, t, h))))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


@pytest.mark.parametrize("chunk", [64, 48, 16])
def test_kda_chunked_matches_the_reference_recurrence(chunk):
    """A chunk that does not divide the length (150 = 2 x 64 + 22)."""
    q, k, v, g, beta = _kda_inputs()
    want = reference.delta_rule(q, k, v, g, beta)
    got, state = kda.kda_chunked(q, k, v, g, beta, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the state it leaves carries on through the step form
    more = _kda_inputs(t=3, seed=1)
    both = tuple(jnp.concatenate([a, b], 1) for a, b in
                 zip((q, k, v, g, beta), more))
    want = reference.delta_rule(*both)[:, 150:]
    for i in range(3):
        o, state = kda.kda_step(*(a[:, i] for a in more), state)
        np.testing.assert_allclose(o, want[:, i], atol=2e-6)


def test_kda_length_mask_inside_a_padded_bucket():
    q, k, v, g, beta = _kda_inputs()
    lengths = jnp.asarray([37, 150], jnp.int32)
    got, state = kda.kda_chunked(q, k, v, g, beta, lengths)
    want, alone = kda.kda_chunked(q[:1, :37], k[:1, :37], v[:1, :37],
                                  g[:1, :37], beta[:1, :37])
    np.testing.assert_allclose(got[0, :37], want[0], atol=2e-6)
    np.testing.assert_allclose(state[0], alone[0], atol=2e-6)
    assert bool(jnp.isfinite(got).all())


def test_kda_gates_at_the_lower_bound_for_a_whole_chunk():
    q, k, v, g, beta = _kda_inputs(t=130)
    g = jnp.full_like(g, -5.0)
    want = reference.delta_rule(q, k, v, g, beta)
    got, _ = kda.kda_chunked(q, k, v, g, beta)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_kda_step_kernel_matches_its_jnp_lowering():
    q, k, v, g, beta = (a[:, 5] for a in _kda_inputs(h=32))
    state = jnp.asarray(np.random.default_rng(3).normal(
        size=(2, 32, 32, 16)), jnp.float32)
    want_o, want_s = kda._step_jnp(q, k, v, g, beta, state)
    got_o, got_s = kda._step_pallas(q, k, v, g, beta, state, interpret=True)
    np.testing.assert_allclose(got_o, want_o, atol=1e-6)
    np.testing.assert_allclose(got_s, want_s, atol=1e-6)


def test_short_convolution_keeps_the_rows_before_the_length():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 9, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    want = reference.short_conv(x, w)
    y, rows = kda.short_conv_prompt(x, w, jnp.asarray([2, 7]))
    np.testing.assert_allclose(y, want, atol=1e-6)
    np.testing.assert_allclose(rows[0], jnp.pad(x[0, :2], ((1, 0), (0, 0))))
    np.testing.assert_allclose(rows[1], x[1, 4:7])
    y7, rows = kda.short_conv_step(x[1:2, 7], w, rows[1:])
    np.testing.assert_allclose(y7[0], want[1, 7], atol=1e-6)
    np.testing.assert_allclose(rows[0], x[1, 5:8])


# ---- latent attention ------------------------------------------------------

def test_latent_attention_paths_agree_with_the_expanded_form():
    rng = np.random.default_rng(0)
    b, t, h, nope, rope, lat, vd = 2, 11, 3, 8, 4, 12, 8
    u = jnp.asarray(rng.normal(size=(b, t, 16)), jnp.float32)
    w = {"q_proj.weight": rng.normal(size=(16, h * (nope + rope))) / 4,
         "kv_down.weight": rng.normal(size=(16, lat + rope)) / 4,
         "kv_norm.weight": 1 + rng.normal(size=(lat,)) / 10,
         "kv_up.weight": rng.normal(size=(lat, h * (nope + vd))) / 3,
         "o_proj.weight": np.eye(h * vd)}
    w = {k: jnp.asarray(a, jnp.float32) for k, a in w.items()}
    want = reference.mla(u, w, heads=h, nope=nope, rope_dim=rope,
                         theta=1e4, eps=1e-6).reshape(b, t, h, vd)
    pos = paddle.to_tensor(np.tile(np.arange(t, dtype=np.int32), (b, 1)))
    q = (u @ w["q_proj.weight"]).reshape(b, t, h, nope + rope)
    down = u @ w["kv_down.weight"]
    qr = F.rotary_embedding(paddle.to_tensor(q[..., nope:]), pos, 1e4,
                            interleaved=True)
    kr = F.rotary_embedding(paddle.to_tensor(down[:, :, None, lat:]), pos,
                            1e4, interleaved=True).numpy()[:, :, 0]
    c = reference.rms_norm(down[..., :lat], w["kv_norm.weight"], 1e-6)
    got = F.latent_attention_prompt(q[..., :nope], qr, c, kr,
                                    w["kv_up.weight"],
                                    np.asarray([t, t], np.int32)).numpy()
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the absorbed decode step at every position of row 0 and row 1
    page = np.zeros((b, t + 3, lat + rope + 5), np.float32)   # a padded row
    for i in range(t):
        rows = np.concatenate([np.asarray(c[:, i]), kr[:, i]], -1)
        page = F.latent_page_write(page, rows,
                                   np.full((b,), i, np.int32)).numpy()
        y = F.latent_attention_decode(q[:, i, :, :nope], qr.numpy()[:, i],
                                      page, np.full((b,), i, np.int32),
                                      w["kv_up.weight"]).numpy()
        np.testing.assert_allclose(y, want[:, i], atol=2e-5)


# ---- the model -------------------------------------------------------------

TINY = dict(vocab_size=96, hidden_size=32, num_attention_heads=2, head_dim=16,
            intermediate_size=48, moe_intermediate_size=24, num_experts=16,
            num_experts_per_tok=4, n_group=4, topk_group=2,
            moe_shared_expert_intermediate_size=24, held=(4, 8),
            first_k_dense_replace=1, layer_group_size=3, kv_lora_rank=24,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            router_bias_std=0.1)
LAYERS = [0, 3, 4, 5]          # one dense layer, then a period of 3


def _tiny(seed=0, layers=LAYERS):
    paddle.seed(seed)
    lm = LingForCausalLM(LingModel(layers=layers, **TINY))
    lm.eval()
    return lm


def _reference(lm, ids, at, layers=LAYERS):
    return reference.forward(
        {k: p._value for k, p in lm.named_parameters()}, ids, at,
        kinds=layer_kinds(layers, 3, 1), heads=2, first=4, top_k=4,
        n_group=4, topk_group=2, scaling=2.5, nope=16, rope_dim=8,
        theta=6e6, eps=1e-6, lower=-5.0)


def test_layer_kinds_follow_the_published_index():
    kinds = layer_kinds([0, 1, 2, 5, 6, 11], 6, 2)
    assert kinds == [("kda", "dense"), ("kda", "dense"), ("kda", "moe"),
                     ("mla", "moe"), ("kda", "moe"), ("mla", "moe")]
    lm = _tiny()
    assert lm.cache_tag == ("state_pool",) * 6 + ("kv_pool",)
    assert [tuple(c.shape) for c in lm.init_cache(3, 20)] == \
        [(3, 2, 16, 16), (3, 3, 96)] * 3 + [(3, 20, 128)]


def test_full_forward_and_cached_path_match_the_reference():
    lm = _tiny()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 96, (2, 30)).astype(np.int32)
    n = np.array([21, 9], np.int32)
    at = n[:, None] - 1 + np.arange(6)[None, :]
    want, routing = _reference(lm, ids, at)
    choices = []
    with paddle.no_grad():
        got = lm(paddle.to_tensor(ids), paddle.to_tensor(at),
                 choices=choices).numpy()
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert len(choices) == len(routing) == 3
        for (mine, scores), ref in zip(choices, routing):
            assert np.array_equal(np.sort(mine.numpy(), -1),
                                  np.sort(ref["experts"], -1))
            np.testing.assert_allclose(scores.numpy(),
                                       np.asarray(ref["biased"]), atol=1e-5)
        # the cached path reports, after the cache's 7 arrays, the experts
        # each of the 3 expert layers chose: the reference's, a position
        logits, out = lm.forward_cached(
            paddle.to_tensor(ids[:, :24]), lm.init_cache(2, 40),
            paddle.zeros([2], dtype="int32"), paddle.to_tensor(n))
        np.testing.assert_allclose(logits.numpy(), want[:, 0], atol=2e-5)
        cache, routes = out[:7], out[7:]
        for mine, ref in zip(routes, routing):
            for r in range(2):
                assert np.array_equal(
                    np.sort(mine.numpy()[r, :n[r]], -1),
                    np.sort(ref["experts"][r, :n[r]], -1))
        for i in range(5):
            logits, out = lm.forward_cached(
                paddle.to_tensor(ids[np.arange(2), n + i][:, None]), cache,
                paddle.to_tensor(n + i))
            np.testing.assert_allclose(logits.numpy(), want[:, i + 1],
                                       atol=2e-5)
            cache, routes = out[:7], out[7:]
            assert len(routes) == 3
            for mine, ref in zip(routes, routing):
                assert np.array_equal(
                    np.sort(mine.numpy()[:, 0], -1),
                    np.sort(ref["experts"][np.arange(2), n + i], -1))
    assert [str(c.dtype) for c in cache] == ["float32"] * 7


@pytest.mark.parametrize("form", ["step", "prompt"])
def test_rows_without_a_sequence_change_nothing_for_the_live_rows(
        monkeypatch, form):
    """A step in which a row sits at position 0 (a free slot's) gives the
    other rows the logits and cache rows of the all-live step; a prompt
    padded to its bucket gives each row the logits and cache rows of the
    row alone, unpadded. The expert layers were handed the dead rows as
    routes to nowhere, and report every row's choice all the same."""
    lm = _tiny(seed=2)
    own, count = len(lm.cache_tag), TINY["held"][1]
    pages = [tag == "kv_pool" for tag in lm.cache_tag]
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
        # both census tags, each array under its own
        assert [mem.tag_of(t._value)[0] for t in eng._pool] == \
            list(lm.cache_tag)
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
    # the three counter groups, each by its own rule
    state = sum(int(np.prod(s)) * 4 for s in
                [(4, 2, 16, 16), (4, 3, 96)] * 3)
    assert delta("llm.decode.state_bytes") == steps * state
    assert delta("llm.decode.kv_rows_pool") == steps * 4 * 32
    assert delta("llm.decode.kv_rows_live") == sum(
        len(p) + i + 1 for p in prompts for i in range(new - 1))
    assert stats["page_len"] == 32
    # a page's row is [latent 24; rotary key 8] in whole 128 lanes
    assert stats["kv_pool_bytes"] == state + 4 * 32 * 128 * 4
    assert eng.kv_pool_bytes("kv_pool") == 4 * 32 * 128 * 4
    assert eng.kv_pool_bytes("state_pool") == state


def test_engine_programs_hand_the_routes_out_after_the_pool():
    """What the model reports beside its cache leaves both programs after
    the pool's arrays; the pool keeps the cache's arrays and no more."""
    lm = _tiny(seed=5)
    eng = LLMEngine(lm, LLMConfig(num_slots=3, max_len=24,
                                  prefill_buckets=(8,),
                                  warmup_on_start=False))
    prompt = np.arange(1, 6, dtype=np.int32)
    with paddle.no_grad():
        first, bucket, logits, routes = eng._prefill_slot(prompt, 1)
        assert bucket == 8 and len(eng._pool) == len(lm.cache_tag) == 7
        assert [tuple(r.shape) for r in routes] == [(1, 8, 4)] * 3
        outs, donated = eng._decode_pool(
            np.array([0, first, 0], np.int32), np.array([0, 5, 0], np.int32))
    assert donated and len(eng._pool) == 7 and len(outs) == 2 + 7 + 3
    assert [tuple(r.shape) for r in outs[9:]] == [(3, 1, 4)] * 3
    choices = []
    ids = np.concatenate([prompt, [first]])[None].astype(np.int32)
    with paddle.no_grad():
        lm(paddle.to_tensor(ids), choices=choices)
    for got, step, (want, _) in zip(routes, outs[9:], choices):
        assert np.array_equal(np.sort(got.numpy()[0, :5], -1),
                              np.sort(want.numpy()[0, :5], -1))
        assert np.array_equal(np.sort(step.numpy()[1, 0]),
                              np.sort(want.numpy()[0, 5]))


def test_engine_refuses_tags_that_do_not_cover_the_cache():
    from paddle_tpu.serving.engine import ServingError
    lm = _tiny()
    lm.cache_tag = ("state_pool", "kv_pool")
    with pytest.raises(ServingError, match="does not tag"):
        LLMEngine(lm, LLMConfig(num_slots=2, max_len=16))
