"""The plain reference of dots.vlm1's language model (`dots_vlm`, a
DeepSeek-V3-shaped decoder): float32 `jax.numpy` at `highest` matmul
precision, independent of `paddle_tpu` (nothing of it is imported). The
equations are DeepSeek-V3's (arXiv:2412.19437, section 2.1), every size the
published config's. u = RMSNorm(x); layer l is the PUBLISHED index:

    x'  = x  + MLA(RMSNorm(x))
    x'' = x' + FFN_l(RMSNorm(x'))         FFN_l = SwiGLU if l < first_k_dense else MoE
    logits = W_head RMSNorm(x_L)          untied; the vocabulary's slice held

MLA in the EXPANDED form, full keys and values a head (never the absorbed
one), the low-rank query, rotary on the rope part only, interleaved pairs:

    c_q = RMSNorm(W_qa u);  [qn; qr] = (W_qb c_q)^h
    [c; kr] = W_kva u;  c = RMSNorm(c);  kr, qr rotated (one kr a position)
    [kn_j^h; v_j^h] = (W_kvb c_j)^h
    a_tj = softmax_{j<=t}(scale (qn_t^h . kn_j^h + qr_t^h . kr_j))
    y_t = W_o concat_h(sum_j a_tj^h v_j^h)

YaRN (`rope_scaling`: factor f, original length L0, beta_fast, beta_slow,
mscale, mscale_all_dim; d rotary dimensions, i < d / 2):

    inv_i = theta^(-2i/d);  turns(b) = d ln(L0 / (2 pi b)) / (2 ln theta)
    low = floor turns(beta_fast), high = ceil turns(beta_slow), in [0, d - 1]
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv'_i = inv_i / f * ramp_i + inv_i (1 - ramp_i)
    m(s) = 0.1 s ln f + 1;  cos, sin x m(mscale) / m(mscale_all_dim)
    scale = (nope + rope)^-0.5 m(mscale_all_dim)^2

MoE as a LOOP over the experts held, one at a time, with a mask (never a
sort or a grouped matmul); the router over all the routed experts is
`reference/ling.py`'s `choose` (the same DeepSeek-V3 rule: sigmoid, a bias
for the choice, the best groups by their two best, top k, weights from the
unbiased scores, normalised and scaled).

It fits beside a model that fills most of a chip: weights come in as the
model's own (bfloat16) arrays and are upcast a layer's mixer, a quarter of
the dense feed-forward or ONE expert at a time; attention runs
`HEAD_BLOCK` heads and `ROW_BLOCK` query rows at a time (a `lax.scan` over
a `lax.map`: at 128 heads and 9,000 positions all heads' keys are 2.4 GB);
the head on the positions asked for only, in blocks of the vocabulary.

Departures from the published model, shared with the program under test and
stated in the configuration file: no multi-token-prediction module and no
vision tower; routes that land on experts not held are left out; the
vocabulary is the slice held.

Weights: a flat `{parameter name: array}` dict under the names the built
model gives them; linear weights are [in, out].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .ling import _HIGHEST, _head_block, _sub, choose, rms_norm

ROW_BLOCK = 256
HEAD_BLOCK = 16
FFN_BLOCKS = 4


def yarn(rope_dim, theta, scaling):
    """(inv' [rope_dim / 2], the cos / sin multiplier, the softmax scale's
    multiplier) of a `rope_scaling` group; (theta's powers, 1, 1) without."""
    i = jnp.arange(rope_dim // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * i / rope_dim)
    if not scaling:
        return inv, 1.0, 1.0
    f, l0 = scaling["factor"], scaling["original_max_position_embeddings"]

    def turns(b):
        return rope_dim * math.log(l0 / (2 * math.pi * b)) \
            / (2 * math.log(theta))
    low = max(math.floor(turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns(scaling["beta_slow"])), rope_dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    m = lambda s: 0.1 * s * math.log(f) + 1.0
    all_dim = m(scaling.get("mscale_all_dim", 0))
    return (inv / f * ramp + inv * (1.0 - ramp),
            m(scaling.get("mscale", 1)) / all_dim, all_dim ** 2)


def rope(x, inv, mult):
    """x [B, T, ..., d] at positions 0..T-1, interleaved pairs (2i, 2i+1)."""
    t, half = x.shape[1], x.shape[-1] // 2
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [T, d/2]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle) * mult, jnp.sin(angle) * mult
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def mla(u, w, *, heads, nope, rope_dim, theta, scaling, eps):
    """u [B, T, hidden] -> [B, T, hidden]."""
    b, t, hidden = u.shape
    latent = w["kv_norm.weight"].shape[0]
    inv, mult, temper = yarn(rope_dim, theta, scaling)
    scale = temper / math.sqrt(nope + rope_dim)
    if "q_down.weight" in w:
        c_q = rms_norm(u @ w["q_down.weight"], w["q_norm.weight"], eps)
        w_q = w["q_up.weight"]
    else:
        c_q, w_q = u, w["q_proj.weight"]
    down = u @ w["kv_down.weight"]
    c = rms_norm(down[..., :latent], w["kv_norm.weight"], eps)
    kr = rope(down[..., latent:], inv, mult)                  # [B, T, rope]
    hb = math.gcd(heads, HEAD_BLOCK)
    blocks = lambda a, axis: jnp.moveaxis(
        a.reshape(a.shape[:axis] + (heads // hb, hb) + a.shape[axis + 1:]),
        axis, 0)
    w_q = blocks(w_q.reshape(w_q.shape[0], heads, nope + rope_dim), 1)
    w_kv = blocks(w["kv_up.weight"].reshape(latent, heads, -1), 1)
    w_o = blocks(w["o_proj.weight"].reshape(heads, -1, hidden), 0)
    pad = -t % ROW_BLOCK
    cols = jnp.arange(t)

    def some_heads(y, ws):
        wq, wkv, wo = ws
        q = jnp.einsum("btr,rhd->bthd", c_q, wq)
        qn, qr = q[..., :nope], rope(q[..., nope:], inv, mult)
        kv = jnp.einsum("btl,lhd->bthd", c, wkv)
        kn, v = kv[..., :nope], kv[..., nope:]

        def some_rows(args):
            r0, qn, qr = args
            s = (jnp.einsum("bthd,bjhd->bhtj", qn, kn)
                 + jnp.einsum("bthd,bjd->bhtj", qr, kr)) * scale
            keep = (r0 + jnp.arange(ROW_BLOCK))[:, None] >= cols[None, :]
            p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            return jnp.einsum("bhtj,bjhd->bthd", p, v)

        rows = lambda a: jnp.moveaxis(
            jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
                b, -1, ROW_BLOCK, hb, a.shape[-1]), 1, 0)
        o = jax.lax.map(some_rows, (jnp.arange(0, t + pad, ROW_BLOCK),
                                    rows(qn), rows(qr)))
        o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, hb, -1)[:, :t]
        return y + jnp.einsum("bthd,hdo->bto", o, wo), None

    return jax.lax.scan(some_heads, jnp.zeros_like(u), (w_q, w_kv, w_o))[0]


@functools.partial(jax.jit, static_argnames=("latent", "scale"))
def latent_step(qn, qr, rows, n, w_ukv, *, latent: int, scale: float):
    """One decode step's attention of one sequence, EXPANDED: qn [H, nope],
    qr [H, rope] (rotated), rows [L, >= latent + rope] of which the first n
    are the sequence's kept rows [c; kr; ...] (this step's included; n is
    data, so one program serves every length up to L), w_ukv [latent,
    H (nope + v)] -> [H, v]: every head's keys and values built from the
    rows, softmax over the n."""
    with _HIGHEST():
        heads, nope = qn.shape
        rope_dim = qr.shape[-1]
        rows, w = rows.astype(jnp.float32), w_ukv.astype(jnp.float32)
        c, kr = rows[:, :latent], rows[:, latent:latent + rope_dim]
        kv = (c @ w).reshape(c.shape[0], heads, -1)
        kn, v = kv[..., :nope], kv[..., nope:]
        s = (jnp.einsum("hd,jhd->hj", qn.astype(jnp.float32), kn)
             + qr.astype(jnp.float32) @ kr.T) * scale
        s = jnp.where(jnp.arange(rows.shape[0])[None, :] < n, s, -jnp.inf)
        return jnp.einsum("hj,jhd->hd", jax.nn.softmax(s, axis=-1), v)


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rope_dim",
                                             "theta", "scaling", "eps"))
def _mixer(x, w_norm, w, *, heads, nope, rope_dim, theta, scaling, eps):
    with _HIGHEST():
        w = {k: a.astype(jnp.float32) for k, a in w.items()}
        u = rms_norm(x, w_norm.astype(jnp.float32), eps)
        return x + mla(u, w, heads=heads, nope=nope, rope_dim=rope_dim,
                       theta=theta, scaling=dict(scaling), eps=eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, w_norm, w, *, eps):
    """x + SwiGLU(RMSNorm(x)), the intermediate width a part at a time."""
    with _HIGHEST():
        m = rms_norm(x, w_norm.astype(jnp.float32), eps)
        width = w["gate_proj.weight"].shape[1]
        step = -(-width // FFN_BLOCKS)
        for c0 in range(0, width, step):
            gate, up = (w[k][:, c0:c0 + step].astype(jnp.float32)
                        for k in ("gate_proj.weight", "up_proj.weight"))
            x = x + (jax.nn.silu(m @ gate) * (m @ up)) \
                @ w["down_proj.weight"][c0:c0 + step].astype(jnp.float32)
        return x


@jax.jit
def _one_expert(y, m, mask_w, gate, up, down):
    """y + mask_w [T, 1] * expert(m): zero for the rows that did not choose
    this expert."""
    with _HIGHEST():
        gate, up, down = (a.astype(jnp.float32) for a in (gate, up, down))
        return y + mask_w * ((jax.nn.silu(m @ gate) * (m @ up)) @ down)


def moe(m, w, *, first, top_k, n_group, topk_group, scaling, forced=None):
    """m [T, hidden]; the experts held are first .. first + count - 1.
    Returns (y [T, hidden], experts [T, top_k], expert margin, group
    margin, s' [T, n_routed_experts]): the reference's OWN choice and
    margins. With `forced` [T, top_k] the sum runs over those experts
    instead (weights from the reference's own s): a choice inside the
    margin may fall either way, and the sum of another expert is another
    number."""
    with _HIGHEST():
        m32 = m.astype(jnp.float32)
        bias = w["router_bias"].astype(jnp.float32)
        experts, weights, margin, group_margin, biased = choose(
            m32, w["router"].astype(jnp.float32), bias, top_k=top_k,
            n_group=n_group, topk_group=topk_group, scaling=scaling)
        used = experts
        if forced is not None:
            used = jnp.asarray(forced)
            s = jnp.take_along_axis(biased - bias, used, axis=1)
            weights = scaling * s / s.sum(-1, keepdims=True)
        # [T, routed]: a route's weight under its expert, zero elsewhere
        dense = jnp.zeros((m.shape[0], w["router"].shape[1]), jnp.float32).at[
            jnp.arange(m.shape[0])[:, None], used].add(weights)
        y = (jax.nn.silu(m32 @ w["shared_gate.weight"].astype(jnp.float32))
             * (m32 @ w["shared_up.weight"].astype(jnp.float32))
             ) @ w["shared_down.weight"].astype(jnp.float32)
    for e in range(w["gate_proj"].shape[0]):
        y = _one_expert(y, m32, dense[:, first + e, None], w["gate_proj"][e],
                        w["up_proj"][e], w["down_proj"][e])
    return y, experts, margin, group_margin, biased


def forward(named: dict, ids, at, *, dense_layers, heads: int, first: int,
            top_k: int, n_group: int, topk_group: int, scaling: float,
            nope: int, rope_dim: int, theta: float, rope_scaling, eps: float,
            prefix: str = "dots", forced=None):
    """Full forward of tokens `ids` [B, T] through the layers held;
    `dense_layers` [bool a layer held]: whose feed-forward is the dense
    SwiGLU. Returns (logits [B, P, V] at the positions `at` [B, P] only,
    and per expert layer a dict of `experts` [B, T, top_k], `margin`
    [B, T], `group_margin` [B, T] and `biased` [B, T, n_routed_experts],
    the scores s' the choice was made by). `forced`: per expert layer the
    experts [B, T, top_k] to sum over in place of the reference's own (see
    `moe`); what is returned per layer is still the reference's own choice,
    on the state the forced sums left."""
    ids = jnp.asarray(ids)
    b, t = ids.shape
    x = jnp.asarray(named[prefix + ".embed_tokens.weight"]
                    )[ids].astype(jnp.float32)
    frozen = tuple(sorted((rope_scaling or {}).items()))
    routing = []
    for i, dense in enumerate(dense_layers):
        w = _sub(named, f"{prefix}.layers.{i}.")
        x = _mixer(x, w["input_norm.weight"], _sub(w, "mixer."), heads=heads,
                   nope=nope, rope_dim=rope_dim, theta=float(theta),
                   scaling=frozen, eps=float(eps))
        if dense:
            x = _dense_ffn(x, w["post_norm.weight"], _sub(w, "mlp."),
                           eps=float(eps))
            continue
        with _HIGHEST():
            m = rms_norm(x, w["post_norm.weight"].astype(jnp.float32), eps)
        y, experts, margin, group_margin, biased = moe(
            m.reshape(b * t, -1), _sub(w, "mlp."), first=first, top_k=top_k,
            n_group=n_group, topk_group=topk_group, scaling=scaling,
            forced=None if forced is None else jnp.asarray(
                forced[len(routing)]).reshape(b * t, top_k))
        x = x + y.reshape(b, t, -1)
        routing.append({"experts": experts.reshape(b, t, top_k),
                        "margin": margin.reshape(b, t),
                        "group_margin": group_margin.reshape(b, t),
                        "biased": biased.reshape(b, t, -1)})
    h = jnp.take_along_axis(x, jnp.asarray(at)[..., None], axis=1)
    with _HIGHEST():
        h = rms_norm(h, jnp.asarray(named[prefix + ".norm.weight"],
                                    jnp.float32), eps)
    head = named["lm_head.weight"]
    block = 16384
    logits = jnp.concatenate(
        [_head_block(h, head[:, v0:v0 + block])
         for v0 in range(0, head.shape[1], block)], axis=-1)
    return logits, routing
