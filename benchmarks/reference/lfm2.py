"""The plain reference of LFM2-MoE (`lfm2_moe`), as LFM2-24B-A2B has it:
float32 `jax.numpy` at `highest` matmul precision, independent of
`paddle_tpu` (nothing of it is imported). u = RMSNorm(h) throughout;
layer l is the PUBLISHED index, its mixer `layer_types[l]`, its FFN dense
before `num_dense_layers` and routed after:

    h += Mixer_l(RMSNorm(h));  h += FFN_l(RMSNorm(h))
    logits = W_head RMSNorm(h_L)            untied

The gated short convolution, the convolution a LOOP over its taps:

    [B | C | x] = W_in u;  v = B * x
    z_t = sum_{j < K} w_j * v_{t - K + 1 + j}       (zeros before the sequence)
    out = W_out (C * z)

Attention: q and k each through an RMSNorm over the head, rotary at
theta (half-split, positions from 0), each K/V head repeated for its group
of query heads, dense and causal, 1 / sqrt(head_dim), no bias.

The routed FFN, every expert a DENSE loop over all the experts held, in
blocks, with a mask (never a sort or a grouped matmul):

    s = sigmoid(W_r m);  T = the top_k of s + b    (b for the choice only)
    w_e = scaling * s_e / (sum_{e' in T} s_{e'} + 1e-6)
    FFN(m) = sum_{e in T, first <= e < first + count} w_e W2_e(silu(W1_e m) * W3_e m)

(the published `norm_topk_prob` with its 1e-6; the program's router adds
1e-20, a departure its configuration file states). The dense FFN is
W_down(silu(W_gate m) * W_up m).

Weights come in as the model's own (bfloat16) arrays and are upcast a
block, or `EXPERT_BLOCK` experts, at a time; attention's scores in blocks
of rows; the head on the positions asked for only, in blocks of the
vocabulary. Weights: a flat `{parameter name: array}` dict under the names
the built model gives them; linear weights are [in, out].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .ling import _HIGHEST, _head_block, _sub, choose, rms_norm

ROW_BLOCK = 256
VOCAB_BLOCK = 16384
EXPERT_BLOCK = 4
ROUTE_EPS = 1e-6


def rope_half(x, theta):
    """x [B, T, H, d] at positions 0..T-1, half-split: with x = [x1, x2],
    [x1 cos - x2 sin, x2 cos + x1 sin], angle = position theta^(-2i/d)."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv)[None, :, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(v, w):
    """z_t = sum_j w[j] v_{t - K + 1 + j}, a tap at a time: v [B, T, C],
    w [K, C] (w[K - 1] on the current row); zeros before the sequence."""
    k, t = w.shape[0], v.shape[1]
    z = jnp.zeros_like(v)
    for j in range(k):
        shift = k - 1 - j
        past = jnp.pad(v, ((0, 0), (shift, 0), (0, 0)))[:, :t]
        z = z + w[j] * past
    return z


def conv(u, w, rows_at, taps):
    """The gated short convolution. Returns (out, v's K - 1 rows that end
    at position `rows_at` [B]: what a sequence keeps after it)."""
    h = u.shape[-1]
    p = u @ w["in_proj.weight"]
    v = p[..., :h] * p[..., 2 * h:]
    y = p[..., h:2 * h] * short_conv(v, w["conv_weight"])
    at = rows_at[:, None] - (taps - 1) + 1 + jnp.arange(taps - 1)[None, :]
    kept = jnp.take_along_axis(v, jnp.maximum(at, 0)[..., None], axis=1)
    kept = jnp.where((at >= 0)[..., None], kept, 0.0)
    return y @ w["out_proj.weight"], kept


def attention(u, w, *, heads, kv_heads, theta, eps):
    bsz, t, hidden = u.shape
    d = hidden // heads
    q = rms_norm((u @ w["q_proj.weight"]).reshape(bsz, t, heads, d),
                 w["q_layernorm.weight"], eps)
    k = rms_norm((u @ w["k_proj.weight"]).reshape(bsz, t, kv_heads, d),
                 w["k_layernorm.weight"], eps)
    q, k = rope_half(q, theta), rope_half(k, theta)
    v = (u @ w["v_proj.weight"]).reshape(bsz, t, kv_heads, d)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
    cols = jnp.arange(t)
    out = []
    for r0 in range(0, t, ROW_BLOCK):
        r1 = min(r0 + ROW_BLOCK, t)
        s = jnp.einsum("bthd,bjhd->bhtj", q[:, r0:r1], k) / math.sqrt(d)
        keep = jnp.arange(r0, r1)[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhtj,bjhd->bthd", p, v))
    return jnp.concatenate(out, axis=1).reshape(bsz, t, -1) \
        @ w["out_proj.weight"]


@functools.partial(jax.jit, static_argnames=("kind", "dense", "heads",
                                             "kv_heads", "theta", "eps",
                                             "taps"))
def _block_first_half(x, w, rows_at, *, kind, dense, heads, kv_heads, theta,
                      eps, taps):
    """x + mixer(norm(x)), then the dense FFN where the block has one.
    Returns (x, the FFN's normed input, a conv block's kept rows or
    None)."""
    with _HIGHEST():
        w = {k: a.astype(jnp.float32) for k, a in w.items()}
        u = rms_norm(x, w["operator_norm.weight"], eps)
        kept = None
        if kind == "conv":
            y, kept = conv(u, _sub(w, "mixer."), rows_at, taps)
        else:
            y = attention(u, _sub(w, "mixer."), heads=heads,
                          kv_heads=kv_heads, theta=theta, eps=eps)
        x = x + y
        m = rms_norm(x, w["ffn_norm.weight"], eps)
        if dense:
            f = _sub(w, "feed_forward.")
            x = x + (jax.nn.silu(m @ f["gate_proj.weight"])
                     * (m @ f["up_proj.weight"])) @ f["down_proj.weight"]
        return x, m, kept


@jax.jit
def _expert_block(m, dense_w, gate, up, down):
    """sum over this block's experts of dense_w[:, e] * expert_e(m)."""
    with _HIGHEST():
        gate, up, down = (a.astype(jnp.float32) for a in (gate, up, down))
        h = jax.nn.silu(jnp.einsum("td,edw->etw", m, gate)) \
            * jnp.einsum("td,edw->etw", m, up)
        return jnp.einsum("etd,te->td", jnp.einsum("etw,ewd->etd", h, down),
                          dense_w)


def moe(m, w, *, first, top_k, scaling, forced=None):
    """m [T, hidden] float32; the experts held are first .. first + count -
    1. Returns (y [T, hidden], experts [T, top_k], the expert margin [T],
    the group margin [T] (+inf: one group), s' [T, experts]): the
    reference's OWN choice and margins. With `forced` [T, top_k] the sum
    runs over those experts instead, weighed by the reference's own s."""
    with _HIGHEST():
        router = w["router"].astype(jnp.float32)
        bias = w["router_bias"].astype(jnp.float32)
        experts, _, margin, group_margin, biased = choose(
            m, router, bias, top_k=top_k, n_group=1, topk_group=1,
            scaling=scaling)
        used = experts if forced is None else jnp.asarray(forced)
        s = jnp.take_along_axis(biased - bias, used, axis=1)
        weights = scaling * s / (s.sum(-1, keepdims=True) + ROUTE_EPS)
        count = w["up_proj"].shape[0]
        dense = jnp.zeros((m.shape[0], router.shape[1]), jnp.float32).at[
            jnp.arange(m.shape[0])[:, None], used].add(weights)
        dense = dense[:, first:first + count]
    y = jnp.zeros_like(m)
    for e0 in range(0, count, EXPERT_BLOCK):
        e1 = min(e0 + EXPERT_BLOCK, count)
        y = y + _expert_block(m, dense[:, e0:e1], w["gate_proj"][e0:e1],
                              w["up_proj"][e0:e1], w["down_proj"][e0:e1])
    return y, experts, margin, group_margin, biased


def forward(named: dict, ids, at, *, kinds, heads: int, kv_heads: int,
            first: int, top_k: int, scaling: float, theta: float, eps: float,
            taps: int, prefix: str = "model", forced=None, rows_at=None):
    """Full forward of tokens `ids` [B, T] through the blocks held, whose
    kinds are `kinds` [(mixer, ffn)]: mixer "conv" or "attention", ffn
    "dense" or "moe". Returns (logits [B, P, V] at the positions `at` [B,
    P] only, per expert block a dict of `experts` [B, T, top_k], `margin`
    [B, T], `group_margin` [B, T] and `biased` [B, T, experts], and per
    conv block v's last `taps` - 1 rows [B, taps - 1, hidden] that end at
    `rows_at` [B] (default: the last position)). `forced`: per expert
    block the experts [B, T, top_k] to sum over in place of the
    reference's own (see `moe`)."""
    ids = jnp.asarray(ids)
    b, t = ids.shape
    x = jnp.asarray(named[prefix + ".embed_tokens.weight"]
                    )[ids].astype(jnp.float32)
    stop = jnp.full((b,), t - 1) if rows_at is None else jnp.asarray(rows_at)
    routing, rows = [], []
    for i, (kind, ffn) in enumerate(kinds):
        w = _sub(named, f"{prefix}.layers.{i}.")
        if ffn == "moe":
            inside = {k: a for k, a in w.items()
                      if not k.startswith("feed_forward.")}
        else:
            inside = w
        x, m, kept = _block_first_half(
            x, inside, stop, kind=kind, dense=ffn == "dense", heads=heads,
            kv_heads=kv_heads, theta=float(theta), eps=float(eps), taps=taps)
        if kept is not None:
            rows.append(kept)
        if ffn != "moe":
            continue
        y, experts, margin, group_margin, biased = moe(
            m.reshape(b * t, -1), _sub(w, "feed_forward."), first=first,
            top_k=top_k, scaling=scaling,
            forced=None if forced is None else jnp.asarray(
                forced[len(routing)]).reshape(b * t, top_k))
        x = x + y.reshape(b, t, -1)
        routing.append({"experts": experts.reshape(b, t, top_k),
                        "margin": margin.reshape(b, t),
                        "group_margin": group_margin.reshape(b, t),
                        "biased": biased.reshape(b, t, -1)})
    h = jnp.take_along_axis(x, jnp.asarray(at)[..., None], axis=1)
    with _HIGHEST():
        h = rms_norm(h, jnp.asarray(named[prefix + ".embedding_norm.weight"],
                                    jnp.float32), eps)
    head = named["lm_head.weight"]
    logits = jnp.concatenate(
        [_head_block(h, head[:, v0:v0 + VOCAB_BLOCK])
         for v0 in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
    return logits, routing, rows
