"""The plain reference of Brumby (power-retention layers in a Qwen3-shaped
decoder): float32 `jax.numpy` at `highest` matmul precision, independent of
`paddle_tpu` (nothing of it is imported).

It is written in the ATTENTION form of power retention: no state, no
chunks, no feature map phi. For a query head h over key/value head
g = h // (H / G), d the head size, p = 2:

    u_t   = RMSNorm(x_t; w_in)
    q_t^h = RoPE_t(RMSNorm_d(W_q u_t)^h)   k_t^g = RoPE_t(RMSNorm_d(W_k u_t)^g)
    v_t^g = (W_v u_t)^g                    l_t^g = logsigmoid((W_g u_t + b_g)^g)
    a_tj  = ((q_t^h . k_j^g) / sqrt(d))^p * exp(sum_{i=j+1..t} l_i^g),  j <= t
    y_t^h = sum_j a_tj v_j^g / (sum_j a_tj + eps)
    x'_t  = x_t + W_o concat_h(y_t^h)
    x''_t = x'_t + W_down(silu(W_gate m_t) * (W_up m_t)),  m_t = RMSNorm(x'_t; w_post)
    logits = W_head RMSNorm(x_L; w_f)                      (untied head)

so it shares neither form with the program under test (a chunked prompt
form and a one-token state update). RoPE is the half-split (Qwen)
convention. Causal, so right-padding a row changes nothing to its left.

It fits beside a model that fills most of a chip: weights come in as the
model's own (bfloat16) arrays and are upcast one layer at a time, scores
are built in blocks of rows, and the head runs on the positions asked for
only, in blocks of the vocabulary.

Weights: a flat `{parameter name: array}` dict under the names the built
model gives them; linear weights are [in, out].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

POWER = 2
EPS = 1e-6
ROW_BLOCK = 256
VOCAB_BLOCK = 16384
_LAYER_KEYS = ("input_norm.weight", "post_norm.weight",
               "retention.q_proj.weight", "retention.k_proj.weight",
               "retention.v_proj.weight", "retention.o_proj.weight",
               "retention.g_proj.weight", "retention.g_proj.bias",
               "retention.q_norm.weight", "retention.k_norm.weight",
               "mlp.gate_proj.weight", "mlp.up_proj.weight",
               "mlp.down_proj.weight")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rope(x, theta):
    """x [B, T, heads, d] at positions 0..T-1, half-split."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [T, d/2]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention(q, k, v, log_g):
    """The attention form. q [B, T, H, d]; k, v [B, T, G, d]; log_g
    [B, T, G] -> y [B, T, H, d]."""
    b, t, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(b, t, g, h // g, d)
    cum = jnp.cumsum(log_g, axis=1).transpose(0, 2, 1)           # [B, G, T]
    cols = jnp.arange(t)
    out = []
    for r0 in range(0, t, ROW_BLOCK):
        r1 = min(r0 + ROW_BLOCK, t)
        s = jnp.einsum("btgnd,bjgd->bgntj", q[:, r0:r1], k) / math.sqrt(d)
        keep = jnp.arange(r0, r1)[:, None] >= cols[None, :]
        gap = cum[:, :, r0:r1, None] - cum[:, :, None, :]
        a = s ** POWER * jnp.exp(jnp.where(keep, gap, -jnp.inf))[:, :, None]
        y = jnp.einsum("bgntj,bjgd->btgnd", a, v)
        out.append(y / (a.sum(-1).transpose(0, 3, 1, 2)[..., None] + EPS))
    return jnp.concatenate(out, axis=1).reshape(b, t, h, d)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps"))
def layer(x, w, *, heads, kv_heads, theta, eps):
    """One block; `w` holds the layer's arrays under `_LAYER_KEYS`, in
    whatever dtype the model keeps them."""
    with jax.default_matmul_precision("highest"):
        w = {k: a.astype(jnp.float32) for k, a in w.items()}
        b, t, _ = x.shape
        d = w["retention.q_norm.weight"].shape[0]
        u = rms_norm(x, w["input_norm.weight"], eps)
        q = (u @ w["retention.q_proj.weight"]).reshape(b, t, heads, d)
        k = (u @ w["retention.k_proj.weight"]).reshape(b, t, kv_heads, d)
        v = (u @ w["retention.v_proj.weight"]).reshape(b, t, kv_heads, d)
        q = rope(rms_norm(q, w["retention.q_norm.weight"], eps), theta)
        k = rope(rms_norm(k, w["retention.k_norm.weight"], eps), theta)
        log_g = jax.nn.log_sigmoid(u @ w["retention.g_proj.weight"]
                                   + w["retention.g_proj.bias"])
        y = retention(q, k, v, log_g).reshape(b, t, heads * d)
        x = x + y @ w["retention.o_proj.weight"]
        m = rms_norm(x, w["post_norm.weight"], eps)
        ff = jax.nn.silu(m @ w["mlp.gate_proj.weight"]) \
            * (m @ w["mlp.up_proj.weight"])
        return x + ff @ w["mlp.down_proj.weight"]


@jax.jit
def _head_block(h, w):
    with jax.default_matmul_precision("highest"):
        return h @ w.astype(jnp.float32)


def logits_at(named: dict, ids, at, *, n_layers: int, heads: int,
              kv_heads: int, theta: float, eps: float,
              prefix: str = "brumby"):
    """Full forward of tokens `ids` [B, T]; logits [B, P, V] at the
    positions `at` [B, P] only."""
    x = jnp.asarray(named[prefix + ".embed_tokens.weight"]
                    )[ids].astype(jnp.float32)
    for i in range(n_layers):
        w = {k: named[f"{prefix}.layers.{i}.{k}"] for k in _LAYER_KEYS}
        x = layer(x, w, heads=heads, kv_heads=kv_heads, theta=float(theta),
                  eps=float(eps))
    h = jnp.take_along_axis(x, jnp.asarray(at)[..., None], axis=1)
    h = rms_norm(h, jnp.asarray(named[prefix + ".norm.weight"], jnp.float32),
                 eps)
    head = named["lm_head.weight"]
    return jnp.concatenate(
        [_head_block(h, head[:, v0:v0 + VOCAB_BLOCK])
         for v0 in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
