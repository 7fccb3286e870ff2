"""Attention functionals.

Reference parity: the reference's `fused_attention_op.cu` /
`operators/fused/fmha_ref.h` (unfused-softmax FMHA). TPU-first: a single
jitted softmax(QK^T)V graph that XLA fuses; on TPU hardware the Pallas
flash-attention kernel (paddle_tpu.kernels.flash_attention) is used for
long sequences.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...ops._dispatch import ensure_tensor, run_op
from ...ops.math import _precision


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """query/key/value: [batch, seqlen, num_heads, head_dim] (paddle layout).

    Uses the Pallas flash-attention kernel on TPU for seq_len >= 1024 with no
    custom mask; otherwise the fused XLA reference path.
    """
    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    mask_arr = ensure_tensor(attn_mask)._value if attn_mask is not None else None

    seq_len = q.shape[1]
    head_dim = q.shape[-1]
    # measured crossover on v5e (fwd+bwd): with bf16 inputs the native-dtype
    # MXU dots win from 1k up (2.2x at 1k, 2.7x at 2k, 5.7x at 8k); fp32
    # inputs keep the old 4k crossover (fp32 MXU dots were only at parity
    # there). The Pallas kernel also keeps memory O(S).
    # threshold keyed on the PROMOTED dtype: bf16 q against an fp32 KV
    # cache runs fp32 dots inside the kernel (operands are promoted at the
    # flash boundary), where the old 4k crossover still applies
    _promoted = jnp.result_type(q._value.dtype, k._value.dtype, v._value.dtype)
    _flash_min_seq = 1024 if _promoted == jnp.bfloat16 else 4096
    use_flash = mask_arr is None and dropout_p == 0.0 \
        and seq_len >= _flash_min_seq \
        and k.shape[1] == seq_len and v.shape[1] == seq_len \
        and head_dim in (64, 128, 256) and jax.default_backend() == "tpu"
    if use_flash:
        from ...kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=is_causal)

    scale = 1.0 / math.sqrt(head_dim)
    # key drawn OUTSIDE the traced fn: drawing inside would leak a tracer
    # into the global RNG state under the eager dispatch cache
    drop_key = None
    if dropout_p > 0.0 and training:
        from ...core import random as rnd
        drop_key = rnd.next_key()

    def f(qa, ka, va):
        # [B,S,H,D] -> [B,H,S,D]
        qa = jnp.swapaxes(qa, 1, 2)
        ka = jnp.swapaxes(ka, 1, 2)
        va = jnp.swapaxes(va, 1, 2)
        logits = jnp.einsum("bhsd,bhtd->bhst", qa, ka, precision=_precision()) * scale
        if is_causal:
            s, t = logits.shape[-2], logits.shape[-1]
            cmask = jnp.tril(jnp.ones((s, t), dtype=bool))
            logits = jnp.where(cmask, logits, jnp.asarray(-1e9, logits.dtype))
        if mask_arr is not None:
            m = mask_arr
            if m.dtype == jnp.bool_:
                logits = jnp.where(m, logits, jnp.asarray(-1e9, logits.dtype))
            else:
                logits = logits + m.astype(logits.dtype)
        probs = jax.nn.softmax(logits, axis=-1)
        if drop_key is not None:
            keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
        out = jnp.einsum("bhst,bhtd->bhsd", probs, va, precision=_precision())
        return jnp.swapaxes(out, 1, 2)

    return run_op(f, [q, k, v], "scaled_dot_product_attention")


def rotary_embedding(x, positions, theta=10000.0, name=None):
    """Rotary position embedding, half-split (the Qwen / GPT-NeoX
    convention): with x = [x1, x2] the two halves of the last axis and
    angle[i] = position * theta^(-2i/d), the result is
    [x1 cos - x2 sin, x2 cos + x1 sin].

    x: [batch, seq, heads, head_dim]; positions: [batch, seq] integers, a
    position per row and token (a cached decode step passes each row's own
    offset). The rotation is computed in float32 and returned in x's dtype.
    """
    x, positions = ensure_tensor(x), ensure_tensor(positions)
    half = x.shape[-1] // 2
    if 2 * half != x.shape[-1]:
        raise ValueError(f"rotary_embedding: head_dim {x.shape[-1]} is odd")

    def f(a, pos):
        inv = jnp.asarray(theta, jnp.float32) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        angle = pos.astype(jnp.float32)[..., None, None] * inv   # [B,T,1,d/2]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a32 = a.astype(jnp.float32)
        x1, x2 = a32[..., :half], a32[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(a.dtype)

    return run_op(f, [x, positions], "rotary_embedding")
