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


def yarn_inv_freq(dim, theta, factor, original, beta_fast=32, beta_slow=1):
    """YaRN's blended rotary frequencies (arXiv:2309.00071, as DeepSeek-V3
    applies them), [dim // 2] float32. A frequency that makes more than
    `beta_fast` turns over the `original` positions is kept, one that makes
    fewer than `beta_slow` is divided by `factor`, and between the two
    dimension indices a linear ramp blends them:

        inv_i = theta^(-2i/dim);  d(b) = dim ln(original / (2 pi b)) / (2 ln theta)
        low, high = floor d(beta_fast), ceil d(beta_slow), in [0, dim - 1]
        ramp_i = clip((i - low) / (high - low), 0, 1)
        inv'_i = inv_i / factor * ramp_i + inv_i * (1 - ramp_i)"""
    import numpy as np

    def turns(b):
        return dim * math.log(original / (2 * math.pi * b)) \
            / (2 * math.log(theta))
    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    inv = float(theta) ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (inv / factor * ramp + inv * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor, mscale=1.0):
    """m(s) = 0.1 s ln(factor) + 1 (1 without scaling): YaRN's correction
    of the attention's temperature."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_embedding(x, positions, theta=10000.0, name=None,
                     interleaved=False, inv_freq=None):
    """Rotary position embedding, half-split (the Qwen / GPT-NeoX
    convention): with x = [x1, x2] the two halves of the last axis and
    angle[i] = position * theta^(-2i/d), the result is
    [x1 cos - x2 sin, x2 cos + x1 sin]. `interleaved` pairs the entries
    (2i, 2i + 1) instead (the GPT-J / DeepSeek convention).

    x: [batch, seq, heads, head_dim]; positions: [batch, seq] integers, a
    position per row and token (a cached decode step passes each row's own
    offset). The rotation is computed in float32 and returned in x's dtype.
    `inv_freq` [head_dim // 2] gives the frequencies themselves in place of
    theta's powers (`yarn_inv_freq`).
    """
    x, positions = ensure_tensor(x), ensure_tensor(positions)
    half = x.shape[-1] // 2
    if 2 * half != x.shape[-1]:
        raise ValueError(f"rotary_embedding: head_dim {x.shape[-1]} is odd")

    def f(a, pos):
        inv = jnp.asarray(theta, jnp.float32) ** (
            -jnp.arange(half, dtype=jnp.float32) / half) \
            if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
        angle = pos.astype(jnp.float32)[..., None, None] * inv   # [B,T,1,d/2]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a32 = a.astype(jnp.float32)
        if interleaved:
            x1, x2 = a32[..., 0::2], a32[..., 1::2]
            return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).reshape(a.shape).astype(a.dtype)
        x1, x2 = a32[..., :half], a32[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(a.dtype)

    return run_op(f, [x, positions], "rotary_embedding")


LATENT_ROW_BLOCK = 512     # rows of scores held at once over a prompt


def _split_up(w_ukv, heads, nope, v_dim):
    """[latent, heads * (nope + v)] -> W_uk [latent, heads, nope], W_uv
    [latent, heads, v]."""
    w = w_ukv.reshape(w_ukv.shape[0], heads, nope + v_dim)
    return w[..., :nope], w[..., nope:]


def _latent_kernels(dtype) -> bool:
    """Whether latent attention runs its two Pallas forms (`mla_prefill`,
    `mla_decode`): on a TPU over floating-point rows at the default matmul
    precision; elsewhere the `jax.numpy` forms below, which are also the
    kernels' references."""
    from ...kernels import mla_decode
    return mla_decode.engages(dtype)


def latent_prompt_flash(qn, qr, c, kr, w, scale):
    """The prompt form through `kernels/flash_attention.py` (arrays; shapes
    as `latent_attention_prompt`'s): every head's keys [nope + rope] and
    values [v] are built from the latent rows, then ONE causal flash kernel
    over score width nope + rope and value width v, named `mla_prefill` in
    the program. No [heads, rows, keys] score array exists at any length.
    A padded prompt needs no length mask: a real row never sees a later
    key, and the rows past a length are nobody's."""
    from ...kernels.flash_attention import flash_prompt_bhsd
    b, t, h, nope = qn.shape
    w_uk, w_uv = _split_up(w, h, nope, w.shape[1] // h - nope)
    kn = jnp.einsum("btl,lhd->bhtd", c, w_uk)
    v = jnp.einsum("btl,lhd->bhtd", c, w_uv)
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, None], (b, h, t, kr.shape[-1]))], -1)
    q = jnp.swapaxes(jnp.concatenate([qn, qr], -1), 1, 2)
    fold = lambda a: a.astype(c.dtype).reshape(b * h, t, a.shape[-1])
    y = flash_prompt_bhsd(fold(q), fold(k), fold(v), scale=scale,
                          name="mla_prefill")
    return jnp.swapaxes(y.reshape(b, h, t, -1), 1, 2)


def latent_attention_prompt(q_nope, q_rope, latent, k_rope, w_ukv, lengths=None,
                            scale=None, name=None):
    """Multi-head latent attention (DeepSeek-V2) over a prompt, EXPANDED:
    every head's keys and values are built from the latent rows, then
    causal softmax attention over nope + rope score dimensions.

    q_nope [B, T, H, nope], q_rope [B, T, H, rope] (rotated), latent [B, T,
    L] (normed), k_rope [B, T, rope] (rotated, shared by the heads), w_ukv
    [L, H * (nope + v)], lengths [B] or None (keys >= lengths masked).
    Returns [B, T, H, v] in latent's dtype. `scale` multiplies the scores
    ((nope + rope)^-0.5 where None). On a TPU the flash kernel
    (`latent_prompt_flash`); elsewhere scores in blocks of rows, float32
    statistics: the kernel's reference."""
    tensors = [ensure_tensor(a) for a in (q_nope, q_rope, latent, k_rope,
                                          w_ukv)]
    if lengths is not None:
        tensors.append(ensure_tensor(lengths))

    if scale is None:
        scale = 1.0 / math.sqrt(tensors[0].shape[-1] + tensors[1].shape[-1])
    # decided out here: what `f` closes over keys the eager dispatch cache
    flash = _latent_kernels(tensors[2]._value.dtype)

    def f(qn, qr, c, kr, w, *rest):
        if flash:
            return latent_prompt_flash(qn, qr, c, kr, w, scale)
        b, t, h, nope = qn.shape
        v_dim = w.shape[1] // h - nope
        w_uk, w_uv = _split_up(w, h, nope, v_dim)
        kn = jnp.einsum("btl,lhd->bthd", c, w_uk)
        v = jnp.einsum("btl,lhd->bthd", c, w_uv)
        cols = jnp.arange(t)
        real = cols[None, :] < rest[0][:, None] if rest else None
        out = []
        for r0 in range(0, t, LATENT_ROW_BLOCK):
            r1 = min(r0 + LATENT_ROW_BLOCK, t)
            # a later key is never read: the slice is causal by construction
            s = (jnp.einsum("bthd,bjhd->bhtj", qn[:, r0:r1], kn[:, :r1],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bthd,bjd->bhtj", qr[:, r0:r1], kr[:, :r1],
                              preferred_element_type=jnp.float32)) * scale
            keep = jnp.arange(r0, r1)[:, None] >= cols[None, :r1]
            if real is not None:
                keep = keep[None] & real[:, None, :r1]
                keep = keep[:, None]
            p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
            out.append(jnp.einsum("bhtj,bjhd->bthd", p.astype(v.dtype),
                                  v[:, :r1],
                                  preferred_element_type=jnp.float32))
        y = jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]
        return y.astype(c.dtype)

    return run_op(f, tensors, "latent_attention_prompt")


def latent_attention_decode(q_nope, q_rope, page, positions, w_ukv, scale=None,
                            name=None):
    """One decode step of latent attention with the up-projection ABSORBED
    into the query: a step reads the page's latent rows and never builds a
    head's keys or values.

        qc^h = W_uk^h^T q_nope^h;  score_j = qc^h . c_j + q_rope^h . kr_j
        y^h  = W_uv^h (sum_j a_j c_j)

    q_nope [B, H, nope], q_rope [B, H, rope] (rotated), page [B, max_len,
    W], W >= L + rope (a row is [latent; rotary key; zeros]), with this
    step's row ALREADY written at `positions` [B]; rows beyond `positions`
    are masked. w_ukv [L, H * (nope + v)]. Returns [B, H, v] in the page's
    dtype. `scale` multiplies the scores ((nope + rope)^-0.5 where None).
    On a TPU the page is read by `kernels/mla_decode.py`, each slot's live
    rows once; elsewhere (and as that kernel's reference) it is read whole,
    twice, by dense einsums, and never sliced: a slice of its minor axis is
    a copy of it."""
    tensors = [ensure_tensor(a) for a in (q_nope, q_rope, page, positions,
                                          w_ukv)]
    if scale is None:
        scale = 1.0 / math.sqrt(tensors[0].shape[-1] + tensors[1].shape[-1])
    # decided out here: what `f` closes over keys the eager dispatch cache
    if _latent_kernels(tensors[2]._value.dtype):
        from ...kernels.mla_decode import mla_decode as read
    else:
        read = _latent_read_dense

    def f(qn, qr, page, pos, w):
        b, h, nope = qn.shape
        rope, lat = qr.shape[-1], w.shape[0]
        w_uk, w_uv = _split_up(w, h, nope, w.shape[1] // h - nope)
        qc = jnp.einsum("bhd,lhd->bhl", qn, w_uk,
                        preferred_element_type=jnp.float32)
        q = jnp.concatenate(
            [qc.astype(page.dtype), qr.astype(page.dtype),
             jnp.zeros((b, h, page.shape[-1] - lat - rope), page.dtype)],
            axis=-1)
        ctx = read(q, page, pos, scale)[..., :lat]
        y = jnp.einsum("bhl,lhd->bhd", ctx.astype(w.dtype), w_uv,
                       preferred_element_type=jnp.float32)
        return y.astype(page.dtype)

    return run_op(f, tensors, "latent_attention_decode")


def _latent_read_dense(q, page, pos, scale):
    """q [B, H, W], page [B, L, W], pos [B] -> sum_j softmax_j(scale q .
    page_j) page_j over j <= pos[b], [B, H, W] float32: the whole page
    read twice."""
    s = jnp.einsum("bhl,bjl->bhj", q, page,
                   preferred_element_type=jnp.float32) * scale
    keep = jnp.arange(page.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhj,bjl->bhl", p.astype(page.dtype), page,
                      preferred_element_type=jnp.float32)


def latent_page_write(page, rows, positions, name=None):
    """page [B, max_len, W] with rows [B, <= W] (zeros after them) written
    at positions [B]."""
    def f(page, rows, pos):
        rows = jnp.pad(rows.astype(page.dtype),
                       ((0, 0), (0, page.shape[-1] - rows.shape[-1])))
        return page.at[jnp.arange(page.shape[0]), pos.astype(jnp.int32)].set(
            rows, unique_indices=True)
    return run_op(f, [ensure_tensor(page), ensure_tensor(rows),
                      ensure_tensor(positions)], "latent_page_write")
