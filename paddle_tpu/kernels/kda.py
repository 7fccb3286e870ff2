"""Kimi Delta Attention (KDA): the gated delta rule with a decay a channel.

Kimi Linear (arXiv:2510.26692). A head keeps a state `S [dk, dv]` in
float32; with a_t = exp(g_t) in (0, 1)^dk, the log-decay g_t <= 0 a key
channel, and beta_t in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(the caller has normalised q and k and scaled q). With u_t = beta_t (v_t -
(Diag(a_t) S_{t-1})^T k_t), the update is S_t = Diag(a_t) S_{t-1} + k_t
u_t^T: a decay and a rank-1 product.

Two forms:

- `kda_chunked`: a whole prompt from an empty state, in chunks of `chunk`
  positions. With G_t the running sum of g inside a chunk, the u of a
  chunk solve one unit lower-triangular system,
      (I + Diag(beta) A) U = Diag(beta) (V - (K * exp(G)) S_0),
      A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c]),  i < t,
  and o = (Q * exp(G)) S_0 + B U with B the same sum over q_t and i <= t.
  The decays of A and B are formed a sub-chunk of `SUB` rows at a time
  against the running sum at the sub-chunk's MIDDLE: a row's factor
  exp(G_t - ref) and a column's exp(ref - G_i) inside the sub-chunk both
  lie within exp(+-5 SUB / 2) = exp(+-40), a column's before it is <= 1,
  and later columns are never formed. Float32 holds exp(+-80) (that is
  what the configuration's lower bound of -5 on g is for), but a row
  scaled by exp(-80) loses its small entries to the denormals: from the
  middle nothing comes near either end. Never a product of exp(G_t) and
  exp(-G_i) over a whole chunk. The triangular systems of
  all chunks are solved in one batched forward substitution; the state is
  then carried over the chunks by a scan. Positions >= `lengths` are
  folded into nothing (k = 0, g = 0, beta = 0).
- `kda_step`: one token. Every byte of every state is read and rewritten,
  so the step is bound by memory; on a TPU it is one Pallas kernel
  (`kda_step` in a trace) that streams S through VMEM once, in place, in
  float32 on the vector unit (no matmul unit pass rounds the state). The
  `jax.numpy` lowering is the CPU path and the kernel's reference.

The short convolution in front of q, k and v (causal, depthwise, kernel
`w.shape[0]`) keeps the last `kernel - 1` pre-activation rows a sequence:
`short_conv_prompt` and `short_conv_step`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16               # rows whose decays share one reference point
HEAD_BLOCK = 16        # heads of one slot a grid step of the kernel takes
STEP_KERNEL = "kda_step"
_EXACT = jax.lax.Precision.HIGHEST


def _decay_products(q, k, cum):
    """A [.., C, C] (k_t . k_i, decayed, i < t) and B (q_t . k_i, i <= t)
    of every chunk. q, k, cum [.., C, dk]; cum is the inclusive running
    sum of the log-decays inside the chunk."""
    c = k.shape[-2]
    rows_a, rows_b = [], []
    for r0 in range(0, c, SUB):
        r1 = min(r0 + SUB, c)
        ref = cum[..., (r0 + r1) // 2 - 1, :][..., None, :]
        cols = k[..., :r1, :] * jnp.exp(ref - cum[..., :r1, :])
        down = jnp.exp(cum[..., r0:r1, :] - ref)
        pad = ((0, 0),) * (k.ndim - 2) + ((0, 0), (0, c - r1))
        for rows, out in ((k[..., r0:r1, :] * down, rows_a),
                          (q[..., r0:r1, :] * down, rows_b)):
            out.append(jnp.pad(jnp.einsum("...td,...id->...ti", rows, cols,
                                          precision=_EXACT), pad))
    a, b = jnp.concatenate(rows_a, -2), jnp.concatenate(rows_b, -2)
    t, i = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    return jnp.where(i < t, a, 0.0), jnp.where(i <= t, b, 0.0)


def kda_chunked(q, k, v, g, beta, lengths=None, chunk: int = CHUNK):
    """A prompt, from an empty state. q, k [B, T, H, dk]; v [B, T, H, dv];
    g [B, T, H, dk] (<= 0, and >= -80 / SUB); beta [B, T, H]; lengths [B]
    or None (every position real); chunk: positions a chunk, a multiple of
    `SUB` (it need not divide T). Returns (o [B, T, H, dv] float32,
    S [B, H, dk, dv] float32) with the state as it stands after position
    lengths - 1. Rows of o at positions >= lengths are not meaningful."""
    b, t, h, dk = k.shape
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if lengths is not None:
        real = jnp.arange(t)[None, :] < lengths[:, None]
        k = jnp.where(real[..., None, None], k, 0.0)
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    c = min(int(chunk), -(-t // SUB) * SUB)
    if c % SUB:
        raise ValueError(f"kda_chunked: chunk {chunk} is no multiple of {SUB}")
    n = -(-t // c)

    def chunks(a):
        """[B, T, H, d] -> [N, B, H, C, d], the tail padded with nothing."""
        a = jnp.pad(a, ((0, 0), (0, n * c - t), (0, 0), (0, 0)))
        return a.reshape(b, n, c, h, a.shape[-1]).transpose(1, 0, 3, 2, 4)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta[..., None]))
    cum = jnp.cumsum(g, axis=-2)
    a_mat, b_mat = _decay_products(q, k, cum)
    grown = jnp.exp(cum)
    # every chunk's system at once: the substitution is 64 short steps
    # over all of them, not 64 a chunk
    w = jax.scipy.linalg.solve_triangular(
        jnp.eye(c, dtype=f32) + beta * a_mat,
        beta * jnp.concatenate([v, k * grown], -1),
        lower=True, unit_diagonal=True)
    w_v, w_k = w[..., :v.shape[-1]], w[..., v.shape[-1]:]
    last = cum[..., -1:, :]
    k_end = k * jnp.exp(last - cum)

    def carry(s, part):
        w_v, w_k, qg, b_mat, k_end, shrink = part
        u = w_v - jnp.einsum("bhck,bhkv->bhcv", w_k, s, precision=_EXACT)
        o = (jnp.einsum("bhck,bhkv->bhcv", qg, s, precision=_EXACT)
             + jnp.einsum("bhci,bhiv->bhcv", b_mat, u, precision=_EXACT))
        s = shrink[..., None] * s + jnp.einsum(
            "bhck,bhcv->bhkv", k_end, u, precision=_EXACT)
        return s, o

    s, o = jax.lax.scan(
        carry, jnp.zeros((b, h, dk, v.shape[-1]), f32),
        (w_v, w_k, q * grown, b_mat, k_end, jnp.exp(last[..., 0, :])))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * c, h, -1)[:, :t]
    return o, s


def _step_jnp(q, k, v, g, beta, s):
    """The step in `jax.numpy`, sums on the vector unit in float32: the
    CPU path and the kernel's reference."""
    sd = s * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - (k[..., None] * sd).sum(-2))
    new = sd + k[..., None] * u[..., None, :]
    return (q[..., None] * new).sum(-2), new


def _step_kernel(st_ref, cols_ref, bv_ref, out_ref, o_ref, *, hb):
    """`hb` heads of one slot. A head's tile [dk, dv]: decay its rows,
    take the rank-1 product in, store in place, read o off the new tile.
    cols holds, a head, four columns [dk, 1]: exp(g), beta k, k, q."""
    for j in range(hb):
        col = lambda part: cols_ref[0, 0, :, part * hb + j:part * hb + j + 1]
        sd = st_ref[0, j] * col(0)
        u = bv_ref[0, 0, j:j + 1, :] - jnp.sum(col(1) * sd, axis=0,
                                               keepdims=True)
        new = sd + col(2) * u
        out_ref[0, j] = new
        o_ref[0, 0, j:j + 1, :] = jnp.sum(col(3) * new, axis=0,
                                          keepdims=True)


def _step_pallas(q, k, v, g, beta, s, interpret=None):
    """The same as one Pallas kernel (interpreted off a TPU)."""
    b, h, dk, dv = s.shape
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 else h
    # columns lie along the sublanes of a state tile: [B, H/hb, dk, 4 hb],
    # a head a lane, so that no [.., dk, 1] array pads its one lane to 128
    cols = jnp.stack([jnp.exp(g), beta[..., None] * k, k, q], axis=1)
    cols = cols.reshape(b, 4, h // hb, hb, dk).transpose(0, 2, 4, 1, 3)
    cols = cols.reshape(b, h // hb, dk, 4 * hb)
    bv = (beta[..., None] * v).reshape(b, h // hb, hb, dv)
    tile = pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0))
    row = pl.BlockSpec((1, 1, hb, dv), lambda i, j: (i, j, 0, 0))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    new, o = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb), name=STEP_KERNEL,
        grid=(b, h // hb),
        in_specs=[tile,
                  pl.BlockSpec((1, 1, dk, 4 * hb), lambda i, j: (i, j, 0, 0)),
                  row],
        out_specs=(tile, row),
        out_shape=(jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct(bv.shape, jnp.float32)),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(s, cols, bv)
    return o.reshape(b, h, dv), new


def kda_step(q, k, v, g, beta, s):
    """One token. q, k, g [B, H, dk]; v [B, H, dv]; beta [B, H]; S [B, H,
    dk, dv] float32. Returns (o [B, H, dv] float32, S'): the Pallas kernel
    on a TPU, `jax.numpy` elsewhere."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    step = _step_pallas if jax.default_backend() == "tpu" else _step_jnp
    return step(q, k, v, g, beta, s)


def _taps(x, w, width):
    """sum_j w[j] * x[:, j:j + width]: x [B, width + K - 1, C], w [K, C]."""
    w = w.astype(jnp.float32)
    return sum(w[j] * x[:, j:j + width].astype(jnp.float32)
               for j in range(w.shape[0]))


def short_conv_prompt(x, w, lengths=None):
    """The causal depthwise convolution of a prompt from an empty state.
    x [B, T, C] pre-activation rows; w [K, C] (w[K - 1] weighs the current
    row). Returns (y [B, T, C] float32, the K - 1 rows before `lengths`
    [B, K - 1, C] in x's dtype; zeros where the prompt is shorter)."""
    taps = w.shape[0] - 1
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps, 0), (0, 0)))
    if lengths is None:
        rows = padded[:, t:]
    else:
        # padded row lengths + j is x's row lengths - taps + j
        at = lengths.astype(jnp.int32)[:, None] + jnp.arange(taps)[None, :]
        rows = jnp.take_along_axis(padded, at[..., None], axis=1)
    return _taps(padded, w, t), rows


def short_conv_step(x, w, rows):
    """One token: x [B, C], rows [B, K - 1, C] -> (y [B, C] float32, the
    rows shifted by one)."""
    window = jnp.concatenate([rows, x[:, None].astype(rows.dtype)], axis=1)
    return _taps(window, w, 1)[:, 0], window[:, 1:]
