"""Mamba-2's selective state-space scan (SSD, arXiv:2405.21060).

A head h of P channels keeps a state `S [P, N]` in float32 and reads the
B and C rows of its group g (heads of one group share them). With the
step size Delta_t > 0 (after softplus) and A < 0 a head:

    S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T       (x_t [P], B_t [N])
    y_t = S_t C_t                                          (the D x_t term is
                                                            the caller's)

Two forms:

- `ssd_chunked`: a whole prompt from an empty state, in chunks of `chunk`
  positions. With a_t = Delta_t A and G_t the running sum of a inside a
  chunk (every difference G_t - G_s with s <= t is <= 0, so no factor
  overflows), a chunk's output is its own part plus what the state
  brought in,

      y_t = sum_{s <= t} (C_t . B_s) exp(G_t - G_s) Delta_s x_s
            + exp(G_t) S_0 C_t,
      S_end = exp(G_last) S_0 + sum_s exp(G_last - G_s) Delta_s x_s B_s^T,

  and the state is carried from chunk to chunk. Positions >= `lengths`
  are folded into nothing (Delta = 0: no decay, no input), so the state
  handed back is the one at each row's length. On a TPU one Pallas kernel
  (`ssd_chunked` in a trace): grid (row, group, chunk), the chunks in
  order with the group's states in VMEM, positions on the lanes, float32
  throughout; the `jax.numpy` form is the CPU path and its reference.
- `ssd_step`: one token, bound by memory: on a TPU one Pallas kernel
  (`ssd_step`) streams every state through VMEM once, in place, float32 on
  the vector unit; exp(Delta A) is one scalar a head, from SMEM, and y is
  read off the new tile transposed, a sum along its sublanes. The
  `jax.numpy` form is the CPU path and the kernel's reference.

The causal depthwise convolution in front (kernel 4, with a bias) is
`kernels/kda.py`'s `short_conv_prompt` / `short_conv_step` plus the bias:
`conv_prompt` / `conv_step` here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kda as _kda

CHUNK = 128
HEAD_BLOCK = 64        # heads of one slot a grid step of the step kernel takes
STEP_KERNEL = "ssd_step"
CHUNK_KERNEL = "ssd_chunked"
_EXACT = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def conv_prompt(x, w, bias, lengths=None):
    """`kda.short_conv_prompt` plus the bias: (y [B, T, C] float32, the
    rows the sequence keeps [B, K - 1, C] in x's dtype)."""
    y, rows = _kda.short_conv_prompt(x, w, lengths)
    return y + bias.astype(jnp.float32), rows


def conv_step(x, w, bias, rows):
    """`kda.short_conv_step` plus the bias: x [B, C], rows [B, K - 1, C]."""
    y, rows = _kda.short_conv_step(x, w, rows)
    return y + bias.astype(jnp.float32), rows


def _chunked_jnp(x, dt, a, b, c, chunk):
    """The chunked form in `jax.numpy`; shapes as `ssd_chunked`'s, dt
    already zero past the lengths and T a multiple of `chunk`."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r, nc = h // g, t // chunk
    xr = x.reshape(bsz, nc, chunk, g, r, p)
    dtr = dt.reshape(bsz, nc, chunk, g, r)
    br = b.reshape(bsz, nc, chunk, g, n)
    cr = c.reshape(bsz, nc, chunk, g, n)
    cum = jnp.cumsum(dtr * a.reshape(g, r), axis=2)          # [B, C, l, G, R]
    cum_t = jnp.moveaxis(cum, 2, -1)                         # [B, C, G, R, l]
    causal = jnp.arange(chunk)[None, :] <= jnp.arange(chunk)[:, None]
    decay = jnp.exp(jnp.where(causal, cum_t[..., :, None]
                              - cum_t[..., None, :], -jnp.inf))  # [.., t, s]
    cb = jnp.einsum("bctgn,bcsgn->bcgts", cr, br, precision=_EXACT)
    m = cb[:, :, :, None] * decay * jnp.moveaxis(dtr, 2, -1)[..., None, :]
    y = jnp.einsum("bcgrts,bcsgrp->bctgrp", m, xr, precision=_EXACT)
    last = cum[:, :, -1:]                                     # [B, C, 1, G, R]
    w_in = jnp.exp(last - cum) * dtr
    chunk_states = jnp.einsum("bcsgr,bcsgrp,bcsgn->bcgrpn", w_in, xr, br,
                              precision=_EXACT)

    def carry(s, part):
        state, shrink = part
        return shrink[..., None, None] * s + state, s

    final, before = jax.lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), jnp.float32),
        (jnp.moveaxis(chunk_states, 1, 0),
         jnp.moveaxis(jnp.exp(last[:, :, 0]), 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                    # [B, C, G, R, P, N]
    y = y + jnp.einsum("bctgn,bcgrpn->bctgrp", cr, before,
                       precision=_EXACT) * jnp.exp(cum)[..., None]
    return y.reshape(bsz, t, h, p), final.reshape(bsz, h, p, n)


def _chunk_kernel(x_ref, rows_ref, cols_ref, b_ref, c_ref, y_ref, out_ref,
                  s_ref, *, heads):
    """One group of one row, one chunk. x and y are [heads, P, l]
    (positions on the lanes); rows [heads, 2, l]: G and Delta a head as
    rows; cols [l, 2 heads]: the same as columns; b, c [l, N]; the group's
    states [heads, P, N] live in `s_ref` from chunk to chunk."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    bm, cm = b_ref[0, 0], c_ref[0, 0]
    length = bm.shape[0]
    nt = (((1,), (1,)), ((), ()))
    # (B_s . C_t) with the source s on the sublanes, the target t on lanes
    cbt = jax.lax.dot_general(bm, cm, nt, precision=_EXACT,
                              preferred_element_type=jnp.float32)
    src = jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
    for j in range(heads):
        cum_row, dt_row = rows_ref[0, j, 0:1, :], rows_ref[0, j, 1:2, :]
        cum_col = cols_ref[0, 0, :, j:j + 1]
        dt_col = cols_ref[0, 0, :, heads + j:heads + j + 1]
        m = cbt * jnp.exp(jnp.where(src <= dst, cum_row - cum_col,
                                    -jnp.inf)) * dt_col
        x, s = x_ref[0, j], s_ref[j]
        y = jnp.dot(x, m, precision=_EXACT,
                    preferred_element_type=jnp.float32)
        y_ref[0, j] = y + jax.lax.dot_general(
            s, cm, nt, precision=_EXACT,
            preferred_element_type=jnp.float32) * jnp.exp(cum_row)
        # G falls along the chunk, so its last entry is its least (a lane
        # reduction: Mosaic broadcasts no single lane to a whole tile)
        last = jnp.min(cum_row, axis=1, keepdims=True)
        s_ref[j] = jnp.exp(last) * s + jnp.dot(
            x * (jnp.exp(last - cum_row) * dt_row), bm, precision=_EXACT,
            preferred_element_type=jnp.float32)
    out_ref[0] = s_ref[...]


def _chunked_pallas(x, dt, a, b, c, chunk, interpret=None):
    """The same as one Pallas kernel (interpreted off a TPU)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r, nc = h // g, t // chunk
    cum = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, h), axis=2
                     ).reshape(bsz, t, h)
    xt = jnp.transpose(x, (0, 2, 3, 1))                       # [B, H, P, T]
    rows = jnp.stack([cum, dt], axis=2).transpose(0, 3, 2, 1)  # [B, H, 2, T]
    cols = jnp.concatenate([cum.reshape(bsz, t, g, r),
                            dt.reshape(bsz, t, g, r)], axis=-1
                           ).transpose(0, 2, 1, 3)            # [B, G, T, 2R]
    bg, cg = (jnp.transpose(v, (0, 2, 1, 3)) for v in (b, c))  # [B, G, T, N]
    lanes = pl.BlockSpec((1, r, p, chunk), lambda i, j, k: (i, j, 0, k))
    state = pl.BlockSpec((1, r, p, n), lambda i, j, k: (i, j, 0, 0))
    group = pl.BlockSpec((1, 1, chunk, n), lambda i, j, k: (i, j, k, 0))
    if interpret is None:
        interpret = not _on_tpu()
    yt, final = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=r), name=CHUNK_KERNEL,
        grid=(bsz, g, nc),
        in_specs=[lanes,
                  pl.BlockSpec((1, r, 2, chunk), lambda i, j, k: (i, j, 0, k)),
                  pl.BlockSpec((1, 1, chunk, 2 * r),
                               lambda i, j, k: (i, j, k, 0)),
                  group, group],
        out_specs=(lanes, state),
        out_shape=(jax.ShapeDtypeStruct((bsz, h, p, t), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((r, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, rows, cols, bg, cg)
    return jnp.transpose(yt, (0, 3, 1, 2)), final


def ssd_chunked(x, dt, a, b, c, lengths=None, chunk: int = CHUNK,
                form=None):
    """A prompt, from an empty state. x [B, T, H, P]; dt [B, T, H] (Delta,
    after softplus); a [H] (A, < 0); b, c [B, T, G, N] with G dividing H
    (head h reads group h // (H / G)); lengths [B] or None (every position
    real). `form` ("pallas" or "jnp") overrides the choice by backend.
    Returns (y [B, T, H, P] float32 without the D x term, S [B, H, P, N]
    float32 as it stands after position lengths - 1). Rows of y at
    positions >= lengths are not meaningful."""
    f32 = jnp.float32
    x, dt, a, b, c = (v.astype(f32) for v in (x, dt, a, b, c))
    t = x.shape[1]
    if lengths is not None:
        dt = jnp.where(jnp.arange(t)[None, :, None]
                       < lengths[:, None, None], dt, 0.0)
    pad = -t % chunk
    if pad:
        tail = lambda v: ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)
        x, dt, b, c = (jnp.pad(v, tail(v)) for v in (x, dt, b, c))
    if form is None:
        form = "pallas" if _on_tpu() else "jnp"
    run = _chunked_pallas if form == "pallas" else _chunked_jnp
    y, s = run(x, dt, a, b, c, chunk)
    return y[:, :t], s


def _step_jnp(x, dt, a, b, c, s):
    """The step in `jax.numpy`, on the vector unit in float32: the CPU path
    and the kernel's reference. x [B, H, P], dt [B, H], b, c [B, G, N],
    s [B, H, P, N]."""
    r = x.shape[1] // b.shape[1]
    bh, ch = (jnp.repeat(v, r, axis=1) for v in (b, c))
    new = (s * jnp.exp(dt * a)[..., None, None]
           + (dt[..., None] * x)[..., None] * bh[:, :, None, :])
    return (new * ch[:, :, None, :]).sum(-1), new


def _step_kernel(ea_ref, st_ref, dx_ref, b_ref, ct_ref, out_ref, y_ref, *,
                 hb, per_group):
    """`hb` heads of one slot. A head's tile [P, N]: decay it by its
    exp(Delta A), one scalar from SMEM; take the rank-1 product in (Delta x,
    a column of `dx_ref` [P, hb], against the group's B row); store in
    place; read y off the new tile transposed, a sum along the sublanes
    against the group's C column (`ct_ref` [N, groups]), and write it as
    the head's row of `y_ref` [hb, P]. B and C are read once a group."""
    p, n = st_ref.shape[2], st_ref.shape[3]
    for g in range(hb // per_group):
        b_row = b_ref[0, 0, g:g + 1, :]
        c_cols = jnp.broadcast_to(ct_ref[0, 0, :, g:g + 1], (n, p))
        for j in range(g * per_group, (g + 1) * per_group):
            new = (st_ref[0, j] * ea_ref[0, 0, 0, j]
                   + dx_ref[0, 0, :, j:j + 1] * b_row)
            out_ref[0, j] = new.astype(out_ref.dtype)
            y_ref[0, 0, j:j + 1, :] = jnp.sum(new.T * c_cols, axis=0,
                                              keepdims=True)


def _step_pallas(x, dt, a, b, c, s, interpret=None):
    """The same as one Pallas kernel (interpreted off a TPU)."""
    bsz, h, p, n = s.shape
    per_group = h // b.shape[1]
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 and HEAD_BLOCK % per_group == 0 \
        else h
    nb, groups = h // hb, hb // per_group
    ea = jnp.exp(dt * a).reshape(bsz, nb, 1, hb)
    # Delta x as columns along the sublanes of a state tile, a head a lane
    dx = (dt[..., None] * x).reshape(bsz, nb, hb, p).transpose(0, 1, 3, 2)
    b_rows = b.reshape(bsz, nb, groups, n)
    c_cols = c.reshape(bsz, nb, groups, n).transpose(0, 1, 3, 2)
    tile = pl.BlockSpec((1, hb, p, n), lambda i, j: (i, j, 0, 0))
    if interpret is None:
        interpret = not _on_tpu()
    new, y = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, per_group=per_group),
        name=STEP_KERNEL, grid=(bsz, nb),
        in_specs=[pl.BlockSpec((1, 1, 1, hb), lambda i, j: (i, j, 0, 0),
                               memory_space=pltpu.SMEM),
                  tile,
                  pl.BlockSpec((1, 1, p, hb), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, 1, groups, n), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, 1, n, groups), lambda i, j: (i, j, 0, 0))],
        out_specs=(tile, pl.BlockSpec((1, 1, hb, p),
                                      lambda i, j: (i, j, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct((bsz, nb, hb, p), jnp.float32)),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(ea, s, dx, b_rows, c_cols)
    return y.reshape(bsz, h, p), new


def ssd_step(x, dt, a, b, c, s):
    """One token. x [B, H, P]; dt [B, H] (Delta); a [H]; b, c [B, G, N];
    s [B, H, P, N] float32. Returns (y [B, H, P] float32 without the D x
    term, S'): the Pallas kernel on a TPU, `jax.numpy` elsewhere."""
    f32 = jnp.float32
    x, dt, a, b, c = (v.astype(f32) for v in (x, dt, a, b, c))
    step = _step_pallas if _on_tpu() else _step_jnp
    return step(x, dt, a, b, c, s)
