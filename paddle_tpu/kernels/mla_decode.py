"""A decode step of latent attention (MLA) over a latent page, ragged by
slot: of each slot's page a step reads the row blocks that slot's own
length reaches, ONCE.

With the up-projection absorbed into the query
(`F.latent_attention_decode`) every head of a slot scores the SAME rows,
`[c_j; kr_j]`, and sums the same rows again for its context: the page is
key and value at once. The dense form reads the whole pool for the scores
and again for the context, whatever is live. Here:

- grid (slot, page block), the block axis sequential. A block is
  `(1, BLOCK_ROWS, W)` straight off the page (W the row as it is held,
  `[latent; rotary key; zeros]` in whole 128 lanes), and its index map is
  CLAMPED to the slot's last needed block: a grid step past it names the
  block already in VMEM, moves nothing, and its body is skipped
  (`pl.when`). A free slot (position 0) costs one block.
- the absorbed queries `[heads, W]` against the block `[rows, W]` give the
  scores `[heads, rows]`; the probabilities against THE SAME block give
  the context `[heads, W]`, of which the caller keeps the latent lanes.
  The block is in VMEM once for both. At 128 heads the two matmuls of a
  row (2 x 128 x 2 x W FLOP) take about as long as its 2 W bytes: the one
  decode kernel here that sits on the chip's ridge.
- online softmax (m, l, acc in VMEM scratch, float32) across blocks.
  Masking is by `where`, for the scores AND for the block's rows as
  values: rows past a length are whatever an earlier sequence left (or
  what a boundary block pads in), and 0 x NaN is NaN.

On a TPU the operands go to the MXU as bfloat16 with float32 accumulation
(what a default-precision einsum does); interpreted (off a TPU) they stay
in their own dtype.

BLOCK_ROWS is 512, not `decode_attention`'s 128: a grid step costs ~0.35
us whether it is skipped or not (PERF.md, PR 32), a page here is up to
13,312 rows a slot against 1,026 there, and a layer's 16 x 104 steps of
128 rows would cost more than the rows they read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "mla_decode"           # the name a trace reader finds
BLOCK_ROWS = 512                # rows of a page a grid step reads
MASKED = -1e30                  # the dense path's mask value


def engages(page_dtype) -> bool:
    """Whether `F.latent_attention_decode` reads its page through this
    kernel: on a TPU, over a floating-point page, at the default matmul
    precision (the kernel's own)."""
    from ..core.flags import flag
    return (jax.default_backend() == "tpu"
            and jnp.issubdtype(page_dtype, jnp.floating)
            and flag("tpu_matmul_precision") == "default")


def _kernel(len_ref, q_ref, page_ref, o_ref, m_ref, l_ref, acc_ref, *,
            rows, scale, mxu, stats):
    b, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * rows < length)
    def _():
        heads, width = q_ref.shape[1], q_ref.shape[2]
        row = j * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
        block = jnp.where(row < length, page_ref[0], 0).astype(mxu)
        s = jax.lax.dot_general(
            q_ref[0].astype(mxu), block, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        key = j * rows + jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
        s = jnp.where(key < length, s, MASKED).astype(stats)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(mxu), block, preferred_element_type=jnp.float32
        ).astype(stats)
        m_ref[...] = m_new

    @pl.when(j == (length - 1) // rows)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _attend(q, page, lengths, *, scale, block_rows, mxu, interpret,
            stats=jnp.float32):
    """The `pallas_call`. q [B, H, W] (H in whole 8s); page [B, L, W];
    lengths [B] int32 in 1..L. `stats` is the dtype of the running
    maximum, sum and context (float32; a control lowers it)."""
    b, heads, width = q.shape
    rows = min(block_rows, page.shape[1])       # a page shorter than a block

    def page_block(i, j, lens):
        return i, jnp.minimum(j, (lens[i] - 1) // rows), 0

    query = pl.BlockSpec((1, heads, width), lambda i, j, lens: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, scale=scale, mxu=mxu,
                          stats=stats),
        name=KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, pl.cdiv(page.shape[1], rows)),
            in_specs=[query, pl.BlockSpec((1, rows, width), page_block)],
            out_specs=query,
            scratch_shapes=[pltpu.VMEM((heads, 1), stats),
                            pltpu.VMEM((heads, 1), stats),
                            pltpu.VMEM((heads, width), stats)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, page)


def mla_decode(q, page, positions, scale, block_rows=BLOCK_ROWS,
               stats=jnp.float32):
    """q [B, H, W]: a slot's absorbed queries, `[W_uk^T q_nope; q_rope;
    zeros]` a head, in the page's dtype; page [B, L, W] with this step's
    row already written at positions[b]; positions [B] int32. Returns
    [B, H, W] in q's dtype: sum_j softmax_j(scale q . page_j) page_j over
    j <= positions[b] (the caller keeps the latent lanes). Compiled on a
    TPU (bfloat16 to the MXU), interpreted elsewhere (the operands' own
    dtype)."""
    interpret = jax.default_backend() != "tpu"
    heads = q.shape[1]
    pad = -heads % 8                            # whole sublanes
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    lengths = jnp.clip(positions.astype(jnp.int32) + 1, 1, page.shape[1])
    out = _attend(q, page, lengths, scale=float(scale),
                  block_rows=block_rows,
                  mxu=q.dtype if interpret else jnp.bfloat16,
                  interpret=interpret, stats=stats)
    return out[:, :heads] if pad else out
