"""Decode attention over K/V pages, ragged by row: a step reads, of each
slot's page, only the row blocks that slot's own fill reaches.

A decode step of `ErnieSelfAttention.forward_cached` scores a block of T
query rows (T = `models.ernie.DECODE_BLOCK`) a slot against that slot's
page `[L, heads * head_dim]`, where key j is visible to query i iff
j <= positions[b] + i. The dense form attends over all L rows under that
mask, so that every fill is one executable; on a TPU it reads (and rounds
to bfloat16) every page of the pool every step, however little of it is
live. The mask already says which rows can matter: rows at or above
`lengths[b] = positions[b] + T`. This kernel takes `lengths` as a
scalar-prefetch argument and reads nothing above them:

- grid (slot, kv block), the kv axis sequential. The K and V blocks are
  `(1, block_k, heads * head_dim)` straight off the page, and their index
  map is CLAMPED to the slot's last needed block, so a grid step past it
  names the block already in VMEM and issues no DMA; its body is skipped
  (`pl.when`). A free slot (position 0) costs one block.
- heads stay folded in the row (the page's layout: one cached position is
  one contiguous row). The T query rows are expanded block-diagonally to
  `[T * heads', heads * head_dim]` (row (i, h) holds head h of query i in
  head h's lanes, zeros elsewhere; heads' = heads rounded up to the 8
  sublanes), so one MXU matmul against the block scores every head, and
  of `probs @ V` each row keeps its own head's lanes at the end. The
  waste (heads x the FLOPs) is free: the step is bound by memory.
- online softmax (m, l, acc in VMEM scratch, float32) across kv blocks.
  Masking is by `where`, for the scores AND for V's rows: a page's rows
  are not a multiple of any block, what a boundary block pads in is
  unspecified, and 0 x NaN is NaN.

Precision is the dense path's: on a TPU the operands go to the MXU as
bfloat16 with float32 accumulation, which is what a default-precision
einsum does to a float32 page; interpreted (off a TPU) they stay in their
own dtype, as the CPU's einsum keeps them. The page itself is untouched.

`engages` is the test `forward_cached` asks: the kernel is the read a
decode block wants, the dense einsums the one a prompt wants.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "decode_attention"     # the name a trace reader finds
MAX_QUERY_ROWS = 8              # a decode block; a prompt is dense
BLOCK_K = 128                   # rows of a page a grid step reads
MASKED = -1e9                   # the dense path's mask value


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def engages(query_rows: int, page_dtype) -> bool:
    """Whether `forward_cached` reads its pages through this kernel: on a
    TPU, for a decode block of rows, over floating-point pages, at the
    default matmul precision (the kernel's own)."""
    from ..core.flags import flag
    return (_on_tpu() and query_rows <= MAX_QUERY_ROWS
            and jnp.issubdtype(page_dtype, jnp.floating)
            and flag("tpu_matmul_precision") == "default")


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, qx_ref, m_ref, l_ref,
            acc_ref, *, t, heads, hp, head_dim, tk, mxu):
    b, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    width = heads * head_dim

    def own():
        """[hp, width]: row h's own lanes, head h's (built where it is
        used: a skipped grid step pays for nothing)."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 0) * head_dim
        return (lane >= head) & (lane < head + head_dim)

    @pl.when(j == 0)
    def _():
        mine = own()
        for i in range(t):
            qx_ref[i * hp:(i + 1) * hp, :] = jnp.where(
                mine, q_ref[0, i:i + 1, :].astype(jnp.float32), 0.0)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * tk < length)
    def _():
        rows = t * hp
        s = jax.lax.dot_general(
            qx_ref[...].astype(mxu), k_ref[0].astype(mxu),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(head_dim))
        key = j * tk + jax.lax.broadcasted_iota(jnp.int32, (rows, tk), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, tk), 0)
        last = length - t                     # positions[b] + query i
        for i in range(1, t):
            last = last + (row >= i * hp).astype(jnp.int32)
        s = jnp.where(key <= last, s, MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        vrow = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, width), 0)
        v = jnp.where(vrow < length, v_ref[0], 0).astype(mxu)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(mxu), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == (length - 1) // tk)
    def _():
        out = acc_ref[...] / l_ref[...]
        mine = own()
        for i in range(t):
            o_ref[0, i:i + 1, :] = jnp.where(
                mine, out[i * hp:(i + 1) * hp], 0.0).sum(
                    axis=0, keepdims=True).astype(o_ref.dtype)


def _attend(q, k_page, v_page, lengths, *, num_heads, block_k, mxu,
            interpret):
    """The `pallas_call`. q [B, T, W]; pages [B, L, W], W = heads *
    head_dim; lengths [B] int32 in T..L."""
    b, t, width = q.shape
    rows_page = k_page.shape[1]
    tk = min(block_k, rows_page)        # a page shorter than one block
    hp = -(-num_heads // 8) * 8

    def page_block(i, j, lens):
        return i, jnp.minimum(j, (lens[i] - 1) // tk), 0

    page = pl.BlockSpec((1, tk, width), page_block)
    query = pl.BlockSpec((1, t, width), lambda i, j, lens: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, t=t, heads=num_heads, hp=hp,
                          head_dim=width // num_heads, tk=tk, mxu=mxu),
        name=KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, pl.cdiv(rows_page, tk)),
            in_specs=[query, page, page], out_specs=query,
            scratch_shapes=[pltpu.VMEM((t * hp, width), jnp.float32),
                            pltpu.VMEM((t * hp, 1), jnp.float32),
                            pltpu.VMEM((t * hp, 1), jnp.float32),
                            pltpu.VMEM((t * hp, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k_page, v_page)


def decode_attention(q, k_page, v_page, positions, num_heads):
    """q [B, T, heads * head_dim], T <= MAX_QUERY_ROWS; k_page, v_page
    [B, L, heads * head_dim] with this block's K/V already written at
    positions[b] .. positions[b] + T - 1; positions [B] int32. Returns
    [B, T, heads * head_dim] in q's dtype: softmax(q k^T / sqrt(head_dim))
    v a head, key j visible to query i iff j <= positions[b] + i. Compiled
    on a TPU (bfloat16 to the MXU), interpreted elsewhere (the operands'
    own dtype)."""
    interpret = jax.default_backend() != "tpu"
    lengths = jnp.minimum(positions.astype(jnp.int32) + q.shape[1],
                          k_page.shape[1])
    return _attend(q, k_page, v_page, lengths, num_heads=num_heads,
                   block_k=BLOCK_K,
                   mxu=q.dtype if interpret else jnp.bfloat16,
                   interpret=interpret)


# Grouped K/V heads: a page row holds `kv_heads * head_dim` lanes and each
# K/V head is read by the `group` query heads that share it (grouped-query
# attention). One decode row a slot; blocks of GQA_BLOCK_K rows, because at
# hundreds of slots a grid step, skipped or not, costs more than the rows
# of a short fill (`kernels/mla_decode.py` says why 512).
GQA_BLOCK_K = 512


def _gqa_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                *, kv_heads, group, head_dim, tk, mxu):
    b, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * tk < length)
    def _():
        key = j * tk + jax.lax.broadcasted_iota(jnp.int32, (group, tk), 1)
        vrow = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, head_dim), 0)
        for g in range(kv_heads):
            heads = slice(g * group, (g + 1) * group)
            lanes = slice(g * head_dim, (g + 1) * head_dim)
            s = jax.lax.dot_general(
                q_ref[0, heads, :].astype(mxu), k_ref[0, :, lanes].astype(mxu),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            s = jnp.where(key < length, s * (1.0 / math.sqrt(head_dim)),
                          MASKED)
            m_prev = m_ref[heads, :]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[heads, :] = alpha * l_ref[heads, :] + p.sum(axis=1,
                                                               keepdims=True)
            v = jnp.where(vrow < length, v_ref[0, :, lanes], 0).astype(mxu)
            acc_ref[heads, :] = alpha * acc_ref[heads, :] + jnp.dot(
                p.astype(mxu), v, preferred_element_type=jnp.float32)
            m_ref[heads, :] = m_new

    @pl.when(j == (length - 1) // tk)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def decode_attention_gqa(q, k_page, v_page, positions):
    """One decode row a slot over pages of grouped K/V heads. q [B, heads,
    head_dim]; k_page, v_page [B, L, kv_heads * head_dim] with this step's
    K/V already written at positions[b]; heads a multiple of kv_heads,
    query head h reading K/V head h // (heads / kv_heads). Returns [B,
    heads, head_dim] in q's dtype: softmax(q k^T / sqrt(head_dim)) v over
    keys 0 .. positions[b]. Each slot's blocks past its fill are skipped
    as `decode_attention`'s are. Compiled on a TPU (bfloat16 to the MXU),
    interpreted elsewhere (the operands' own dtype)."""
    interpret = not _on_tpu()
    b, heads, head_dim = q.shape
    kv_heads = k_page.shape[2] // head_dim
    rows_page = k_page.shape[1]
    tk = min(GQA_BLOCK_K, rows_page)
    lengths = jnp.minimum(positions.astype(jnp.int32) + 1, rows_page)

    def page_block(i, j, lens):
        return i, jnp.minimum(j, (lens[i] - 1) // tk), 0

    page = pl.BlockSpec((1, tk, kv_heads * head_dim), page_block)
    query = pl.BlockSpec((1, heads, head_dim), lambda i, j, lens: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_gqa_kernel, kv_heads=kv_heads,
                          group=heads // kv_heads, head_dim=head_dim, tk=tk,
                          mxu=q.dtype if interpret else jnp.bfloat16),
        name=KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, pl.cdiv(rows_page, tk)),
            in_specs=[query, page, page], out_specs=query,
            scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, head_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k_page, v_page)
