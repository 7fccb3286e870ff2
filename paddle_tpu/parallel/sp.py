"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Reference parity: ABSENT in the reference snapshot (SURVEY.md §5 verified no
ring-attention/Ulysses/context-parallel) — this is the required new
first-class component, designed TPU-first:

  - Ring attention: K/V blocks rotate around the 'sp' mesh axis via
    `lax.ppermute` (ICI-neighbour hops make the ring free-standing), with
    flash-style online-softmax accumulation so memory stays O(block) and
    sequence length scales linearly with the number of chips.
  - Ulysses: `lax.all_to_all` swaps the sharded dimension seq→heads, runs
    dense attention on full sequence with H/sp heads per chip, then swaps
    back. Better for moderate sequence lengths with many heads.

Both run inside `shard_map` over the 'sp' axis; `sequence_parallel_attention`
wraps global arrays for direct use in models/tests.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..ops._dispatch import ensure_tensor, run_op
from .topology import get_mesh


def _block_attn(q, k, v, scale, mask=None):
    """One q-block × kv-block attention piece, returning (o_part, lse parts).

    q: [B,S,H,D]; returns m (running max logits), s (sumexp), o (weighted V).
    """
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
    m = jnp.max(logits, axis=-1, keepdims=True)                  # [B,H,S,1]
    p = jnp.exp(logits - m)
    s = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhst,bthd->bshd", p, v)
    return m, s, o


def ring_attention_local(q, k, v, axis_name="sp", causal=False):
    """Per-shard ring attention (call inside shard_map).

    q/k/v: local shards [B, S_local, H, D]. Rotates K/V n-1 times via
    ppermute, accumulating with the online-softmax (flash) recurrence.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    qpos = my * s_loc + jnp.arange(s_loc)

    m_acc = jnp.full((b, h, s_loc, 1), -jnp.inf, dtype=jnp.float32)
    s_acc = jnp.zeros((b, h, s_loc, 1), dtype=jnp.float32)
    o_acc = jnp.zeros((b, s_loc, h, d), dtype=jnp.float32)

    k_cur, v_cur = k, v
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        kb = (my - step) % n  # which block of K/V we currently hold
        if causal:
            kpos = kb * s_loc + jnp.arange(s_loc)
            mask = kpos[None, :] <= qpos[:, None]          # [S_loc, S_loc]
            mask = mask[None, None]                        # [1,1,S,S] → bhst
        else:
            mask = None
        m_new, s_new, o_new = _block_attn(
            q.astype(jnp.float32), k_cur.astype(jnp.float32),
            v_cur.astype(jnp.float32), scale, mask)
        m_tot = jnp.maximum(m_acc, m_new)
        alpha = jnp.exp(m_acc - m_tot)
        beta = jnp.exp(m_new - m_tot)
        s_acc = s_acc * alpha + s_new * beta
        o_acc = o_acc * jnp.moveaxis(alpha, 1, 2) + o_new * jnp.moveaxis(beta, 1, 2)
        m_acc = m_tot
        if step < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    out = o_acc / jnp.moveaxis(jnp.maximum(s_acc, 1e-20), 1, 2)
    return out.astype(q.dtype)


def ulysses_attention_local(q, k, v, axis_name="sp", causal=False):
    """Per-shard Ulysses attention (call inside shard_map).

    Swaps seq-sharded [B,S/n,H,D] → head-sharded [B,S,H/n,D] with all_to_all,
    runs dense (causal) attention over the FULL sequence, swaps back.
    """
    def seq2head(x):
        # split heads across the axis: [B,S/n,H,D] -> [B,S,H/n,D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    d = qh.shape[-1]
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bshd,bthd->bhst", qh.astype(jnp.float32),
                        kh.astype(jnp.float32)) * scale
    if causal:
        s_full = logits.shape[-2]
        cmask = jnp.tril(jnp.ones((s_full, s_full), dtype=bool))
        logits = jnp.where(cmask, logits, -1e9)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", p, vh.astype(jnp.float32))
    return head2seq(out.astype(q.dtype))


def sequence_parallel_attention(q, k, v, impl="ring", causal=False, mesh=None,
                                axis_name="sp"):
    """Global-array entry point: q/k/v [B, S, H, D] sharded (or shardable) on
    S over the 'sp' mesh axis. Differentiable (recorded as one tape node)."""
    mesh = mesh or get_mesh()
    if mesh is None or axis_name not in mesh.shape or mesh.shape[axis_name] == 1:
        from ..nn.functional.attention import scaled_dot_product_attention
        return scaled_dot_product_attention(q, k, v, is_causal=causal)
    q, k, v = ensure_tensor(q), ensure_tensor(k), ensure_tensor(v)
    if impl == "ring":
        # flash-block ring when the local shard can tile the MXU,
        # dense-block einsum ring otherwise (decided per-geometry inside)
        local = ring_flash_attention_local
    elif impl == "ring_dense":
        local = ring_attention_local
    else:
        local = ulysses_attention_local
    spec = P(None, axis_name, None, None)
    other = tuple(a for a in mesh.axis_names if a != axis_name)

    fn = shard_map(
        functools.partial(local, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    def f(qa, ka, va):
        ns = NamedSharding(mesh, spec)
        qa, ka, va = (lax.with_sharding_constraint(x, ns) if isinstance(x, jax.core.Tracer)
                      else jax.device_put(x, ns) for x in (qa, ka, va))
        return fn(qa, ka, va)

    return run_op(f, [q, k, v], f"{impl}_attention")


class SequenceParallelAttention:
    """Layer-ish wrapper selecting ring vs ulysses by sequence/head geometry."""

    def __init__(self, impl="auto", causal=True, axis_name="sp"):
        self.impl = impl
        self.causal = causal
        self.axis_name = axis_name

    def __call__(self, q, k, v):
        impl = self.impl
        if impl == "auto":
            mesh = get_mesh()
            n = mesh.shape.get(self.axis_name, 1) if mesh else 1
            heads = ensure_tensor(q).shape[2]
            impl = "ulysses" if heads % max(n, 1) == 0 and heads >= n * 2 else "ring"
        return sequence_parallel_attention(q, k, v, impl=impl, causal=self.causal,
                                           axis_name=self.axis_name)


# ---- ring attention with Pallas flash blocks -------------------------------

def _to_bhsd(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _from_bhsd(x, b, h):
    bh, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, causal, block_q, block_k, interpret):
    out, _ = _ring_flash_fwd(q, k, v, axis_name, causal, block_q, block_k,
                             interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, block_q, block_k, interpret):
    """Forward ring: flash kernel per hop, lse-weighted merge across hops.

    The per-hop kernel returns softmax-normalized block outputs plus their
    logsumexp; combining hops i with weights exp(lse_i - lse_total) is
    exactly the flash recurrence lifted to hop granularity, so the merged
    result equals full-sequence attention to numerical precision.
    """
    from ..kernels.flash_attention import ring_block_fwd
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    qf, kf, vf = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    o = jnp.zeros((b * h, s, d), jnp.float32)
    lse = jnp.full((b * h, 1, s), -1e30, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur = kf, vf
    for step in range(n):
        kb = (my - step) % n
        offs = jnp.stack([my * s, kb * s]).astype(jnp.int32)
        o_b, lse_b = ring_block_fwd(qf, k_cur, v_cur, offs, causal=causal,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret)
        lse_new = jnp.logaddexp(lse, lse_b)
        w_old = jnp.exp(lse - lse_new)
        w_new = jnp.exp(lse_b - lse_new)
        o = o * jnp.swapaxes(w_old, 1, 2) \
            + o_b.astype(jnp.float32) * jnp.swapaxes(w_new, 1, 2)
        lse = lse_new
        if step < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    out = _from_bhsd(o, b, h).astype(q.dtype)
    return out, lse


def _ring_flash_fwd_rule(q, k, v, axis_name, causal, block_q, block_k,
                         interpret):
    out, lse = _ring_flash_fwd(q, k, v, axis_name, causal, block_q, block_k,
                               interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd_rule(axis_name, causal, block_q, block_k, interpret, res,
                         g):
    """Backward ring: dq accumulates locally; dk/dv accumulators rotate WITH
    their k/v blocks and arrive home after the full ring (n hops). Uses the
    global lse, so per-hop probabilities are already globally normalized —
    hop contributions just sum (flash backward algebra, block-diagonal in
    hops)."""
    from ..kernels.flash_attention import ring_block_dq, ring_block_dkv
    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    qf, kf, vf = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    of, dof = _to_bhsd(out), _to_bhsd(g)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]
    dq = jnp.zeros((b * h, s, d), jnp.float32)
    dk_cur = jnp.zeros((b * h, s, d), jnp.float32)
    dv_cur = jnp.zeros((b * h, s, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur = kf, vf
    for step in range(n):
        kb = (my - step) % n
        offs = jnp.stack([my * s, kb * s]).astype(jnp.int32)
        dq = dq + ring_block_dq(qf, k_cur, v_cur, dof, lse, delta, offs,
                                causal=causal, block_q=block_q,
                                block_k=block_k, interpret=interpret)
        dk_b, dv_b = ring_block_dkv(qf, k_cur, v_cur, dof, lse, delta, offs,
                                    causal=causal, block_q=block_q,
                                    block_k=block_k, interpret=interpret)
        dk_cur = dk_cur + dk_b
        dv_cur = dv_cur + dv_b
        # rotate grads WITH their k/v block; after n hops the grads are
        # home (k/v need not make the final hop — nothing reads them)
        if step < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
        dk_cur = lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = lax.ppermute(dv_cur, axis_name, perm)
    dq_ = _from_bhsd(dq, b, h).astype(q.dtype)
    dk_ = _from_bhsd(dk_cur, b, h).astype(k.dtype)
    dv_ = _from_bhsd(dv_cur, b, h).astype(v.dtype)
    return dq_, dk_, dv_


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def ring_flash_attention_local(q, k, v, axis_name="sp", causal=False,
                               block_q=None, block_k=None):
    """Per-shard ring attention with Pallas flash block kernels (call inside
    shard_map). Falls back to the dense-block einsum ring when the local
    sequence is too short to tile the MXU."""
    from ..kernels.flash_attention import DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    s_loc = q.shape[1]
    bq = min(block_q or DEFAULT_BLOCK_Q, s_loc)
    bk = min(block_k or DEFAULT_BLOCK_K, s_loc)
    if s_loc < 128 or s_loc % bq or s_loc % bk:
        return ring_attention_local(q, k, v, axis_name=axis_name,
                                    causal=causal)
    interpret = jax.default_backend() != "tpu"
    return _ring_flash(q, k, v, axis_name, causal, bq, bk, interpret)
