"""MoE: top-k gating, capacity buckets, expert-parallel dispatch/combine.

Reference parity: `operators/collective/global_scatter_op.cc` /
`global_gather_op.cc` (count-driven token exchange), python wrappers
`distributed/utils.py:52-129`, and the incubate MoELayer gate semantics.

TPU-native redesign (GShard formulation): variable-count LoD exchange
becomes STATIC-shape capacity buckets — gating produces a dispatch mask
[T, E, C] and combine weights [T, E, C]; dispatch/combine are einsums (MXU
work, not gather loops); the cross-device hop is one `lax.all_to_all` over
the 'ep' mesh axis inside shard_map. Experts are evaluated as ONE batched
einsum over stacked weights [E_local, d, h] instead of a per-expert loop.
`local_count`/`global_count` survive as optional per-bucket validity counts
(rows beyond the count are masked), honoring the reference op contract
under static shapes.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.tensor import Tensor
from ..ops._dispatch import ensure_tensor, run_op
from .collective import _in_spmd


# ---------------- gating ----------------
def top_k_gating(logits, k=2, capacity=None, capacity_factor=1.25,
                 normalize=True):
    """GShard-style top-k gate.

    logits: [T, E]. Returns (dispatch [T,E,C] bool-as-float, combine
    [T,E,C] float, aux_loss scalar). Capacity defaults to
    ceil(capacity_factor * k * T / E). Tokens overflowing an expert's
    capacity are dropped (zero combine weight) — reference drop policy.
    """
    T, E = logits.shape
    if capacity is None:
        capacity = int(math.ceil(capacity_factor * k * T / E))
    C = int(capacity)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    remaining = probs
    offset = jnp.zeros((E,), jnp.int32)
    combine = jnp.zeros((T, E, C), jnp.float32)
    dispatch = jnp.zeros((T, E, C), jnp.float32)
    gates_sum = jnp.zeros((T,), jnp.float32)
    top1_mask = None
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                     # [T]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)         # [T, E]
        if top1_mask is None:
            top1_mask = mask
        pos = (jnp.cumsum(mask, axis=0) - 1) * mask + offset[None, :] * mask
        pos_t = jnp.sum(pos, axis=-1).astype(jnp.int32)          # [T]
        keep = (jnp.sum(mask * (pos + 1), axis=-1) > 0) & (pos_t < C)
        gate = jnp.sum(probs * mask, axis=-1)                    # [T]
        sel = mask * keep[:, None]                               # [T, E]
        slot = jax.nn.one_hot(jnp.clip(pos_t, 0, C - 1), C,
                              dtype=jnp.float32)                 # [T, C]
        dispatch = dispatch + sel[:, :, None] * slot[:, None, :]
        combine = combine + (gate[:, None, None] * sel[:, :, None]
                             * slot[:, None, :])
        gates_sum = gates_sum + gate * keep
        offset = offset + jnp.sum(sel, axis=0).astype(jnp.int32)
        remaining = remaining * (1.0 - mask)

    if normalize and k > 1:
        combine = combine / jnp.maximum(gates_sum, 1e-9)[:, None, None]

    # load-balancing aux loss (Switch/GShard): E * sum_e mean_probs_e *
    # fraction_of_tokens_routed_e (top-1 routing fractions)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(top1_mask, axis=0)
    aux_loss = E * jnp.sum(me * ce)
    return dispatch, combine, aux_loss


def moe_dispatch(x, dispatch):
    """x: [T, d], dispatch: [T, E, C] -> expert inputs [E, C, d] (einsum)."""
    return jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32)
                      ).astype(x.dtype)


def moe_combine(expert_out, combine):
    """expert_out: [E, C, d], combine: [T, E, C] -> [T, d]."""
    return jnp.einsum("tec,ecd->td", combine,
                      expert_out.astype(jnp.float32)).astype(expert_out.dtype)


# ---------------- count-masked a2a (global_scatter/gather op contract) ----
def _mask_counts(a, count):
    """Zero bucket rows at index >= count. a: [E, C, d], count: [E]."""
    C = a.shape[1]
    valid = lax.broadcasted_iota(jnp.int32, (a.shape[0], C), 1) < count[:, None]
    return jnp.where(valid[:, :, None], a, jnp.zeros((), a.dtype))


def global_scatter(x, local_count=None, global_count=None, group=None):
    """Send bucketed expert inputs to their owning ranks.

    x: [E, C, d] grouped by destination expert (E = ep * E_local). Returns
    [E_local, ep*C, d] on each rank: this rank's experts' buckets from every
    source. `local_count[e]` (optional) marks how many rows of bucket e are
    valid; the rest are zero-masked (the reference's count semantics under
    static shapes).
    """
    t = ensure_tensor(x)
    ax = group if isinstance(group, str) else "ep"
    lc = ensure_tensor(local_count)._value if local_count is not None else None

    def f(a):
        if lc is not None:
            a = _mask_counts(a, lc)
        if not _in_spmd(ax):
            return a
        ep = lax.axis_size(ax)
        e_local = a.shape[0] // ep
        out = lax.all_to_all(a, ax, 0, 0, tiled=True)  # [ep*E_local, C, d]
        out = out.reshape(ep, e_local, a.shape[1], a.shape[2])
        return jnp.swapaxes(out, 0, 1).reshape(e_local, ep * a.shape[1],
                                               a.shape[2])

    return run_op(f, [t], "global_scatter")


def global_gather(x, local_count=None, global_count=None, group=None):
    """Inverse of global_scatter: [E_local, ep*C, d] -> [E, C, d]."""
    t = ensure_tensor(x)
    ax = group if isinstance(group, str) else "ep"
    gc = ensure_tensor(global_count)._value if global_count is not None else None

    def f(a):
        if not _in_spmd(ax):
            return a if gc is None else _mask_counts(a, gc)
        ep = lax.axis_size(ax)
        e_local, epc, d = a.shape
        c = epc // ep
        b = a.reshape(e_local, ep, c, d)
        b = jnp.swapaxes(b, 0, 1).reshape(ep * e_local, c, d)
        out = lax.all_to_all(b, ax, 0, 0, tiled=True)  # back to [E, C, d]
        if gc is not None:
            out = _mask_counts(out, gc)
        return out

    return run_op(f, [t], "global_gather")


# ---------------- the layer ----------------
class MoELayer:
    """Mixture-of-experts FFN block (incubate MoELayer role).

    Experts are stacked weights — the expert pass is one batched einsum.
    Call inside shard_map/SPMD with `ep_axis` set for expert parallelism;
    without a mesh it runs all experts locally (dense fallback used by the
    equivalence tests).
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, ep_axis: Optional[str] = None,
                 seed=0, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        s1 = 1.0 / math.sqrt(d_model)
        s2 = 1.0 / math.sqrt(d_hidden)
        self.wg = jnp.asarray(rng.uniform(-s1, s1, (d_model, num_experts)),
                              dtype)
        self.w1 = jnp.asarray(rng.uniform(-s1, s1,
                                          (num_experts, d_model, d_hidden)), dtype)
        self.b1 = jnp.zeros((num_experts, d_hidden), dtype)
        self.w2 = jnp.asarray(rng.uniform(-s2, s2,
                                          (num_experts, d_hidden, d_model)), dtype)
        self.b2 = jnp.zeros((num_experts, d_model), dtype)
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.ep_axis = ep_axis
        self.aux_loss = 0.0

    @staticmethod
    def _ffn(inp, w1, b1, w2, b2):
        """[E', C', d] through stacked expert FFNs — one batched einsum."""
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", inp, w1) + b1[:, None, :])
        return jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]

    def _experts(self, inp):
        return self._ffn(inp, self.w1, self.b1, self.w2, self.b2)

    def __call__(self, x, capacity=None, return_aux=False):
        """x: [T, d] (flatten batch*seq first). Returns [T, d], or
        (out, aux_loss) with `return_aux=True`.

        Under jit/shard_map tracing, use `return_aux=True` — `self.aux_loss`
        is a trace-time side effect (stale on cached executions) kept only
        for eager convenience."""
        arr = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        logits = arr @ self.wg
        dispatch, combine, aux = top_k_gating(
            logits, self.top_k, capacity=capacity,
            capacity_factor=self.capacity_factor)
        self.aux_loss = aux
        buckets = moe_dispatch(arr, dispatch)                # [E, C, d]
        ax = self.ep_axis
        if ax is not None and _in_spmd(ax):
            ep = lax.axis_size(ax)
            e_local = self.num_experts // ep
            rank = lax.axis_index(ax)
            # tokens' buckets -> owning ranks; each rank runs ITS experts
            inp = global_scatter(Tensor(buckets), group=ax)._value
            out = self._local_expert_slice(inp, rank, e_local)
            out = global_gather(Tensor(out), group=ax)._value
        else:
            out = self._experts(buckets)
        y = moe_combine(out, combine)
        wrap = isinstance(x, Tensor)
        y = Tensor(y) if wrap else y
        if return_aux:
            return y, (Tensor(aux) if wrap else aux)
        return y

    def _local_expert_slice(self, inp, rank, e_local):
        # dynamic slice of stacked weights by mesh rank (traced index)
        sl = lambda w: lax.dynamic_slice_in_dim(w, rank * e_local, e_local, 0)  # noqa: E731
        return self._ffn(inp, sl(self.w1), sl(self.b1), sl(self.w2),
                         sl(self.b2))
