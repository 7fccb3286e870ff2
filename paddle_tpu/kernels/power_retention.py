"""Power retention (degree 2): linear attention with a fixed-size state.

Manifest AI, "Scaling Context Requires Rethinking Attention"
(arXiv:2507.04239). For a query head h over key/value head g = h // (H/G),
with log-gates l_t <= 0:

    a[t, j] = ((q_t . k_j) / sqrt(d))^2 * exp(sum_{i=j+1..t} l_i),  j <= t
    y_t     = sum_j a[t, j] v_j / (sum_j a[t, j] + eps)

The square factors through phi: R^d -> R^D, D = d(d+1)/2, with
phi(a) . phi(b) = (a . b)^2 (entries a_i a_j for i <= j, times sqrt(2) for
i < j), so the sums over j are a recurrent state a key/value head keeps:

    S_t = exp(l_t) S_{t-1} + [v_t] phi(k_t)^T        z_t = exp(l_t) z_{t-1} + phi(k_t)
    y_t = S_t phi(q_t / sqrt(d)) / (z_t . phi(q_t / sqrt(d)) + eps)

**Layout.** A state is held transposed, `S [B, G, d, R]` and `z [B, G, R]`
in float32, with R = D rounded up to a multiple of 128 (d = 128: D = 8256,
R = 8320; the 64 extra rows are zero). The long axis lies on the TPU's
lanes and d on the sublanes, so neither is padded, a tile of S is updated
by a rank-1 product of a column (v) and a row (phi(k)), and the read-out is
a matmul that contracts the lane axis of both operands (the q @ k^T form).
The normaliser is an array of its own: as a 129th column of S it would pad
the 129 to 256 lanes and double the state.

Two forms:

- `power_retention_chunked`: a whole prompt. Inside a chunk it is the
  attention form in blocks of rows (cost grows with the chunk, needs no
  phi of a query); across chunks the state carries. At d = 128 the
  attention form is the cheaper one up to ~8k keys (a query's read of the
  state costs 2 D (d + 1) = 2.1 MFLOP, a key 4 d), so the caller gives one
  chunk for a prompt that fits one. Positions >= `lengths` contribute
  nothing to the state (k = v = 0, log-gate 0).
- `power_retention_step`: one token. Every byte of the state is read and
  rewritten, so the step is bound by memory; on a TPU it is one Pallas
  kernel (`power_retention_step` in a trace) that streams S through VMEM
  once, in place, and reads y off the updated tile while it is there. The
  `jax.numpy` lowering is the CPU path and the kernel's reference.

phi is computed with two one-hot matmuls (x @ A) * (x @ B) * c: a gather
along the lane axis is slow on a TPU and a selection by the MXU is exact.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-6
ROW_BLOCK = 512        # rows of scores held at once inside a chunk
STATE_BLOCK = 512      # tokens whose phi(k) is held at once
STEP_KERNEL = "power_retention_step"


def state_rows(d: int) -> int:
    """Rows of a state as held: d(d+1)/2 rounded up to the 128 lanes."""
    return -(-(d * (d + 1) // 2) // 128) * 128


@functools.lru_cache(maxsize=None)
def _phi_tables(d: int):
    """(A, B) one-hot [d, R] and c [R]: phi(x)[r] = c[r] x[i_r] x[j_r] over
    the upper triangle in row-major order; padding rows select nothing."""
    i, j = np.triu_indices(d)
    rows = state_rows(d)
    a = np.zeros((d, rows), np.float32)
    b = np.zeros((d, rows), np.float32)
    c = np.zeros((rows,), np.float32)
    r = np.arange(i.size)
    a[i, r] = 1.0
    b[j, r] = 1.0
    c[r] = np.where(i == j, 1.0, math.sqrt(2.0))
    return a, b, c


def phi(x):
    """[..., d] -> [..., R] float32 with phi(a) . phi(b) = (a . b)^2."""
    a, b, c = _phi_tables(x.shape[-1])
    # a selection: exact in the input's own dtype (float32 needs the
    # multi-pass product, or the TPU rounds the operand to bfloat16)
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None

    def pick(table):
        return jnp.einsum("...d,dr->...r", x, jnp.asarray(table, x.dtype),
                          precision=exact, preferred_element_type=x.dtype)

    return pick(a).astype(jnp.float32) * pick(b).astype(jnp.float32) * c


def _group(q, groups: int):
    """[B, T, H, d] -> [B, T, G, H/G, d]."""
    b, t, h, d = q.shape
    return q.reshape(b, t, groups, h // groups, d)


def _chunk_attend(q, k, v, cum, state, cum_prev, eps):
    """y of one chunk: the attention form over the chunk's own keys in
    blocks of rows, plus what the state carried in from earlier chunks.
    q [B, c, G, hg, d]; k, v [B, c, G, d]; cum [B, c, G] the running sum of
    log-gates; state (S, z) or None; cum_prev [B, G] the sum before the
    chunk."""
    c, d = q.shape[1], q.shape[-1]
    outs = []
    for r0 in range(0, c, ROW_BLOCK):
        r1 = min(r0 + ROW_BLOCK, c)
        qb, lq = q[:, r0:r1], cum[:, r0:r1]
        # a later key is never read: the slice is causal by construction
        kb, vb, lk = k[:, :r1], v[:, :r1], cum[:, :r1]
        s = jnp.einsum("btghd,bjgd->bghtj", qb, kb,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        keep = (jnp.arange(r0, r1)[:, None] >= jnp.arange(r1)[None, :])
        gap = lq.transpose(0, 2, 1)[..., :, None] \
            - lk.transpose(0, 2, 1)[..., None, :]          # [B, G, t, j]
        decay = jnp.exp(jnp.where(keep, gap, -jnp.inf))
        a = s * s * decay[:, :, None]
        num = jnp.einsum("bghtj,bjgd->btghd", a.astype(v.dtype), vb,
                         preferred_element_type=jnp.float32)
        den = a.sum(-1).transpose(0, 3, 1, 2)               # [B, t, G, hg]
        if state is not None:
            st, z = state
            w = jnp.exp(lq - cum_prev[:, None])[..., None]  # [B, t, G, 1]
            pq = phi(qb) / d
            num += w[..., None] * jnp.einsum("btghr,bgdr->btghd", pq, st)
            den += w * jnp.einsum("btghr,bgr->btgh", pq, z)
        outs.append(num / (den[..., None] + eps))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def _chunk_state(k, v, cum, state, cum_prev):
    """The state after one chunk: what came in, decayed over the chunk, plus
    sum_j exp(cum_end - cum_j) [v_j, 1] phi(k_j)^T, in blocks of tokens."""
    c, d = k.shape[1], k.shape[-1]
    end = cum[:, -1]                                        # [B, G]
    add = None
    for j0 in range(0, c, STATE_BLOCK):
        j1 = min(j0 + STATE_BLOCK, c)
        w = jnp.exp(end[:, None] - cum[:, j0:j1])[..., None]
        pk = (phi(k[:, j0:j1]) * w).astype(v.dtype)
        vb = v[:, j0:j1]
        v1 = jnp.concatenate([vb, jnp.ones_like(vb[..., :1])], axis=-1)
        part = jnp.einsum("bjgc,bjgr->bgcr", v1, pk,
                          preferred_element_type=jnp.float32)
        add = part if add is None else add + part
    st, z = add[:, :, :d], add[:, :, d]
    if state is not None:
        carry = jnp.exp(end - cum_prev)
        st = st + carry[..., None, None] * state[0]
        z = z + carry[..., None] * state[1]
    return st, z


def power_retention_chunked(q, k, v, log_g, lengths=None, chunk=None,
                            eps: float = EPS):
    """A prompt, from an empty state. q [B, T, H, d]; k, v [B, T, G, d];
    log_g [B, T, G] (<= 0); lengths [B] or None (every position real);
    chunk: tokens a chunk (None: one chunk; it need not divide T).
    Returns (y [B, T, H, d] in v's dtype, (S [B, G, d, R], z [B, G, R]))
    with the state as it stands after position lengths - 1. Rows of y at
    positions >= lengths are not meaningful."""
    b, t, h, d = q.shape
    g = k.shape[2]
    log_g = log_g.astype(jnp.float32)
    if lengths is not None:
        real = jnp.arange(t)[None, :] < lengths[:, None]
        k = jnp.where(real[..., None, None], k, 0)
        v = jnp.where(real[..., None, None], v, 0)
        log_g = jnp.where(real[..., None], log_g, 0.0)
    cum = jnp.cumsum(log_g, axis=1)
    qg = _group(q, g)
    chunk = t if chunk is None else int(chunk)
    state, cum_prev, ys = None, None, []
    for c0 in range(0, t, chunk):
        c1 = min(c0 + chunk, t)
        part = (k[:, c0:c1], v[:, c0:c1], cum[:, c0:c1], state, cum_prev)
        ys.append(_chunk_attend(qg[:, c0:c1], *part, eps))
        state = _chunk_state(*part)
        cum_prev = cum[:, c1 - 1]
    y = jnp.concatenate(ys, axis=1) if len(ys) > 1 else ys[0]
    return y.reshape(b, t, h, d).astype(v.dtype), state


def _step_jnp(pq, pk, gate, v, st):
    """The step's numerator and new state in `jax.numpy`: the CPU path and
    the kernel's reference."""
    new = (gate[..., None, None] * st
           + v.astype(jnp.float32)[..., :, None] * pk[..., None, :])
    return jnp.einsum("bghr,bgdr->bghd", pq, new), new


def _step_kernel(st_ref, pk_ref, pq_ref, gate_ref, v_ref, out_ref, num_ref):
    """One tile [d, rows] of one (slot, head): decay, add the rank-1
    product, store in place, and add the tile's part of S phi(q)."""
    new = gate_ref[0, 0] * st_ref[0, 0] + v_ref[0, 0] * pk_ref[0, 0]
    out_ref[0, 0] = new
    part = jax.lax.dot_general(pq_ref[0, 0], new, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        num_ref[0, 0] = part

    @pl.when(pl.program_id(2) > 0)
    def _():
        num_ref[0, 0] += part


def _tile_rows(rows: int, d: int, cap: int = 2 ** 20) -> int:
    """The most lanes of a [d, lanes] float32 tile that divide `rows` in
    whole 128s and stay under `cap` bytes (in and out, double-buffered:
    four tiles live)."""
    n = rows // 128
    best = 1
    for m in range(1, n + 1):
        if n % m == 0 and d * m * 128 * 4 <= cap:
            best = m
    return best * 128


def _step_pallas(pq, pk, gate, v, st):
    """The same as one Pallas kernel (interpreted off a TPU)."""
    b, g, d, rows = st.shape
    hg = pq.shape[2]
    hp = -(-hg // 8) * 8                     # query heads padded to a sublane tile
    pq = jnp.pad(pq, ((0, 0), (0, 0), (0, hp - hg), (0, 0)))
    tr = _tile_rows(rows, d)
    col = lambda x: x.astype(jnp.float32)[..., None]        # [B, G, d, 1]
    gate = jnp.broadcast_to(gate[..., None], (b, g, d))
    tile = pl.BlockSpec((1, 1, d, tr), lambda i, j, r: (i, j, 0, r))
    column = pl.BlockSpec((1, 1, d, 1), lambda i, j, r: (i, j, 0, 0))
    new, num = pl.pallas_call(
        _step_kernel, name=STEP_KERNEL, grid=(b, g, rows // tr),
        in_specs=[tile,
                  pl.BlockSpec((1, 1, 1, tr), lambda i, j, r: (i, j, 0, r)),
                  pl.BlockSpec((1, 1, hp, tr), lambda i, j, r: (i, j, 0, r)),
                  column, column],
        out_specs=(tile,
                   pl.BlockSpec((1, 1, hp, d), lambda i, j, r: (i, j, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct(st.shape, st.dtype),
                   jax.ShapeDtypeStruct((b, g, hp, d), jnp.float32)),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
    )(st, pk[:, :, None, :], pq, col(gate), col(v))
    return num[:, :, :hg], new


def power_retention_step(q, k, v, log_g, state, eps: float = EPS):
    """One token. q [B, H, d]; k, v [B, G, d]; log_g [B, G]; state (S, z)
    in float32, as `power_retention_chunked` returns it. Returns
    (y [B, H, d] in v's dtype, (S', z')): the Pallas kernel on a TPU,
    `jax.numpy` elsewhere."""
    st, z = state
    b, h, d = q.shape
    g = k.shape[1]
    gate = jnp.exp(log_g.astype(jnp.float32))
    pk = phi(k)
    pq = phi(q.reshape(b, g, h // g, d)) / d
    z = gate[..., None] * z + pk
    den = jnp.einsum("bghr,bgr->bgh", pq, z)
    step = _step_pallas if jax.default_backend() == "tpu" else _step_jnp
    num, st = step(pq, pk, gate, v, st)
    y = num / (den[..., None] + eps)
    return y.reshape(b, h, d).astype(v.dtype), (st, z)
