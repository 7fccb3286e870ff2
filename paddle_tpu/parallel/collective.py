"""Collective communication API.

Reference parity: `python/paddle/distributed/collective.py` (all_reduce:289,
all_gather, broadcast, reduce, scatter, alltoall, send/recv, barrier,
new_group:208) over the `operators/collective/c_*` op corpus.

TPU-native: a collective is an XLA op over a MESH AXIS, not an NCCL ring.
Each function has two execution regimes, detected automatically:
  1. inside an SPMD region (shard_map'd / pjit-manual code where the mesh
     axis name is bound) — lowers to lax.psum / all_gather / ppermute /
     all_to_all riding ICI;
  2. eager, single-controller — the global array is already replicated or
     sharded across the mesh; reductions become jnp ops on the global view
     (XLA inserts the transfer), so user code behaves like rank-0 semantics
     of the reference.
The `group` argument accepts a mesh axis name (str) — the `ring_id` of the
TPU world. `ReduceOp` mirrors the reference enum.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import monitor as _monitor
from .. import obs as _obs
from ..core.tensor import Tensor
from ..ops._dispatch import ensure_tensor, run_op


def _record(name: str, t) -> None:
    """Monitor + flight-recorder planes: count the collective and its
    logical payload bytes. Works on tracers too (shape/dtype are static),
    so SPMD-region collectives are accounted once per trace. The flight
    recorder keeps the recent (name, bytes) sequence — after a wedged
    collective, the dump shows what the rank issued leading up to it."""
    if not (_monitor._ENABLED or _obs._FR_ENABLED):
        return
    v = getattr(t, "_value", t)
    try:
        nbytes = int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
    except Exception:
        nbytes = 0
    if _monitor._ENABLED:
        _monitor.record_collective(name, nbytes)
    if _obs._FR_ENABLED:
        _obs.record_collective(name, nbytes)


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """Mesh-axis-backed communication group (ring_id → axis name)."""

    def __init__(self, axis_name: str, nranks: int = 1, ring_id: int = 0):
        self.axis_name = axis_name
        self.nranks = nranks
        self.id = ring_id

    @property
    def world_size(self):
        return self.nranks

    def __repr__(self):
        return f"Group(axis={self.axis_name}, nranks={self.nranks})"


_GROUPS = {}


def new_group(ranks=None, backend=None, axis_name: Optional[str] = None):
    """Create a group. TPU-native: groups are mesh axes; pass axis_name, or
    ranks spanning a full axis of the current mesh."""
    from .topology import get_mesh
    mesh = get_mesh()
    name = axis_name or (f"g{len(_GROUPS)}" if ranks else "dp")
    n = len(ranks) if ranks else (mesh.shape.get(name, 1) if mesh else 1)
    g = Group(name, n, ring_id=len(_GROUPS) + 1)
    _GROUPS[g.id] = g
    return g


def _axis(group):
    if group is None:
        return None
    if isinstance(group, Group):
        return group.axis_name
    if isinstance(group, str):
        return group
    return None


def _in_spmd(axis_name) -> bool:
    """True when `axis_name` is bound in the current trace (inside shard_map)."""
    if axis_name is None:
        return False
    try:
        lax.axis_size(axis_name)
        return True
    except Exception:
        return False


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """In-place (paddle semantics): tensor payload replaced with the result."""
    t = ensure_tensor(tensor)
    _record("c_allreduce", t)
    ax = _axis(group) or "dp"
    if _in_spmd(ax):
        red = {ReduceOp.SUM: lax.psum, ReduceOp.MAX: lax.pmax, ReduceOp.MIN: lax.pmin}
        with _obs.phase("collective"):
            if op == ReduceOp.AVG:
                out = run_op(lambda a: lax.pmean(a, ax), [t], "c_allreduce_avg")
            else:
                fn = red.get(op)
                if fn is None:  # PROD via exp-sum-log not safe; use reduce then broadcast
                    out = run_op(lambda a: jnp.exp(lax.psum(jnp.log(a), ax)), [t],
                                 "c_allreduce_prod")
                else:
                    out = run_op(lambda a: fn(a, ax), [t], "c_allreduce")
        from ..ops._dispatch import inplace_from
        return inplace_from(t, out)
    # eager single-controller: the global array already holds the logical value
    return t


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    t = ensure_tensor(tensor)
    _record("c_allgather", t)
    ax = _axis(group) or "dp"
    if _in_spmd(ax):
        with _obs.phase("collective"):
            out = run_op(lambda a: lax.all_gather(a, ax, tiled=False), [t],
                         "c_allgather")
        n = lax.axis_size(ax)
        parts = [Tensor(out._value[i]) for i in range(n)]
        if tensor_list is not None:
            tensor_list.extend(parts)
        return out
    if tensor_list is not None:
        tensor_list.append(t)
    return t


def all_gather_object(obj_list, obj, group=None):
    obj_list.append(obj)
    return obj_list


def store_all_gather_object(store, key: str, obj, rank: int, world_size: int,
                            timeout_s: float = 30.0, poll_s: float = 0.01):
    """Multi-controller all-gather of a small JSON-able object through a
    rendezvous store (TCPStore, or any set/get mapping). The eager
    collectives above cover the single-controller regime where every rank
    IS this process; cross-PROCESS exchange (guard desync fingerprints,
    membership votes) goes through the store the job already rendezvoused
    on. Returns {rank: obj}; raises TimeoutError when a peer's value does
    not appear within `timeout_s` (a hang, not a desync — callers must not
    blame a rank for being slow)."""
    import json as _json
    import time as _time
    with _obs.phase("collective"):
        store.set(f"{key}:{rank}", _json.dumps(obj))
        if _monitor._ENABLED:
            _monitor.count("c_store_allgather_obj")
        if _obs._FR_ENABLED:
            _obs.record_collective("store_allgather_obj", 0)
        out = {}
        deadline = _time.monotonic() + timeout_s
        for r in range(world_size):
            while True:
                try:
                    raw = store.get(f"{key}:{r}")
                    break
                except Exception:
                    if _time.monotonic() > deadline:
                        raise TimeoutError(
                            f"store_all_gather_object: rank {r} never published "
                            f"{key!r} within {timeout_s}s")
                    _time.sleep(poll_s)
            out[r] = _json.loads(raw.decode() if isinstance(raw, (bytes, bytearray))
                                 else raw)
    return out


def reduce_scatter(tensor, tensor_list_or_input, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    ax = _axis(group) or "dp"
    src = tensor_list_or_input
    if isinstance(src, (list, tuple)):
        from ..ops.manipulation import concat
        src = concat(list(src), axis=0)
    t = ensure_tensor(src)
    _record("c_reducescatter", t)
    if _in_spmd(ax):
        with _obs.phase("collective"):
            out = run_op(lambda a: lax.psum_scatter(a, ax, tiled=True), [t],
                         "c_reducescatter")
        if tensor is not None:
            tensor._value = out._value
        return out
    return t


def broadcast(tensor, src=0, group=None, sync_op=True):
    t = ensure_tensor(tensor)
    _record("c_broadcast", t)
    ax = _axis(group) or "dp"
    if _in_spmd(ax):
        idx = lax.axis_index(ax)
        out = run_op(
            lambda a: lax.psum(jnp.where(idx == src, a, jnp.zeros_like(a)), ax),
            [t], "c_broadcast")
        from ..ops._dispatch import inplace_from
        return inplace_from(t, out)
    return t


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)  # SPMD: every shard holds result


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    ax = _axis(group) or "dp"
    _record("c_scatter", ensure_tensor(tensor))
    if tensor_list is not None and _in_spmd(ax):
        from ..ops.manipulation import stack
        stacked = stack(list(tensor_list), axis=0)
        idx = lax.axis_index(ax)
        out = run_op(lambda a: a[idx], [stacked], "c_scatter")
        tensor._value = out._value
        return tensor
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    ax = _axis(group) or "mp"
    if isinstance(in_tensor_list, (list, tuple)):
        from ..ops.manipulation import stack
        src = stack(list(in_tensor_list), axis=0)
    else:
        src = ensure_tensor(in_tensor_list)
    _record("alltoall", src)
    if _in_spmd(ax):
        out = run_op(lambda a: lax.all_to_all(a, ax, split_axis=0, concat_axis=0,
                                              tiled=False), [src], "alltoall")
        if out_tensor_list is not None:
            n = lax.axis_size(ax)
            out_tensor_list.extend(Tensor(out._value[i]) for i in range(n))
        return out
    if out_tensor_list is not None and isinstance(in_tensor_list, (list, tuple)):
        out_tensor_list.extend(in_tensor_list)
    return src


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    t = ensure_tensor(in_tensor)
    _record("alltoall_single", t)
    ax = _axis(group) or "mp"
    if _in_spmd(ax):
        out = run_op(lambda a: lax.all_to_all(a, ax, split_axis=0, concat_axis=0,
                                              tiled=True), [t], "alltoall_single")
        if out_tensor is not None:
            out_tensor._value = out._value
        return out
    return t


def send(tensor, dst=0, group=None, sync_op=True):
    """SPMD p2p: expressed as ppermute to the destination stage (pipeline use)."""
    t = ensure_tensor(tensor)
    _record("send_v2", t)
    ax = _axis(group) or "pp"
    if _in_spmd(ax):
        n = lax.axis_size(ax)
        perm = [(i, (i + 1) % n) for i in range(n)]
        return run_op(lambda a: lax.ppermute(a, ax, perm), [t], "send_v2")
    return t


def recv(tensor, src=0, group=None, sync_op=True):
    return ensure_tensor(tensor)


isend = send
irecv = recv


def p2p_shift(x, group="pp", shift=1):
    """ppermute neighbour shift — the TPU-native partial_send/recv."""
    t = ensure_tensor(x)
    _record("p2p_shift", t)
    ax = _axis(group) or "pp"
    n = lax.axis_size(ax)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return run_op(lambda a: lax.ppermute(a, ax, perm), [t], "p2p_shift")


def barrier(group=None):
    # single-controller SPMD: dispatch order already serializes; sync devices
    for d in jax.devices():
        pass
    jnp.zeros(()).block_until_ready()


def wait(tensor, group=None, use_calc_stream=True):
    ensure_tensor(tensor).block_until_ready()


def get_group(ring_id=0):
    return _GROUPS.get(ring_id)


# ---- model-parallel helpers (collective.py:793-927 parity) ----
def _c_identity(tensor, group=None):
    return ensure_tensor(tensor)


def _mp_allreduce(tensor, op=ReduceOp.SUM, group=None, use_calc_stream=True,
                  use_model_parallel=True):
    return all_reduce(tensor, op, group or "mp")


def _c_concat(tensor, group=None):
    t = ensure_tensor(tensor)
    _record("c_concat", t)
    ax = _axis(group) or "mp"
    if _in_spmd(ax):
        return run_op(lambda a: lax.all_gather(a, ax, axis=a.ndim - 1, tiled=True),
                      [t], "c_concat")
    return t


def _c_split(tensor, group=None):
    t = ensure_tensor(tensor)
    _record("c_split", t)
    ax = _axis(group) or "mp"
    if _in_spmd(ax):
        n = lax.axis_size(ax)
        idx = lax.axis_index(ax)

        def f(a):
            sz = a.shape[-1] // n
            return lax.dynamic_slice_in_dim(a, idx * sz, sz, axis=a.ndim - 1)

        return run_op(f, [t], "c_split")
    return t
