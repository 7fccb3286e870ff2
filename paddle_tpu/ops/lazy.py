"""Lazy batching eager executor — kill the per-op dispatch tax.

Reference parity: the final-state eager dygraph (`paddle/fluid/eager/`)
retired fluid's per-op Tracer round trip; on a TPU the analogous tax is
one cached-XLA-executable dispatch per primitive chain
(`ops/_dispatch.run_op`), one host dispatch per op. This module retires it the
TPU-native way: under ``FLAGS_lazy_eager``, ``run_op``/``nondiff_op`` stop
executing and instead append ``(fn, inputs, name)`` records to a per-thread
:class:`LazySegment`; output Tensors carry a :class:`_LazyValue` pending
payload. At a *sync point* — exactly the sites tpu-lint's host-sync /
tensor-branch rules enumerate (``.numpy()``/``.item()``/``float()``/
``bool()``/print, control flow on tensor values, ``backward()``,
``paddle.sync()``) — the segment is topologically closed, keyed by its
op-sequence + leaf shape/dtype signature, compiled once into a single
jitted replay, and dispatched as ONE executable. Steady-state eager steps
therefore dispatch O(1) executables instead of O(ops).

The tape keeps working: a deferred diff op records its node immediately
(against the lazy outputs) with a :class:`_PendingVJP` placeholder; the
flush patches every placeholder to a real :class:`autograd._JitVJP` whose
residuals came out of the same jitted replay, so ``backward()`` (which
flushes first) runs the normal — and, for repeating tapes, fused — walk.

Fallbacks (each op, decided at defer time; counted as
``lazy.fallback_ops``): inputs already tracers (inside a jax trace), an
op closure that cannot be value-keyed (`autograd._fn_key` raises), an op
whose shapes cannot be abstractly evaluated, or a diff op mixing a
non-stop-gradient integer input. Fallback materializes pending inputs and
lets the immediate path run the op, preserving eager semantics bit-for-bit.

Accounting (FLAGS_monitor): ``lazy.ops_deferred``, ``lazy.flushes``,
``lazy.dispatches``, ``lazy.ops_flushed``, ``lazy.cache_hits``,
``lazy.fallback_ops``, plus ``jit.lazy_segment.traces``/``.retraces``
via ``monitor.record_retrace`` (the shared ``core/executable.py``
ledger regime). Observability: each flush is booked on
the step timeline as one ``trace_compile`` (novel signature) or
``device_compute`` (cache hit) phase — not smeared per-op.
"""
from __future__ import annotations

import sys
import threading
from collections import OrderedDict
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor
from .. import obs as _obs
from ..obs import memory as _mem
from ..core import autograd
from ..core import compile_cache as _cc
from ..core import executable as _exe
from ..core import flags as _flags
from ..core import tensor as _tensor_mod
from ..core.tensor import Tensor

__all__ = ["LazySegment", "flush_pending", "pending_ops", "sync"]


class _LazyValue:
    """Pending payload of a deferred op's output Tensor.

    Carries the abstract value (shape/dtype) so metadata reads stay free;
    any *data* read (`__array__`/`__jax_array__`/`block_until_ready`)
    flushes the owning segment and resolves to the concrete array. After
    the flush, `_arr` is set so stale aliases (detach/clone sharing the
    payload) keep resolving without touching the dead segment.
    """

    __slots__ = ("_arr", "_seg", "_ridx", "_oidx", "shape", "dtype",
                 "weak_type", "_ts")

    def __init__(self, seg: "LazySegment", ridx: int, oidx: int, aval):
        self._arr = None
        self._seg = seg
        self._ridx = ridx
        self._oidx = oidx
        self.shape = tuple(aval.shape)
        self.dtype = np.dtype(aval.dtype)
        self.weak_type = bool(getattr(aval, "weak_type", False))
        self._ts: List[Tensor] = []   # tensors to patch concrete at flush

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    def _resolve(self):
        if self._arr is None:
            self._seg.flush()
        return self._arr

    # ---- sync points: any data access materializes the segment ----
    def __array__(self, dtype=None):
        a = np.asarray(self._resolve())
        return a.astype(dtype) if dtype is not None else a

    def __jax_array__(self):
        return self._resolve()

    def block_until_ready(self):
        a = self._resolve()
        if hasattr(a, "block_until_ready"):
            a.block_until_ready()
        return a

    def __repr__(self):
        state = "materialized" if self._arr is not None else "pending"
        return f"<lazy {state} {self.dtype.name}{list(self.shape)}>"


class _PendingVJP:
    """Tape placeholder for a deferred diff op's VJP: invoking it (an eager
    backward reaching an unflushed node) flushes the segment, which patches
    in the real `_JitVJP`; delegate to it."""

    __slots__ = ("seg", "resolved")

    def __init__(self, seg: "LazySegment"):
        self.seg = seg
        self.resolved = None

    def __call__(self, cts):
        if self.resolved is None:
            self.seg.flush()
        if self.resolved is None:      # flush died before reaching this op
            raise RuntimeError("lazy VJP unresolved after segment flush")
        return self.resolved(cts)


class _Record:
    """One deferred op: how to re-derive its inputs inside the replay and
    where to deliver its outputs/VJP afterwards."""

    __slots__ = ("fn", "name", "kind", "bindings", "inexact", "multi",
                 "lvs", "node", "pending", "key", "nan_check")

    def __init__(self, fn, name, kind, bindings, inexact, multi, lvs,
                 node, pending, key, nan_check):
        self.fn = fn
        self.name = name
        self.kind = kind            # "vjp" | "vjp_split" | "primal" | "nondiff"
        self.bindings = bindings    # tuple of ("l", leaf_idx) | ("r", rec, out)
        self.inexact = inexact      # tuple[bool] for vjp_split, else None
        self.multi = multi          # fn returns a tuple
        self.lvs = lvs              # output _LazyValues, positional
        self.node = node            # tape Node (diff records) or None
        self.pending = pending      # _PendingVJP installed on the node
        self.key = key              # hashable replay-cache component
        self.nan_check = nan_check  # FLAGS_check_nan_inf was on at defer


# ---- segment signature cache (executable-substrate ledger) ----------------
# LRU-ordered: a flush hit moves the signature to the MRU end, overflow
# evicts from the LRU end one entry at a time (the old wholesale .clear()
# threw away every hot replay whenever one workload overflowed the cap).
# Replaces the private _SEG_CACHE/_SEG_SEEN pair with the shared
# core/executable.py ledger; the monitor eviction counter keeps its name.


def _count_eviction(_sig, _replay) -> None:
    if _monitor._ENABLED:
        _monitor.count("lazy.cache_evictions")


_LEDGER = _exe.ExecutableLedger(
    "lazy_segment",
    cap=max(1, int(_flags.flag("lazy_cache_entries"))),
    on_evict=_count_eviction)


def _on_cache_entries(value) -> None:
    _LEDGER.set_cap(max(1, int(value)))


_flags.watch_flag("lazy_cache_entries", _on_cache_entries)
# (fn-id component, input aval sig) -> output ShapeDtypeStructs
_SHAPE_CACHE: dict = {}
_SHAPE_CACHE_CAP = 8192

_FALLBACK = object()   # sentinel: dispatch must run the op immediately

# Hot-path gate: ops/_dispatch reads this module attribute; one attribute
# load is the entire disabled-path cost (PR 1 monitor._ENABLED regime).
_ACTIVE: bool = bool(_flags.flag("lazy_eager"))
_MAX_OPS: int = int(_flags.flag("lazy_max_segment_ops"))


def _on_max_ops(value) -> None:
    global _MAX_OPS
    _MAX_OPS = int(value)


_flags.watch_flag("lazy_max_segment_ops", _on_max_ops)


class _TLS(threading.local):
    def __init__(self):
        self.seg: Optional[LazySegment] = None


_STATE = _TLS()


def _segment() -> "LazySegment":
    seg = _STATE.seg
    if seg is None:
        seg = _STATE.seg = LazySegment()
    return seg


def _on_flag(value) -> None:
    global _ACTIVE
    on = bool(value)
    if _ACTIVE and not on:
        flush_pending()            # turning lazy off is itself a sync point
    _ACTIVE = on


_flags.watch_flag("lazy_eager", _on_flag)


def pending_ops() -> int:
    """Deferred-op count in the calling thread's segment (0 = drained)."""
    seg = _STATE.seg
    return 0 if seg is None else len(seg.records)


def flush_pending() -> None:
    """Flush the calling thread's pending segment (no-op when drained)."""
    seg = _STATE.seg
    if seg is not None and seg.records:
        seg.flush()


def sync() -> None:
    """Explicit sync point (`paddle.sync()`): flush the pending lazy
    segment so every deferred op is executed and materialized."""
    flush_pending()


def segment_memory() -> List[dict]:
    """Compiler-reported memory breakdown for every cached segment replay
    executable (obs.executable_memory), MRU last. Each signature carries
    its leaf avals, so the replays AOT-lower without live inputs."""
    from .. import obs as _obs_pkg
    out = []
    for sig, replay in _LEDGER.items():
        structs = [jax.ShapeDtypeStruct(tuple(shape), np.dtype(dt),
                                        weak_type=wt)
                   for shape, dt, wt in sig[1]]
        try:
            rep = _obs_pkg.executable_memory(replay.lower(structs).compile())
        except Exception:
            continue
        out.append({"ops": len(sig[0]), "leaves": len(structs), **rep})
    return out


def _aval_of(v):
    return jax.ShapeDtypeStruct(
        v.shape, v.dtype, weak_type=bool(getattr(v, "weak_type", False)))


def _out_shapes(fn, fkey, in_avals):
    """eval_shape with a value-keyed cache; None when fn is untraceable."""
    sig = (fkey, tuple((a.shape, str(a.dtype)) for a in in_avals))
    try:
        hit = sig in _SHAPE_CACHE
    except TypeError:
        hit = False
        sig = None
    if hit:
        return _SHAPE_CACHE[sig]
    try:
        out = jax.eval_shape(fn, *in_avals)
    except Exception:
        return None
    if sig is not None:
        if len(_SHAPE_CACHE) >= _SHAPE_CACHE_CAP:
            _SHAPE_CACHE.clear()
        _SHAPE_CACHE[sig] = out
    return out


def _materialize_inputs(tensors) -> None:
    """Resolve any pending payloads so the immediate path sees arrays."""
    for t in tensors:
        v = t._value
        if type(v) is _LazyValue:
            t._value = v._resolve()


def _scan_nan_inf(name: str, arrs) -> None:
    # FLAGS_check_nan_inf parity for deferred ops: the per-op scan is
    # re-run over the flushed outputs (attribution by op name survives;
    # only the *timing* of the abort moves to the sync point).
    for i, o in enumerate(arrs):
        if jnp.issubdtype(o.dtype, jnp.floating):
            if not bool(jnp.all(jnp.isfinite(o))):  # tpu-lint: disable=host-sync (debug-only deferred NaN scan)
                raise FloatingPointError(
                    f"Operator {name} output {i} contains NaN/Inf "
                    "(FLAGS_check_nan_inf=True, detected at lazy flush)")


class LazySegment:
    """Per-thread accumulator of deferred ops and their dataflow.

    `leaves` are the concrete arrays entering the segment (deduped by
    identity); each record's inputs are bindings into the leaf list or
    into an earlier record's outputs, so the whole segment replays as a
    pure function of the leaves — compiled once per (op-sequence, leaf
    signature) and re-dispatched from the module segment ledger thereafter.
    """

    __slots__ = ("records", "leaves", "leaf_ids", "_flushing")

    def __init__(self):
        self.records: List[_Record] = []
        self.leaves: List[Any] = []
        self.leaf_ids: dict = {}
        self._flushing = False

    # ---- record side -----------------------------------------------------
    def _bind(self, v):
        """Binding for one input payload (concrete array or _LazyValue)."""
        if type(v) is _LazyValue:
            if v._arr is not None:
                v = v._arr                       # already materialized
            elif v._seg is not self:
                v = v._resolve()                 # cross-thread tensor: sync
            else:
                return ("r", v._ridx, v._oidx)
        i = self.leaf_ids.get(id(v))
        if i is None:
            i = self.leaf_ids[id(v)] = len(self.leaves)
            self.leaves.append(v)
        return ("l", i)

    def defer(self, fn, tensors, name, kind, inexact, record):
        """Append one op; returns wrapped output Tensor(s) or _FALLBACK."""
        try:
            fkey = autograd._fn_key(fn)
        except autograd._Uncacheable:
            _materialize_inputs(tensors)
            if _monitor._ENABLED:
                _monitor.count("lazy.fallback_ops")
            return _FALLBACK
        in_avals = [_aval_of(t._value) for t in tensors]
        out = _out_shapes(fn, fkey, in_avals)
        if out is None:
            _materialize_inputs(tensors)
            if _monitor._ENABLED:
                _monitor.count("lazy.fallback_ops")
            return _FALLBACK
        if len(self.records) >= _MAX_OPS:
            self.flush()
        multi = isinstance(out, tuple)
        out_avals = out if multi else (out,)
        bindings = tuple(self._bind(t._value) for t in tensors)
        ridx = len(self.records)
        lvs = [_LazyValue(self, ridx, i, a) for i, a in enumerate(out_avals)]
        out_tensors = [Tensor(lv) for lv in lvs]
        for lv, t in zip(lvs, out_tensors):
            lv._ts.append(t)
        node = pending = None
        if record:
            pending = _PendingVJP(self)
            node = autograd.record_node(pending, tensors, out_tensors,
                                        name, fn=fn)
        key = (kind, fkey, bindings, inexact, multi)
        self.records.append(_Record(
            fn, name, kind, bindings, inexact, multi, lvs, node, pending,
            key, _flags.flag("check_nan_inf")))
        if _monitor._ENABLED:
            _monitor.count("lazy.ops_deferred")
        if multi:
            return tuple(out_tensors)
        return out_tensors[0]

    # ---- flush side ------------------------------------------------------
    def flush(self) -> None:
        """Sync point: close the segment, dispatch it as one executable,
        and deliver outputs/VJPs back onto the recorded tensors/tape."""
        if self._flushing or not self.records:
            return
        self._flushing = True
        records, leaves = self.records, self.leaves
        self.records, self.leaves, self.leaf_ids = [], [], {}
        try:
            sig = (tuple(r.key for r in records),
                   tuple((tuple(a.shape), str(a.dtype),
                          bool(getattr(a, "weak_type", False)))
                         for a in leaves))
            replay = _LEDGER.get(sig)
            novel = not _LEDGER.seen(sig)
            if _monitor._ENABLED:
                _monitor.count("lazy.flushes")
                _monitor.count("lazy.dispatches")
                _monitor.count("lazy.ops_flushed", len(records))
                if not novel:
                    _monitor.count("lazy.cache_hits")
            if novel:
                _LEDGER.note(sig, detail=(
                    (f"ops={len(records)}",)
                    + _monitor.arg_signature(leaves))
                    if _monitor._ENABLED else None)
            with _exe.booking("lazy_segment") as bk:
                if replay is None:
                    replay = _build_replay(records)
                    source = "fresh"
                    if _cc.enabled() and all(
                            r.kind in ("primal", "nondiff")
                            for r in records):
                        # only sync-free segments persist: a diff segment's
                        # replay returns jax.vjp closures, which the export
                        # path cannot serialize (they'd count export_skips
                        # for every flush — skip upfront instead)
                        replay, source = _exe.acquire(
                            "lazy_segment", replay, (leaves,),
                            label=f"ops={len(records)}")
                    _LEDGER.put(sig, replay)
                    if novel and source == "fresh":
                        bk.compiled()
                elif novel:
                    bk.compiled()
                out_groups, vjp_raws = replay(leaves)
            if _mem._ENABLED:
                _mem.tag("lazy_segment",
                         [arr for outs in out_groups for arr in outs],
                         origin=f"LazySegment.flush ops={len(records)}")
            # deliver: materialize payloads, rebind tensors, patch VJPs
            for rec, outs, raw in zip(records, out_groups, vjp_raws):
                for lv, arr in zip(rec.lvs, outs):
                    lv._arr = arr
                    for t in lv._ts:
                        if type(t._value) is _LazyValue:
                            t._value = arr
                if rec.node is not None:
                    jv = autograd._JitVJP(raw, rec.inexact)
                    rec.pending.resolved = jv
                    if rec.node.vjp_fn is rec.pending:
                        rec.node.vjp_fn = jv
            for rec, outs in zip(records, out_groups):
                if rec.nan_check:
                    _scan_nan_inf(rec.name, outs)
        finally:
            self._flushing = False


def _build_replay(records):
    """Jit the whole segment as one pure function of its leaves, returning
    every record's outputs plus the VJP residuals of the diff records
    (jax.vjp's closure is a pytree over a static treedef, so it rides out
    of the jit — the `autograd._cached_jit(kind='vjp')` precedent)."""
    specs = tuple((r.kind, r.fn, r.inexact, r.bindings) for r in records)

    def replay(leaves):
        vals: List[tuple] = []
        vjps: List[Any] = []
        for kind, fn, inexact, bindings in specs:
            ins = [leaves[b[1]] if b[0] == "l" else vals[b[1]][b[2]]
                   for b in bindings]
            if kind == "vjp":
                outs, raw = jax.vjp(fn, *ins)
            elif kind == "vjp_split":
                outs, raw = autograd._split_vjp_builder(fn, inexact)(*ins)
            else:
                outs, raw = fn(*ins), None
            vals.append(outs if isinstance(outs, tuple) else (outs,))
            vjps.append(raw)
        return vals, vjps

    return jax.jit(replay)


def defer_op(fn, tensors, name):
    """run_op front half under FLAGS_lazy_eager. Returns Tensor(s) or
    _FALLBACK (after materializing pending inputs) when the op must run
    immediately."""
    seg = _segment()
    arrays = [t._value for t in tensors]
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        _materialize_inputs(tensors)   # inside a jax trace: let JAX see it
        return _FALLBACK
    record = autograd._STATE.enabled and any(
        not t.stop_gradient for t in tensors)
    if not record:
        return seg.defer(fn, tensors, name, "primal", None, False)
    inexact = tuple(
        bool(jnp.issubdtype(a.dtype, jnp.inexact)) for a in arrays)
    if all(inexact):
        return seg.defer(fn, tensors, name, "vjp", None, True)
    if all(t.stop_gradient or f for t, f in zip(tensors, inexact)):
        return seg.defer(fn, tensors, name, "vjp_split", inexact, True)
    # differentiating through an integer input (float0 cotangents): rare —
    # keep exact immediate-mode semantics rather than teach the replay
    _materialize_inputs(tensors)
    if _monitor._ENABLED:
        _monitor.count("lazy.fallback_ops")
    return _FALLBACK


def defer_nondiff(fn, tensors):
    """nondiff_op front half under FLAGS_lazy_eager."""
    seg = _segment()
    if any(isinstance(t._value, jax.core.Tracer) for t in tensors):
        _materialize_inputs(tensors)
        return _FALLBACK
    return seg.defer(fn, tensors, "nondiff", "nondiff", None, False)


# Wire the pending-payload type into Tensor construction (no isinstance
# cost added to the non-lazy path: it extends the existing accepted-types
# tuple) and give autograd its flush-at-backward hook.
_tensor_mod._VALUE_TYPES = _tensor_mod._VALUE_TYPES + (_LazyValue,)
autograd._LAZY = sys.modules[__name__]
