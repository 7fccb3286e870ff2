"""Imperative autograd: a reverse-mode tape over JAX VJPs.

Reference parity: this is the TPU-native answer to the dygraph stack —
`imperative/tracer.cc:172` (TraceOp records grad nodes) +
`imperative/basic_engine.cc:391` (reverse-topological execute) +
`imperative/gradient_accumulator.cc` (grad sums).

TPU-first design: instead of per-op CUDA grad kernels selected by a grad-op
registry, every traced op captures its VJP via `jax.vjp` at forward time.
Forward runs eagerly on the XLA backend (each primitive is compile-cached by
JAX); backward walks the tape in reverse creation order and feeds cotangents
through the stored VJP closures. Gradients accumulate on leaf tensors'
`.grad`, matching Paddle dygraph semantics (stop_gradient, leaf-only grads).
"""
from __future__ import annotations

import functools
import threading
import weakref
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class _State(threading.local):
    def __init__(self):
        self.enabled = True
        self.seq = 0
        # Live-node registry for introspection only (tape_size). Weak refs:
        # node lifetime is keyed to output-tensor reachability, so side
        # branches (metrics, logging) are GC'd when their tensors die instead
        # of accumulating forever on a global list.
        self.live: "weakref.WeakSet[Node]" = weakref.WeakSet()


_STATE = _State()

# set by paddle_tpu.ops.lazy at import: backward()/paddle.grad are sync
# points for the lazy batching eager executor — the pending segment must
# flush (materializing outputs and patching _PendingVJP -> _JitVJP on the
# tape) before the walk starts
_LAZY = None


def _lazy_flush():
    if _LAZY is not None and _LAZY._ACTIVE:
        _LAZY.flush_pending()


class Node:
    """One traced op: inputs, outputs, and the VJP closure linking them.

    Nodes are NOT held by any global structure (only weakly, for stats);
    the graph is reachable solely through output tensors' `_node` refs and
    `node.inputs -> tensor -> _node` chains. `seq` preserves creation order
    so backward can process in reverse-creation order without a tape list.
    """

    __slots__ = ("vjp_fn", "inputs", "outputs", "name", "seq", "fn",
                 "__weakref__")

    def __init__(self, vjp_fn, inputs, outputs, name, fn=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs      # list[Tensor] (diff inputs, positional)
        self.outputs = outputs    # list[Tensor] (diff outputs, positional)
        self.name = name
        self.fn = fn              # primal fn — kept for double grad (remat)
        _STATE.seq += 1
        self.seq = _STATE.seq


def is_grad_enabled() -> bool:
    return _STATE.enabled


def set_grad_enabled(mode: bool):
    _STATE.enabled = bool(mode)


class no_grad:
    """Context manager & decorator: disable tape recording (paddle.no_grad)."""

    def __enter__(self):
        self._prev = _STATE.enabled
        _STATE.enabled = False
        return self

    def __exit__(self, *exc):
        _STATE.enabled = self._prev
        return False

    def __call__(self, fn):
        def wrapper(*a, **kw):
            with no_grad():
                return fn(*a, **kw)

        wrapper.__name__ = getattr(fn, "__name__", "wrapped")
        return wrapper


class enable_grad(no_grad):
    def __enter__(self):
        self._prev = _STATE.enabled
        _STATE.enabled = True
        return self


# ---- eager dispatch cache -------------------------------------------------
# The reference's dygraph hot loop (`imperative/tracer.cc:172`) pays one
# kernel launch per op; our eager hot loop pays one jax.vjp RE-TRACE per op
# (~5-10ms of Python) plus one host dispatch per primitive.
# Both collapse when the (forward, vjp) pair is traced ONCE per op closure
# and re-dispatched as a single cached XLA executable: `jax.jit` can return
# jax.vjp's function (it is a pytree of residual arrays over a static
# treedef), and a shared jitted applicator replays the backward.
#
# Cache key: the op closure's identity-by-VALUE — code object + frozen
# closure cells + frozen defaults. Closures capturing anything unhashable
# (arrays, Tensors, per-call lambdas) fall back to the uncached path, so
# caching can never alias two behaviorally different ops.

_JIT_CACHE: dict = {}
_UNJITTABLE: set = set()
_JIT_CACHE_CAP = 4096
from .random import TraceKeyError as _TraceKeyError  # noqa: E402

_BAILOUT_ERRORS = (jax.errors.TracerBoolConversionError,
                   jax.errors.ConcretizationTypeError,
                   jax.errors.TracerArrayConversionError,
                   jax.errors.TracerIntegerConversionError,
                   jax.errors.UnexpectedTracerError,
                   _TraceKeyError)


class _Uncacheable(Exception):
    pass


def _freeze(v):
    """Hashable value-token for a closure cell, or raise _Uncacheable."""
    if isinstance(v, (str, int, float, bool, bytes, complex, type(None))):
        return v
    if isinstance(v, np.dtype):
        return ("dt", v.str)
    if isinstance(v, tuple):
        return ("t",) + tuple(_freeze(x) for x in v)
    if isinstance(v, list):
        return ("l",) + tuple(_freeze(x) for x in v)
    if isinstance(v, (set, frozenset)):
        return ("s",) + tuple(sorted((_freeze(x) for x in v), key=repr))
    if isinstance(v, dict):
        return ("d",) + tuple((k, _freeze(x)) for k, x in sorted(v.items()))
    if isinstance(v, functools.partial):
        return ("p", _freeze(v.func), _freeze(v.args), _freeze(v.keywords))
    if callable(v):
        qn = getattr(v, "__qualname__", None)
        if qn is not None and "<locals>" not in qn \
                and getattr(v, "__module__", None):
            return ("f", v.__module__, qn)  # stable module-level callable
        if qn is None and type(v).__name__ == "ufunc" \
                and getattr(v, "__name__", None):
            # jnp.add/multiply/... are jax.numpy.ufunc instances: no
            # __qualname__, but singleton, stateless and named — a stable
            # token (this makes binary_op(jnp.<ufunc>) dispatch-cacheable)
            return ("uf", getattr(type(v), "__module__", "jnp"), v.__name__)
    raise _Uncacheable


def _ambient_key():
    """Global state op fns may read at trace time (AMP autocast regime,
    matmul precision flag, default dtype) — it must key the cache, or a fn
    traced under one regime would replay under another."""
    from ..amp.state import amp_state
    from . import flags as _flags
    from .dtype import get_default_dtype
    s = amp_state()
    return (s.enabled, str(s.dtype), s.level,
            _flags.flag("tpu_matmul_precision"), get_default_dtype())


def _fn_key(fn):
    # INVARIANT (ADVICE r4): this key freezes closure cells, defaults and
    # the fixed _ambient_key() tuple, but NOT module-level globals. Op fns
    # routed through the dispatch cache must not read mutable globals
    # outside _ambient_key — any new config flag an op fn consults at
    # trace time MUST be added to _ambient_key, or a cached executable
    # traced under the old value would silently replay after it changes.
    code = getattr(fn, "__code__", None)
    if code is None:
        return ("fn", _freeze(fn), _ambient_key())
    frozen = tuple(_freeze(c.cell_contents) for c in (fn.__closure__ or ()))
    dflt = _freeze(fn.__defaults__) if fn.__defaults__ else None
    kwd = _freeze(fn.__kwdefaults__) if getattr(fn, "__kwdefaults__", None) \
        else None
    return ("code", code, frozen, dflt, kwd, _ambient_key())


def _cached_jit(fn, kind, build=None):
    """Jitted forward (kind='primal') or forward+vjp (kind='vjp') for fn,
    or None when fn's closure can't be value-keyed."""
    try:
        key = (kind, _fn_key(fn))
    except _Uncacheable:
        return None, None
    if key in _UNJITTABLE:
        return None, None
    jf = _JIT_CACHE.get(key)
    if jf is None:
        from .. import monitor as _monitor
        if _monitor._ENABLED:
            _monitor.count("autograd.jit_cache_miss")
        if len(_JIT_CACHE) >= _JIT_CACHE_CAP:
            _JIT_CACHE.clear()
        if build is not None:
            jf = build()
        elif kind == "vjp":
            jf = jax.jit(lambda *a: jax.vjp(fn, *a))
        else:
            jf = jax.jit(fn)
        _JIT_CACHE[key] = jf
    return jf, key


@functools.lru_cache(maxsize=1)
def _bwd_apply():
    # jit cache specializes on the VJP pytree's treedef (= its backward
    # jaxpr), which is stable across calls of the same cached forward.
    return jax.jit(lambda vjp_fn, cts: vjp_fn(cts))


class _JitVJP:
    """VJP wrapper routing application through the shared jitted applicator
    so backward is one executable dispatch instead of an op-by-op walk.

    `inexact` (when set) marks which of the op's positional inputs were
    differentiated; integer/bool inputs got no cotangent slot and are
    reported as None (their tape entries are stop_gradient and skipped).
    `treedef` (when set) is the NESTED output structure of the traced
    function: the tape stores flat leaf tensors, so the flat cotangents
    are unflattened back before hitting the raw vjp (static-program
    captures of layers returning nested tuples, e.g. LSTM's
    (out, (h, c)))."""

    __slots__ = ("raw", "inexact", "treedef")

    def __init__(self, raw, inexact=None, treedef=None):
        self.raw = raw
        self.inexact = inexact
        self.treedef = treedef

    def __call__(self, cts):
        if self.treedef is not None:
            flat = list(cts) if isinstance(cts, tuple) else [cts]
            cts = jax.tree_util.tree_unflatten(self.treedef, flat)
        try:
            part = _bwd_apply()(self.raw, cts)
        except _BAILOUT_ERRORS:
            part = self.raw(cts)
        if self.inexact is None:
            return part
        it = iter(part)
        return tuple(next(it) if f else None for f in self.inexact)


def _split_vjp_builder(fn, inexact):
    """fn with integer args: differentiate only the inexact positions,
    threading the integer arrays through as plain jit arguments."""
    didx = tuple(i for i, f in enumerate(inexact) if f)

    def wrapper(*args):
        def g(*diff):
            it = iter(diff)
            full = [next(it) if inexact[i] else args[i]
                    for i in range(len(args))]
            return fn(*full)
        return jax.vjp(g, *(args[i] for i in didx))

    return wrapper


def apply_op(
    fn: Callable,
    diff_inputs: Sequence["Tensor"],  # noqa: F821
    name: str = "op",
    n_outs: int = 1,
) -> Any:
    """Run `fn(*arrays) -> array | tuple` over the diff inputs, recording a tape node
    when grad is enabled and any input requires grad.

    Returns raw jax output(s); wrapping into Tensor happens in the ops layer so
    this module stays free of Tensor construction policy.
    """
    arrays = tuple(t._value for t in diff_inputs)
    record = _STATE.enabled and any(not t.stop_gradient for t in diff_inputs)
    # Inside a jax trace (to_static), inputs are tracers: let JAX do the
    # differentiation; recording a tape of tracers would leak them.
    tracing = any(isinstance(a, jax.core.Tracer) for a in arrays)
    if record and tracing:
        record = False
    if not record:
        if tracing:
            return fn(*arrays), None
        jf, key = _cached_jit(fn, "primal")
        if jf is not None:
            try:
                return jf(*arrays), None
            except _BAILOUT_ERRORS:
                _UNJITTABLE.add(key)
        return fn(*arrays), None
    inexact = tuple(bool(jnp.issubdtype(a.dtype, jnp.inexact))
                    for a in arrays)
    if all(inexact):
        jf, key = _cached_jit(fn, "vjp")
        if jf is not None:
            try:
                outs, vjp_fn = jf(*arrays)
                return outs, _JitVJP(vjp_fn)
            except _BAILOUT_ERRORS:
                _UNJITTABLE.add(key)
    elif all(t.stop_gradient or f
             for t, f in zip(diff_inputs, inexact)):
        # integer inputs (labels, indices) ride through as jit args; only
        # the float positions are differentiated — no float0 round-trip.
        jf, key = _cached_jit(fn, ("vjp_split", inexact),
                              build=lambda f=fn: jax.jit(
                                  _split_vjp_builder(f, inexact)))
        if jf is not None:
            try:
                outs, vjp_fn = jf(*arrays)
                return outs, _JitVJP(vjp_fn, inexact)
            except _BAILOUT_ERRORS:
                _UNJITTABLE.add(key)
    outs, vjp_fn = jax.vjp(fn, *arrays)
    return outs, vjp_fn


def record_node(vjp_fn, diff_inputs, out_tensors, name, fn=None):
    node = Node(vjp_fn, list(diff_inputs), list(out_tensors), name, fn=fn)
    for t in out_tensors:
        t._node = node
        t.stop_gradient = False
    _STATE.live.add(node)
    return node


def _collect(roots):
    """Walk ancestor nodes from root nodes; return them sorted newest-first."""
    needed = {}
    stack = [n for n in roots if n is not None]
    while stack:
        node = stack.pop()
        if id(node) in needed:
            continue
        needed[id(node)] = node
        for t in node.inputs:
            if t._node is not None and id(t._node) not in needed:
                stack.append(t._node)
    return sorted(needed.values(), key=lambda n: -n.seq)


def _accumulate(store: dict, tensor, value):
    # SelectedRows values accumulate row-form (SelectedRows.__add__ handles
    # sparse+sparse concat and sparse+dense densify); conversion to dense
    # happens only when a cotangent is CONSUMED by an upstream jnp vjp
    # (_dense_cot) — paddle.grad on a sparse leaf stays sparse.
    if value is None:  # integer input skipped by a split vjp
        return
    key = id(tensor)
    cur = store.get(key)
    store[key] = value if cur is None else cur + value


def _dense_cot(c):
    """Cotangent about to enter a jnp-based vjp: densify SelectedRows."""
    from .selected_rows import SelectedRows
    return c.to_dense() if isinstance(c, SelectedRows) else c


# ---- fused tape walk ---------------------------------------------------
# The eager walk dispatches one jitted vjp per node (plus per-leaf adds):
# that is one host dispatch per op. When the whole tape is
# _JitVJP nodes (the common repeated-training-step shape), the walk itself
# is pure orchestration of arrays — so it can run INSIDE one jit, keyed by
# the tape's structure: each step's tensors are new objects, but the
# wiring (who feeds whom) repeats, and the vjp residual pytrees ride in as
# jit arguments. One executable per backward instead of N.
_FUSED_BWD_CACHE: dict = {}
_FUSED_BWD_SEEN: dict = {}
_FUSED_BWD_MAX = 256
_FUSED_BWD_THRESHOLD = 2   # compile only for REPEATING tape structures


def _fused_backward_try(root, grad, ordered):
    """Returns list of (leaf_tensor, grad_array) or None if ineligible."""
    from .selected_rows import SelectedRows
    # slot assignment: every tensor seen gets an integer slot
    slots: dict = {}
    tensors_by_slot: dict = {}

    def slot_of(t):
        s = slots.get(id(t))
        if s is None:
            s = slots[id(t)] = len(slots)
            tensors_by_slot[s] = t
        return s

    structure = []

    for node in ordered:
        if not isinstance(node.vjp_fn, _JitVJP):
            return None
        for t in node.inputs:
            if (not t.stop_gradient and t._node is None
                    and getattr(t, "_hooks", ())):
                return None        # leaf hooks: keep the eager walk
            if isinstance(t.grad, SelectedRows):
                return None
        out_slots = tuple(
            (slot_of(t), tuple(t._value.shape), str(t._value.dtype))
            for t in node.outputs)
        in_slots = tuple(
            (slot_of(t), bool(t.stop_gradient), t._node is None,
             str(t._value.dtype))
            for t in node.inputs)
        structure.append((node.name, node.vjp_fn.inexact,
                          node.vjp_fn.treedef, out_slots, in_slots))

    key = (len(slots), slot_of(root), tuple(structure))
    fn = _FUSED_BWD_CACHE.get(key)
    if fn is None:
        # gate the whole-tape compile on structure REPETITION (mirror of
        # the forward's _AUTOJIT_THRESHOLD): a varying-shape / dynamic-
        # graph workload would otherwise pay a full XLA compile on every
        # novel backward instead of the already-compiled eager walk
        seen = _FUSED_BWD_SEEN.get(key, 0) + 1
        if len(_FUSED_BWD_SEEN) >= 4 * _FUSED_BWD_MAX:
            _FUSED_BWD_SEEN.clear()
        _FUSED_BWD_SEEN[key] = seen
        if seen < _FUSED_BWD_THRESHOLD:
            return None
        if len(_FUSED_BWD_CACHE) >= _FUSED_BWD_MAX:
            _FUSED_BWD_CACHE.clear()
        struct = tuple(structure)
        root_slot = slot_of(root)

        def walk(g_root, raws):
            cot: dict = {root_slot: g_root}
            leaf_out: dict = {}
            for (name, inexact, treedef, out_slots, in_slots), raw in zip(
                    struct, raws):
                out_cots = []
                any_live = False
                for s, shp, dt in out_slots:
                    c = cot.pop(s, None)
                    if c is None:
                        c = jnp.zeros(shp, dt)
                    else:
                        any_live = True
                    out_cots.append(c)
                if not any_live:
                    continue
                if treedef is not None:
                    part = raw(jax.tree_util.tree_unflatten(treedef,
                                                            out_cots))
                else:
                    part = raw(tuple(out_cots) if len(out_cots) > 1
                               else out_cots[0])
                if inexact is not None:
                    it = iter(part)
                    part = tuple(next(it) if f else None for f in inexact)
                for (s, stop, is_leaf, dt), c in zip(in_slots, part):
                    if stop or c is None:
                        continue
                    if is_leaf:
                        c = c.astype(dt) if str(c.dtype) != dt else c
                        leaf_out[s] = (leaf_out[s] + c) if s in leaf_out \
                            else c
                    else:
                        cot[s] = (cot[s] + c) if s in cot else c
            return leaf_out

        fn = _FUSED_BWD_CACHE[key] = jax.jit(walk)
    raws = [n.vjp_fn.raw for n in ordered]
    try:
        leaf_grads = fn(grad, raws)
    except _BAILOUT_ERRORS:
        return None
    return [(tensors_by_slot[s], g) for s, g in leaf_grads.items()]


def backward(root, grad=None, retain_graph: bool = False):
    """Run the tape backward from `root` (paddle.Tensor.backward parity)."""
    from .. import monitor as _monitor
    if not _monitor._ENABLED:
        return _backward_impl(root, grad, retain_graph)
    import time as _time
    _t0 = _time.time()
    try:
        return _backward_impl(root, grad, retain_graph)
    finally:
        _monitor.count("autograd.backward_count")
        _monitor.observe("autograd.backward_dur", _time.time() - _t0)


def _backward_impl(root, grad=None, retain_graph: bool = False):
    _lazy_flush()
    if root._node is None:
        if not root.stop_gradient:
            g = jnp.ones_like(root._value) if grad is None else grad
            root.grad = (root.grad + g) if root.grad is not None else +g
        return

    if grad is None:
        if root._value.size != 1:
            raise RuntimeError(
                "backward() on a non-scalar tensor requires an explicit grad "
                f"(shape {root._value.shape})"
            )
        grad = jnp.ones_like(root._value)
    elif hasattr(grad, "_value"):
        grad = grad._value

    ordered = _collect([root._node])
    from .. import monitor as _monitor
    if _monitor._ENABLED:
        _monitor.count("autograd.nodes_walked", len(ordered))

    fused = _fused_backward_try(root, grad, ordered)
    if fused is not None:
        if _monitor._ENABLED:
            _monitor.count("autograd.fused_backward")
        for t, g in fused:
            t.grad = g if t.grad is None else t.grad + g
        if not retain_graph:
            for n in ordered:
                for t in n.outputs:
                    t._node = None
                n.vjp_fn = None
                n.inputs = n.outputs = ()
                _STATE.live.discard(n)
        return

    cot: dict = {id(root): grad}
    with no_grad():
        for node in ordered:
            out_cots = []
            any_live = False
            for t in node.outputs:
                c = cot.pop(id(t), None)
                if c is None:
                    c = jnp.zeros_like(t._value)
                else:
                    any_live = True
                out_cots.append(_dense_cot(c))
            if not any_live:
                continue
            in_cots = node.vjp_fn(tuple(out_cots) if len(out_cots) > 1 else out_cots[0])
            for t, c in zip(node.inputs, in_cots):
                if t.stop_gradient:
                    continue
                if t._node is None:  # leaf: accumulate .grad
                    from .selected_rows import SelectedRows
                    if isinstance(c, SelectedRows):
                        # sparse embedding grad: stays row-form; hooks see
                        # the SelectedRows; mixing with an existing dense
                        # grad densifies via __add__
                        for h in getattr(t, "_hooks", ()):
                            r = h(c)
                            if r is not None:
                                c = r._value if hasattr(r, "_value") else r
                        t.grad = c if t.grad is None else t.grad + c
                        continue
                    gc = c.astype(t._value.dtype) if c.dtype != t._value.dtype else c
                    for h in getattr(t, "_hooks", ()):
                        r = h(gc)
                        if r is not None:
                            gc = r._value if hasattr(r, "_value") else r
                    t.grad = gc if t.grad is None else t.grad + gc
                else:
                    _accumulate(cot, t, c)

    if not retain_graph:
        for n in ordered:
            for t in n.outputs:
                t._node = None
            n.vjp_fn = None
            n.inputs = n.outputs = ()
            _STATE.live.discard(n)


def grad_fn(outputs, inputs, grad_outputs=None, retain_graph=False, create_graph=False,
            allow_unused=False):
    """paddle.grad parity (partial_grad_engine.cc): grads of outputs w.r.t.
    inputs without touching .grad. With create_graph=True the backward pass
    itself is RECORDED on the tape (each node's VJP replayed through its
    saved primal fn via jax.vjp — rematerialized), so the returned grads are
    differentiable again (double/higher-order grad)."""
    _lazy_flush()
    outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    ordered = _collect([o._node for o in outs])
    if create_graph:
        return _grad_create_graph(outs, ins, grad_outputs, allow_unused,
                                  ordered)

    cot: dict = {}
    for i, o in enumerate(outs):
        g = None
        if grad_outputs is not None and grad_outputs[i] is not None:
            g = getattr(grad_outputs[i], "_value", grad_outputs[i])
        else:
            g = jnp.ones_like(o._value)
        _accumulate(cot, o, g)

    results = [None] * len(ins)
    with no_grad():
        for node in ordered:
            out_cots, any_live = [], False
            for t in node.outputs:
                c = cot.get(id(t))
                if c is None:
                    c = jnp.zeros_like(t._value)
                else:
                    any_live = True
                out_cots.append(_dense_cot(c))
            if not any_live:
                continue
            in_cots = node.vjp_fn(tuple(out_cots) if len(out_cots) > 1 else out_cots[0])
            for t, c in zip(node.inputs, in_cots):
                _accumulate(cot, t, c)

    for i, t in enumerate(ins):
        c = cot.get(id(t))
        if c is None and not allow_unused:
            raise RuntimeError(f"input {i} unused in graph (allow_unused=False)")
        results[i] = c
    return results


def _grad_create_graph(outs, ins, grad_outputs, allow_unused, ordered):
    """Differentiable backward: cotangents are Tensors, every VJP step is a
    recorded op (remat through node.fn)."""
    from .tensor import Tensor
    from ..ops._dispatch import run_op

    cot: dict = {}  # id(tensor) -> Tensor cotangent

    def _acc(t, c):
        prev = cot.get(id(t))
        cot[id(t)] = c if prev is None else prev + c

    for i, o in enumerate(outs):
        if grad_outputs is not None and grad_outputs[i] is not None:
            g = grad_outputs[i]
            g = g if isinstance(g, Tensor) else Tensor(jnp.asarray(g))
        else:
            g = Tensor(jnp.ones_like(o._value))
        _acc(o, g)

    for node in ordered:
        out_cots, any_live = [], False
        for t in node.outputs:
            c = cot.get(id(t))
            if c is None:
                c = Tensor(jnp.zeros_like(t._value))
            else:
                any_live = True
            out_cots.append(_dense_cot(c))
        if not any_live:
            continue
        if node.fn is None:
            raise NotImplementedError(
                f"double grad through '{node.name}': no primal fn recorded "
                "(PyLayer/custom node) — wrap it in a differentiable op")
        n_in, n_out, fn = len(node.inputs), len(node.outputs), node.fn

        def vjp_replay(*arrs, _fn=fn, _n=n_in, _nout=n_out):
            primals, cots = arrs[:_n], arrs[_n:]
            _, vjp = jax.vjp(_fn, *primals)
            res = vjp(tuple(cots) if _nout > 1 else cots[0])
            return tuple(res) if len(res) > 1 else res[0]

        in_cots = run_op(vjp_replay, list(node.inputs) + out_cots,
                         node.name + "_grad")
        in_cots = in_cots if isinstance(in_cots, tuple) else (in_cots,)
        for t, c in zip(node.inputs, in_cots):
            _acc(t, c)

    results = []
    for i, t in enumerate(ins):
        c = cot.get(id(t))
        if c is None and not allow_unused:
            raise RuntimeError(f"input {i} unused in graph (allow_unused=False)")
        results.append(c)
    return results


def clear_tape():
    """Break every live node's links so the whole recorded graph is freed."""
    for n in list(_STATE.live):
        for t in n.outputs:
            t._node = None
        n.vjp_fn = None
        n.inputs = n.outputs = ()
    _STATE.live = weakref.WeakSet()


def tape_size() -> int:
    return len(_STATE.live)
