"""@to_static: capture a Layer/function into ONE compiled XLA program.

Reference parity: `python/paddle/fluid/dygraph/jit.py:163` (declarative) +
`dygraph_to_static/program_translator.py:775`. Like the reference, the
captured function is first AST-rewritten (paddle_tpu.jit.dy2static — the
ifelse/loop transformer equivalents) so Python `if`/`while`/`for` over
tensors lower to `lax.cond`/`lax.while_loop` automatically; explicit
`paddle_tpu.static.nn.cond/while_loop` remain available for full control.

Differentiability: the whole compiled program is recorded as ONE tape node
(vjp through `jax.jit`), so `loss.backward()` works across the static
boundary exactly like `run_program_op`'s grad in the reference
(`operators/run_program_op.cc`).
"""
from __future__ import annotations

import functools
import weakref

import jax

from .. import analysis as _analysis
from .. import monitor as _monitor
from ..core import compile_cache as _cc
from ..core import executable as _exe
from ..core import random as rnd
from ..core.tensor import Tensor
from ..ops import _dispatch as _dsp
from ..ops._dispatch import run_op
from .functional import functional_call, split_state
from .input_spec import InputSpec  # noqa: F401  (re-export)


class StaticFunction:
    def __init__(self, function, layer=None, input_spec=None, name=None,
                 donate_inputs=None):
        # the compiled program's name (`jit_<name>` in an HLO dump and in a
        # profiler trace's `XLA Modules`): a reader picks a program by it
        self._name = name or (type(layer).__name__ if layer is not None
                              else getattr(function, "__name__", "to_static"))
        try:
            from .dy2static import ast_transform
            self._function = ast_transform(function)
        except Exception:  # source unavailable / exotic callable: trace as-is
            self._function = function
        self._layer = layer
        self._input_spec = input_spec
        # which positional inputs the caller gives away to the program
        # (indices or a slice of them): only the owner of a buffer may
        # say so, hence an argument of the one call that wraps, not a flag
        self._donate_inputs = donate_inputs
        self._jit_cache = {}
        # executable substrate: only a NOVEL signature is a recompile —
        # alternating between two known shapes (e.g. the serving engine
        # cycling batch buckets) replays jax.jit's cache and must not
        # count as retraces. The ledger also caches persistent-cache
        # deserialized executables per signature, and its `current_sig`
        # is the signature the published Program was built for.
        self._ledger = _exe.ExecutableLedger("to_static")
        try:
            functools.update_wrapper(self, function)
        except Exception:
            pass

    @property
    def layer(self):
        return self._layer

    def release(self) -> None:
        """Drop every cached executable. The `pure` closures in
        `_jit_cache` reference `self` through jax's C-level function
        wrappers, which the cycle collector cannot traverse — an owner
        that wants `self._layer`'s weights freed must break the cycle
        explicitly (e.g. a serving engine on `stop()`)."""
        self._jit_cache.clear()
        self._ledger.clear()

    def _get_pure(self, training, pnames, bnames, static_kwargs):
        key = ("pure", training, tuple(pnames), tuple(bnames),
               tuple(sorted(static_kwargs.items())))
        pure = self._jit_cache.get(key)
        if pure is None:
            # Capture self WEAKLY: jax's C-level jit machinery keeps a
            # reference to `pure` in a process-global cache, so a strong
            # `layer`/`func` cell here would pin the whole model long
            # after the StaticFunction is dropped. The weakref is always
            # live during a call — the caller IS the StaticFunction.
            self_ref = weakref.ref(self)
            kw = dict(static_kwargs)

            def pure(param_arrays, buffer_arrays, rng_key, input_arrays):
                sf = self_ref()
                if sf is None:  # pragma: no cover - defensive
                    raise RuntimeError("StaticFunction was released")
                layer, func = sf._layer, sf._function
                rnd.push_trace_key(rng_key)
                swapped = layer is not None and isinstance(
                    layer.__dict__.get("forward"), StaticFunction)
                if swapped:  # un-hook ourselves so tracing hits the original forward
                    saved_fwd = layer.__dict__["forward"]
                    layer.__dict__["forward"] = func
                try:
                    if layer is not None:
                        return functional_call(layer, pnames, param_arrays, bnames,
                                               buffer_arrays, *input_arrays, **kw)
                    wrapped = [Tensor(a) for a in input_arrays]
                    out = func(*wrapped, **kw)
                    return jax.tree_util.tree_map(
                        lambda t: t._value if isinstance(t, Tensor) else t, out,
                        is_leaf=lambda x: isinstance(x, Tensor))
                finally:
                    rnd.pop_trace_key()
                    if swapped:
                        layer.__dict__["forward"] = saved_fwd

            self._jit_cache[key] = pure
        return pure

    def _donated(self, n_inputs):
        """Indices of the positional inputs that are donated, ascending."""
        d = self._donate_inputs
        if d is None:
            return ()
        if isinstance(d, slice):
            return tuple(range(n_inputs)[d])
        return tuple(sorted({range(n_inputs)[int(i)] for i in d}))

    @staticmethod
    def _call_args(params, buffers, karg, inputs, donated=()):
        """The jitted program's arguments. Donated inputs travel as an
        argument of their own (the only unit `donate_argnums` knows);
        with none, the four arguments every capture has always had."""
        if not donated:
            return params, buffers, karg, inputs
        given = set(donated)
        return (params, buffers, karg,
                [a for i, a in enumerate(inputs) if i not in given],
                [inputs[i] for i in donated])

    def _get_jitted(self, training, pnames, bnames, static_kwargs,
                    raw_key=False, donated=()):
        key = ("jit", training, tuple(pnames), tuple(bnames),
               tuple(sorted(static_kwargs.items())), raw_key, donated)
        jitted = self._jit_cache.get(key)
        if jitted is None:
            if _monitor._ENABLED:
                _monitor.count("jit.to_static.cache_miss")
            pure = self._get_pure(training, pnames, bnames, static_kwargs)
            if raw_key:
                # persistent-cache mode: jax.export cannot serialize
                # typed PRNG key avals, so the exported program takes RAW
                # key data and wraps at the boundary (same adapter as
                # TrainStep._build)
                base = pure

                def pure(param_arrays, buffer_arrays, key_data,
                         input_arrays):
                    return base(param_arrays, buffer_arrays,
                                jax.random.wrap_key_data(key_data),
                                input_arrays)

            if donated:
                whole = pure

                def pure(param_arrays, buffer_arrays, rng_key, kept_arrays,
                         donated_arrays):
                    inputs = list(kept_arrays)
                    for i, a in zip(donated, donated_arrays):  # ascending
                        inputs.insert(i, a)
                    return whole(param_arrays, buffer_arrays, rng_key, inputs)

            pure.__name__ = pure.__qualname__ = self._name
            jitted = jax.jit(pure, donate_argnums=(4,) if donated else ())
            self._jit_cache[key] = jitted
        return jitted

    def _get_fwd_vjp(self, training, pnames, bnames, static_kwargs, n_p):
        """jit'd (outs, vjp) of the pure forward with the rng key and
        buffer arrays as ARGUMENTS. The earlier design closed the per-call
        rng key into the run_op fn, which made every call miss the global
        vjp cache (`_fn_key` correctly refuses to value-key arrays) and
        dropped backward to an unjitted transposed-jaxpr walk — measured
        78 ms/step LeNet vs 44 eager. With key/buffers as traced args the
        whole fwd+vjp pair is ONE cached executable each way."""
        key = ("fwd_vjp", training, tuple(pnames), tuple(bnames),
               tuple(sorted(static_kwargs.items())), n_p)
        f = self._jit_cache.get(key)
        if f is None:
            if _monitor._ENABLED:
                _monitor.count("jit.to_static.cache_miss")
            pure = self._get_pure(training, pnames, bnames, static_kwargs)

            def fwd_vjp(diff, barrs, rkey):
                def g(*d):
                    return pure(list(d[:n_p]), barrs, rkey, list(d[n_p:]))
                return jax.vjp(g, *diff)

            f = jax.jit(fwd_vjp)
            self._jit_cache[key] = f
        return f

    def __call__(self, *args, **kwargs):
        from ..core import autograd as _ag
        mon, hook = _monitor._ENABLED, _dsp._PROFILE_HOOK
        # the `static_program` profiler event and `jit.to_static.dur` are
        # the call span's interval, so it is timed for either reader
        call_span = (_monitor.Span("jit.to_static.call")
                     if mon or hook is not None else _monitor._NULL_SPAN)
        with _monitor.span("jit.to_static.prepare"):
            layer = self._layer
            input_tensors = [a if isinstance(a, Tensor) else Tensor(a)
                             for a in args]
            if any(isinstance(v, Tensor) for v in kwargs.values()):
                raise ValueError(
                    "to_static: pass Tensor arguments positionally")
            try:
                hash(tuple(sorted(kwargs.items())))
                static_kwargs = kwargs
            except TypeError:
                raise ValueError(
                    "to_static kwargs must be hashable (static) values")

            if layer is not None:
                trainable, frozen = split_state(layer)
                pnames, bnames = list(trainable), list(frozen)
                ptensors = [trainable[n] for n in pnames]
                barrs = [frozen[n]._value for n in bnames]
                # composite mode flag: sublayer train/eval toggles re-key the
                # trace caches (a capture traced with dropout active must not
                # replay after model.dropout.eval())
                training = tuple(l.training for l in
                                 layer.sublayers(include_self=True))
            else:
                pnames, bnames, ptensors, barrs = [], [], [], []
                training = True

            key = rnd.default_generator().next_key()
            n_p = len(ptensors)
            donated = self._donated(len(input_tensors))
            diff_inputs = ptensors + input_tensors
            arrays = [t._value for t in diff_inputs]
            # persistent-cache mode rides the raw-key-data program variant
            raw = _cc.enabled()
            karg = jax.random.key_data(key) if raw else key

            # publish this capture as the default program (ProgramDesc role):
            # introspection/pruning lower lazily from the same traced callable.
            # Rebuilt only when the input signature changes (zero steady-state
            # cost on the hot path).
            sig = tuple((t._value.shape, str(t._value.dtype))
                        for t in diff_inputs)
            # a NOVEL signature on a to_static capture = retrace: the whole
            # program recompiles for the new shapes/dtypes. A previously-seen
            # signature hits jax.jit's executable cache and is free — only
            # the Program rebuild below runs.
            novel = self._ledger.note(sig, detail=[f"{s}:{d}" for s, d in sig])
            if self._ledger.current_sig != sig:
                if _analysis._ENABLED:
                    # trace-time tpu-lint: novel-signature block only, so the
                    # steady-state call path never reaches this check
                    _analysis.lint_traced(self._function, "to_static")
                jitted = self._get_jitted(training, pnames, bnames,
                                          static_kwargs, raw, donated)

                def fn(*arrs, _jit=jitted, _b=list(barrs), _k=karg, _np=n_p,
                       _d=donated):
                    return _jit(*StaticFunction._call_args(
                        list(arrs[:_np]), _b, _k, list(arrs[_np:]), _d))

                from ..static.program import Program, _set_default_program
                specs = [jax.ShapeDtypeStruct(t._value.shape, t._value.dtype)
                         for t in diff_inputs]
                self._last_program = Program(fn, specs, name=getattr(
                    self._function, "__name__", "main"))
                self._ledger.current_sig = sig
                _set_default_program(self._last_program)

            record = (_ag.is_grad_enabled()
                      and any(not t.stop_gradient for t in diff_inputs)
                      and not any(isinstance(a, jax.core.Tracer)
                                  for a in arrays))
        if not record:
            jitted = self._get_jitted(training, pnames, bnames,
                                      static_kwargs, raw, donated)
            call_args = self._call_args(arrays[:n_p], barrs, karg,
                                        arrays[n_p:], donated)
            csig = (sig, training, tuple(sorted(static_kwargs.items())), raw)
            with _exe.booking("to_static") as bk:
                call = self._ledger.get(csig)
                if call is None:
                    call = jitted
                    if raw:
                        call, source = _exe.acquire(
                            "to_static", jitted, call_args,
                            donate=(4,) if donated else (),
                            label=getattr(self._function, "__name__",
                                          "to_static"))
                        self._ledger.put(csig, call)
                        if novel and source == "fresh":
                            bk.compiled()
                    elif novel:
                        bk.compiled()
                elif novel:
                    bk.compiled()
                with call_span:
                    out = call(*call_args)
        else:
            if donated:
                raise RuntimeError(
                    "to_static: donate_inputs gives buffers away, so the "
                    "call cannot be recorded for backward; call it under "
                    "no_grad()")
            with _exe.booking("to_static") as bk:
                if novel:
                    bk.compiled()
                fwd_vjp = self._get_fwd_vjp(training, pnames, bnames,
                                            static_kwargs, n_p)
                with call_span:
                    out, raw_vjp = fwd_vjp(arrays, barrs, key)
        # arbitrary output pytrees (e.g. RNN layers return (out, (h, c))):
        # the tape stores flat leaf tensors; the vjp wrapper unflattens the
        # flat cotangents back to the traced structure
        leaves, treedef = jax.tree_util.tree_flatten(out)
        # tape convention: bare cotangent for single output, flat tuple for
        # >1. A 1-TUPLE output is NOT native (the vjp expects (c,), the
        # tape would pass a bare array) — keep its treedef for unflatten.
        flat_native = (treedef == jax.tree_util.tree_structure(0)
                       or (len(leaves) > 1 and treedef ==
                           jax.tree_util.tree_structure(tuple(leaves))))
        outs_list = [Tensor(o) for o in leaves]
        from ..core import flags as _flags
        if _flags.flag("check_nan_inf") and not any(
                isinstance(o, jax.core.Tracer) for o in leaves):
            _dsp._check_nan_inf("static_program", tuple(leaves))
        if hook is not None:
            hook("static_program", call_span.wall,
                 call_span.wall + call_span.dur)
        if mon:
            _monitor.count("jit.to_static.calls")
            _monitor.observe("jit.to_static.dur", call_span.dur)
        if record:
            _ag.record_node(
                _ag._JitVJP(raw_vjp,
                            treedef=None if flat_native else treedef),
                diff_inputs, outs_list, "static_program")
        return jax.tree_util.tree_unflatten(
            treedef, [t for t in outs_list])

    def program(self, *args):
        """The Program captured by the most recent call (lazy-lowered);
        with args, captures a fresh one for those input shapes."""
        if args:
            self(*args)
        prog = getattr(self, "_last_program", None)
        if prog is None:
            raise RuntimeError("call the @to_static function once (or pass "
                               "example args) to capture its program")
        return prog


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              name=None, donate_inputs=None, **kwargs):
    """Decorator/wrapper. Accepts a Layer, a Layer's bound forward, or a pure
    function of Tensors. `name` names the compiled program (`jit_<name>`);
    the default is the Layer's class or the function's own name.

    `donate_inputs` (indices or a slice of the positional tensor inputs)
    hands those inputs' buffers to the program, which may then update them
    in place and alias them to its outputs: after a call they are deleted
    and the caller goes on with what the call returned. Only the owner of
    the buffers may ask for it; such a function runs under `no_grad()`."""

    def decorate(obj):
        from ..nn.layer.layers import Layer
        if isinstance(obj, Layer):
            static = StaticFunction(obj.forward, layer=obj,
                                    input_spec=input_spec, name=name,
                                    donate_inputs=donate_inputs)
            obj.forward = static
            return obj
        if hasattr(obj, "__self__") and isinstance(obj.__self__, Layer):
            return StaticFunction(obj.__func__.__get__(obj.__self__),
                                  layer=obj.__self__, input_spec=input_spec,
                                  name=name, donate_inputs=donate_inputs)
        return StaticFunction(obj, layer=None, input_spec=input_spec,
                              name=name, donate_inputs=donate_inputs)

    if function is not None:
        return decorate(function)
    return decorate


declarative = to_static


def not_to_static(fn):
    fn._not_to_static = True
    return fn
