"""nn.Layer — module base class.

Reference parity: `python/paddle/fluid/dygraph/layers.py:82` (Layer) with
`__call__` at `:916` (pre-hooks → forward → post-hooks), parameter and
sublayer registries, buffers, state_dict/set_state_dict, train/eval.
"""
from __future__ import annotations

import collections
import threading
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from ...core.dtype import convert_dtype, get_default_dtype
from ...core.tensor import Parameter, Tensor

__all__ = ["Layer"]


class _HookRemoveHelper:
    def __init__(self, hooks, key):
        self._hooks, self._key = hooks, key

    def remove(self):
        self._hooks.pop(self._key, None)


class _CallDepth(threading.local):
    def __init__(self):
        self.depth = 0


_LAYER_CALL_DEPTH = _CallDepth()


class Layer:
    def __init__(self, name_scope=None, dtype=None):
        self.training = True
        # None: `paddle.get_default_dtype()` at construction, as the
        # reference's layers take it from their helper
        self._dtype = convert_dtype(dtype) or get_default_dtype()
        self._parameters: Dict[str, Parameter] = collections.OrderedDict()
        self._sub_layers: Dict[str, "Layer"] = collections.OrderedDict()
        self._buffers: Dict[str, Tensor] = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ---- attribute routing ----
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter) and params is not None:
            layers.pop(name, None)
            buffers.pop(name, None)
            self.__dict__.pop(name, None)
            params[name] = value
        elif isinstance(value, Layer) and layers is not None:
            params.pop(name, None)
            buffers.pop(name, None)
            self.__dict__.pop(name, None)
            layers[name] = value
        elif buffers is not None and name in buffers:
            buffers[name] = value
        else:
            if params is not None and name in params and value is None:
                params.pop(name)
            if layers is not None and name in layers and value is None:
                layers.pop(name)
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    # ---- registration ----
    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True):
        self.__dict__.pop(name, None)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from .. import initializer as I
        dt = convert_dtype(dtype) or self._dtype
        init = default_initializer
        if attr is not None and getattr(attr, "initializer", None) is not None:
            init = attr.initializer
        else:
            # set_global_initializer overrides LAYER-BUILTIN defaults (the
            # reference contract: user-specified ParamAttr initializers
            # still win, the layers' own defaults do not)
            gw, gb = I._GLOBAL_INITIALIZER
            g = gb if is_bias else gw
            if g is not None:
                init = g
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        p = Parameter(jnp.zeros([int(s) for s in shape], dtype=dt))
        init(p)
        if attr is not None:
            lr = getattr(attr, "learning_rate", None)
            if lr is not None:
                p.optimize_attr["learning_rate"] = lr
            if getattr(attr, "trainable", True) is False:
                p.stop_gradient = True
                p.trainable = False
        return p

    # ---- traversal ----
    def named_parameters(self, prefix="", include_sublayers=True) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        yield from self._named_parameters_impl(prefix, include_sublayers, seen)

    def _named_parameters_impl(self, prefix, include_sublayers, seen):
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer._named_parameters_impl(sub_prefix, True, seen)

    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(sub_prefix)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield sub_prefix, layer
            yield from layer.named_sublayers(sub_prefix)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return iter((n, l) for n, l in self._sub_layers.items() if l is not None)

    def apply(self, fn: Callable):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    def full_name(self):
        return self._name_scope

    # ---- modes ----
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # ---- hooks ----
    def register_forward_pre_hook(self, hook):
        key = len(self._forward_pre_hooks)
        self._forward_pre_hooks[key] = hook
        return _HookRemoveHelper(self._forward_pre_hooks, key)

    def register_forward_post_hook(self, hook):
        key = len(self._forward_post_hooks)
        self._forward_post_hooks[key] = hook
        return _HookRemoveHelper(self._forward_post_hooks, key)

    # ---- state dict ----
    def state_dict(self, destination=None, include_sublayers=True, use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(include_sublayers=include_sublayers):
            shortname = name.rsplit(".", 1)[-1]
            if shortname not in self._non_persistable_buffer_names:
                dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            arr = v.numpy() if hasattr(v, "numpy") else np.asarray(v)  # tpu-lint: disable=host-sync,lazy-sync (host-side state load, not a hot loop)
            tgt = own[k]
            if tuple(arr.shape) != tuple(tgt.shape):
                raise ValueError(f"shape mismatch for {k}: {arr.shape} vs {tuple(tgt.shape)}")
            tgt._value = jnp.asarray(arr, dtype=tgt._value.dtype)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = convert_dtype(dtype)
            for p in self.parameters():
                if np.issubdtype(p.dtype, np.floating):
                    p._value = p._value.astype(dt)
            for _, b in self.named_buffers():
                if np.issubdtype(b.dtype, np.floating):
                    b._value = b._value.astype(dt)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # ---- call ----
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    # step-chain capture (FLAGS_eager_auto_jit): a TOP-LEVEL layer called
    # repeatedly with the same signature is promoted to its captured
    # static program (jit.to_static machinery) — the repeated per-op tape
    # becomes ONE fwd executable + ONE vjp executable. This is the eager
    # hot loop's answer to the reference's dygraph program-desc caching
    # (imperative/tracer.cc:172): per-op host dispatches dominate eager
    # stepping, and capture removes all but one.
    _AUTOJIT_THRESHOLD = 3

    def _autojit_try(self, inputs, kwargs):
        from ...core import flags as _flags
        from ...core.tensor import Tensor as _T
        if self.__dict__.get("_autojit_off") or kwargs:
            return None
        if not _flags.flag("eager_auto_jit"):
            return None
        from ...ops import lazy as _lazy
        if _lazy._ACTIVE:
            # the lazy batching executor already collapses the step into
            # O(1) dispatches; capturing on top would fight its segment
            # accounting (and bake pending payloads into a static program)
            return None
        if _LAYER_CALL_DEPTH.depth or not inputs \
                or not all(isinstance(a, _T) for a in inputs):
            return None
        if isinstance(self.__dict__.get("forward"), object) and \
                type(self.__dict__.get("forward")).__name__ == "StaticFunction":
            return None            # explicitly to_static'd already
        import jax as _jax
        if any(isinstance(a._value, _jax.core.Tracer) for a in inputs):
            return None
        for l in self.sublayers(include_self=True):
            if l.training and l._buffers:
                # buffer mutations (BN running stats) are DISCARDED by the
                # functional capture; keep training-mode BN models eager
                return None
            if l._forward_pre_hooks or l._forward_post_hooks:
                # hooks run INSIDE the capture trace, so python side
                # effects (logging, stats) would fire once per compile
                # instead of once per call — keep hooked models eager
                return None
        # key the capture on EVERY sublayer's training flag: toggling one
        # sublayer's train/eval (e.g. model.dropout.eval()) must retrace,
        # not replay the stale program
        sig = (tuple(l.training
                     for l in self.sublayers(include_self=True)),
               tuple((tuple(a.shape), str(a.dtype), a.stop_gradient)
                     for a in inputs))
        state = self.__dict__.setdefault("_autojit_state", {})
        state[sig] = state.get(sig, 0) + 1
        if len(state) > 32:
            state.clear()
        if state[sig] < self._AUTOJIT_THRESHOLD:
            return None
        sf = self.__dict__.get("_autojit_sf")
        if sf is None:
            from ...jit.to_static import StaticFunction
            sf = StaticFunction(type(self).forward.__get__(self), layer=self)
            self.__dict__["_autojit_sf"] = sf
        return sf

    def __call__(self, *inputs, **kwargs):
        sf = self._autojit_try(inputs, kwargs)
        if sf is not None:
            try:
                return sf(*inputs, **kwargs)
            except Exception:
                # any capture failure (untraceable control flow, exotic
                # outputs) permanently reverts this layer to eager
                self.__dict__["_autojit_off"] = True
        _LAYER_CALL_DEPTH.depth += 1
        try:
            for hook in list(self._forward_pre_hooks.values()):
                result = hook(self, inputs)
                if result is not None:
                    inputs = result if isinstance(result, tuple) else (result,)
            outputs = self.forward(*inputs, **kwargs)
            for hook in list(self._forward_post_hooks.values()):
                result = hook(self, inputs, outputs)
                if result is not None:
                    outputs = result
        finally:
            _LAYER_CALL_DEPTH.depth -= 1
        return outputs

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            mod_str = repr(layer)
            mod_str = "\n  ".join(mod_str.split("\n"))
            lines.append(f"({name}): {mod_str}")
        main = self.__class__.__name__ + "(" + extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"

    def extra_repr(self):
        return ""
