"""What the pre-norm decoders with a declared cache share
(`models/brumby.py`, `models/ling.py`): parameters drawn straight into
their dtype, the SwiGLU feed-forward, the untied head on one hidden state a
row."""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as rnd
from ..core.dtype import get_default_dtype, set_default_dtype
from ..framework.param_attr import ParamAttr
from ..nn import functional as F
from ..nn import initializer as I
from ..ops._dispatch import run_op


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class _Normal(I.Initializer):
    """N(0, std) (std None: Xavier over the last two axes, sqrt(2 / (fan_in
    + fan_out)), as `nn.Linear` starts) drawn by ONE program straight into
    the parameter's dtype. The eager initializers hold up to three float32
    copies of what they draw: 9 GB for a [151936, 5120] table that is 1.6
    GB in bfloat16, beside the rest of a model that fills half the chip."""

    def __init__(self, std=None):
        self.std = std

    def _generate(self, shape, dtype):
        std = self.std or math.sqrt(2.0 / (shape[-2] + shape[-1]))
        return _draw(rnd.next_key(), tuple(shape), jnp.dtype(dtype), std)


def _linear(n_in, n_out, bias_attr=False):
    return nn.Linear(n_in, n_out, weight_attr=ParamAttr(initializer=_Normal()),
                     bias_attr=bias_attr)


@contextlib.contextmanager
def _parameters_in(dtype):
    """Layers built inside create their parameters in `dtype` (a 14B
    model built in float32 and cast would not fit beside itself)."""
    was = get_default_dtype()
    set_default_dtype(dtype)
    try:
        yield
    finally:
        set_default_dtype(was)


class SwiGLU(nn.Layer):
    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.gate_proj = _linear(hidden_size, intermediate_size)
        self.up_proj = _linear(hidden_size, intermediate_size)
        self.down_proj = _linear(intermediate_size, hidden_size)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


def _logits(norm, lm_head, h):
    """Final norm and the untied head; logits in float32."""
    return run_op(
        lambda a, w: jnp.matmul(a, w, preferred_element_type=jnp.float32),
        [norm(h), lm_head.weight], "lm_head")


def _rows_at(h, index):
    """h [B, T, hidden], index [B] or [B, P] -> h[b, index[b]] as
    [B, hidden] or [B, P, hidden]."""
    def f(a, i):
        i = i.astype(jnp.int32)
        if i.ndim == 1:
            return jnp.take_along_axis(a, i[:, None, None], axis=1)[:, 0]
        return jnp.take_along_axis(a, i[..., None], axis=1)
    return run_op(f, [h, index], "llm_last_hidden")
