"""Normalization functionals.

Reference parity: `python/paddle/nn/functional/norm.py` (batch_norm,
layer_norm, instance_norm, group_norm, local_response_norm). Running-stat
updates happen OUTSIDE the tape (buffers), matching fluid's in-place
mean/variance variables.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...core.autograd import no_grad
from ...ops._dispatch import ensure_tensor, run_op


def batch_norm(x, running_mean, running_var, weight=None, bias=None, training=False,
               momentum=0.9, epsilon=1e-05, data_format="NCHW", use_global_stats=None,
               name=None):
    x = ensure_tensor(x)
    channel_last = not data_format.upper().startswith("NC")
    c_axis = x.ndim - 1 if channel_last else 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    use_batch = training and not use_global_stats
    if use_batch:
        with no_grad():
            # the tracer check gates the COMPUTATION, not just the buffer
            # write: under trace the update is discarded anyway, and
            # computing bm/bv first left 3 dead eqns per BN layer in every
            # traced training program (found by tpu-lint's dead-op rule)
            if running_mean is not None and not isinstance(
                    x._value, jax.core.Tracer):
                bm = jnp.mean(x._value, axis=reduce_axes)
                bv = jnp.var(x._value, axis=reduce_axes)
                running_mean._value = (momentum * running_mean._value
                                       + (1 - momentum) * bm.astype(running_mean._value.dtype))
                running_var._value = (momentum * running_var._value
                                      + (1 - momentum) * bv.astype(running_var._value.dtype))

        def f(a, *rest):
            m = jnp.mean(a, axis=reduce_axes, keepdims=True)
            v = jnp.var(a, axis=reduce_axes, keepdims=True)
            out = (a - m) * jax.lax.rsqrt(v + epsilon)
            return _affine(out, rest)
    else:
        rm = running_mean._value.reshape(bshape)
        rv = running_var._value.reshape(bshape)

        def f(a, *rest):
            out = (a - rm.astype(a.dtype)) * jax.lax.rsqrt(rv.astype(a.dtype) + epsilon)
            return _affine(out, rest)

    def _affine(out, rest):
        if len(rest) == 2:
            w, b = rest
            return out * w.reshape(bshape) + b.reshape(bshape)
        if len(rest) == 1:
            return out * rest[0].reshape(bshape)
        return out

    ins = [x]
    if weight is not None:
        ins.append(ensure_tensor(weight))
    if bias is not None:
        ins.append(ensure_tensor(bias))
    return run_op(f, ins, "batch_norm")


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05, name=None):
    x = ensure_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    nd = len(normalized_shape)
    axes = tuple(range(x.ndim - nd, x.ndim))

    def f(a, *rest):
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + epsilon)
        if len(rest) == 2:
            return out * rest[0] + rest[1]
        if len(rest) == 1:
            return out * rest[0]
        return out

    ins = [x]
    if weight is not None:
        ins.append(ensure_tensor(weight))
    if bias is not None:
        ins.append(ensure_tensor(bias))
    return run_op(f, ins, "layer_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-05, data_format="NCHW",
                  name=None):
    x = ensure_tensor(x)
    channel_last = not data_format.upper().startswith("NC")
    c_axis = x.ndim - 1 if channel_last else 1
    spatial = tuple(i for i in range(2, x.ndim)) if not channel_last else \
        tuple(i for i in range(1, x.ndim - 1))
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    def f(a, *rest):
        m = jnp.mean(a, axis=spatial, keepdims=True)
        v = jnp.var(a, axis=spatial, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + eps)
        if len(rest) == 2:
            return out * rest[0].reshape(bshape) + rest[1].reshape(bshape)
        if len(rest) == 1:
            return out * rest[0].reshape(bshape)
        return out

    ins = [x]
    if weight is not None:
        ins.append(ensure_tensor(weight))
    if bias is not None:
        ins.append(ensure_tensor(bias))
    return run_op(f, ins, "instance_norm")


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = ensure_tensor(x)
    channel_last = not data_format.upper().startswith("NC")
    c_axis = x.ndim - 1 if channel_last else 1
    c = x.shape[c_axis]
    bshape = [1] * x.ndim
    bshape[c_axis] = c

    def f(a, *rest):
        if channel_last:
            perm = [0, a.ndim - 1] + list(range(1, a.ndim - 1))
            a_t = jnp.transpose(a, perm)
        else:
            a_t = a
        n = a_t.shape[0]
        grouped = a_t.reshape((n, num_groups, c // num_groups) + a_t.shape[2:])
        axes = tuple(range(2, grouped.ndim))
        m = jnp.mean(grouped, axis=axes, keepdims=True)
        v = jnp.var(grouped, axis=axes, keepdims=True)
        out = ((grouped - m) * jax.lax.rsqrt(v + epsilon)).reshape(a_t.shape)
        if channel_last:
            inv = [0] + list(range(2, a.ndim)) + [1]
            out = jnp.transpose(out, inv)
        if len(rest) == 2:
            return out * rest[0].reshape(bshape) + rest[1].reshape(bshape)
        if len(rest) == 1:
            return out * rest[0].reshape(bshape)
        return out

    ins = [x]
    if weight is not None:
        ins.append(ensure_tensor(weight))
    if bias is not None:
        ins.append(ensure_tensor(bias))
    return run_op(f, ins, "group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW",
                        name=None):
    x = ensure_tensor(x)

    def f(a):
        sq = jnp.square(a)
        half = size // 2
        c = a.shape[1]
        padded = jnp.pad(sq, [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (a.ndim - 2))
        window = sum(padded[:, i:i + c] for i in range(size))
        return a / jnp.power(k + alpha * window / size, beta)

    return run_op(f, [x], "local_response_norm")


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm (TPU-era addition; used by modern LLM blocks)."""
    x = ensure_tensor(x)

    def f(a, *rest):
        # statistics in float32 whatever the input (a bfloat16 model's
        # norm; a no-op for float32), the result in the input's dtype
        a32 = a.astype(jnp.promote_types(a.dtype, jnp.float32))
        ms = jnp.mean(jnp.square(a32), axis=-1, keepdims=True)
        out = (a32 * jax.lax.rsqrt(ms + epsilon)).astype(a.dtype)
        return out * rest[0] if rest else out

    ins = [x] + ([ensure_tensor(weight)] if weight is not None else [])
    return run_op(f, ins, "rms_norm")
