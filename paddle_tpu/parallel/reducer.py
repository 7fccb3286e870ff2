"""Bucketed backward-interleaved gradient reduction for data parallelism.

Reference parity: the dygraph `Reducer` (`imperative/reducer.cc`, PAPER.md
§1 row 6): group gradients into size-capped buckets
(`FLAGS_dp_bucket_mb`, reference DataParallel comm_buffer_size) and issue
one fused allreduce per bucket AS ITS GRADS BECOME READY during the
backward, so communication overlaps the remaining backward compute instead
of serializing after it.

TPU-native version: there is no eager hook stream — the whole step is one
traced program — so "as grads become ready" is expressed STRUCTURALLY:
buckets are ordered by reverse parameter order (the backward produces the
last layer's grads first), and each bucket's collective depends ONLY on its
own members' grads. XLA's latency-hiding scheduler can therefore start
bucket k's reduce while the grads of buckets k+1.. are still being
computed — the compiler plays the role of the reference's overlapping comm
stream. One end-of-step reduction over the whole tree (a single concat +
psum) would instead serialize: nothing can start until the LAST grad exists.

Used by `SPMDTrainStep(grad_reduction="bucketed")`, which runs the step
inside shard_map over the dp axis with explicit per-bucket collectives —
visible to `collective_signature()` / tpu-lint collective-order
verification, unlike GSPMD-inserted reductions.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np
import jax.numpy as jnp
from jax import lax

from ..core import flags as _flags
from .collective import _record

__all__ = ["Reducer"]


def _nbytes(shape, dtype) -> int:
    return int(np.prod(shape)) * np.dtype(dtype).itemsize if shape \
        else np.dtype(dtype).itemsize


class Reducer:
    """Size-capped gradient buckets over a parameter list, reduced one
    collective per bucket in backward (reverse-parameter) order.

    `params` supplies shape/dtype metadata only (Parameter/Tensor or bare
    arrays). Buckets never mix dtypes (a concat must be homogeneous; the
    reference buckets by dtype too).
    """

    def __init__(self, params: Sequence, axis: str = "dp",
                 bucket_bytes: Optional[int] = None, mean: bool = True):
        self.axis = axis
        self.mean = mean
        if bucket_bytes is None:
            bucket_bytes = int(_flags.flag("dp_bucket_mb")) << 20
        self.bucket_bytes = max(1, int(bucket_bytes))
        shapes = [tuple(getattr(p, "shape", np.shape(p))) for p in params]
        dtypes = [np.dtype(str(getattr(p, "dtype", np.asarray(p).dtype)))
                  for p in params]
        self._shapes, self._dtypes = shapes, dtypes
        self._buckets = self._build(shapes, dtypes)

    def _build(self, shapes, dtypes) -> List[List[int]]:
        buckets: List[List[int]] = []
        cur: List[int] = []
        cur_bytes = 0
        cur_dtype = None
        # reverse order = backward production order: the last parameters'
        # grads exist first, so their bucket's collective can issue while
        # earlier layers' grads are still being computed
        for i in reversed(range(len(shapes))):
            nb = _nbytes(shapes[i], dtypes[i])
            if cur and (dtypes[i] != cur_dtype
                        or cur_bytes + nb > self.bucket_bytes):
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nb
            cur_dtype = dtypes[i]
        if cur:
            buckets.append(cur)
        return buckets

    # ---- introspection (tests / docs) ----
    def bucket_layout(self) -> List[List[int]]:
        """Original-order parameter indices per bucket, in issue order."""
        return [list(b) for b in self._buckets]

    def bucket_sizes(self) -> List[int]:
        return [sum(_nbytes(self._shapes[i], self._dtypes[i]) for i in b)
                for b in self._buckets]

    # ---- the traced reduction ----
    def reduce(self, grads: Sequence) -> List:
        """Reduce a grad list (aligned with the constructor's params) across
        `self.axis`: one flattened-concat psum per bucket, buckets issued in
        backward order. Must run inside a shard_map region binding the axis
        (SPMDTrainStep's bucketed mode); mean=True averages over the axis.
        Returns the reduced grads in ORIGINAL parameter order."""
        n = lax.axis_size(self.axis)
        scale = 1.0 / n if self.mean else None
        out: List = [None] * len(grads)
        for bucket in self._buckets:
            if len(bucket) == 1:
                i = bucket[0]
                _record("c_allreduce_bucket", grads[i])
                red = lax.psum(grads[i], self.axis)
                out[i] = red * jnp.asarray(scale, red.dtype) if scale else red
                continue
            flat = jnp.concatenate([jnp.ravel(grads[i]) for i in bucket])
            _record("c_allreduce_bucket", flat)
            red = lax.psum(flat, self.axis)
            if scale:
                red = red * jnp.asarray(scale, red.dtype)
            off = 0
            for i in bucket:
                size = int(np.prod(self._shapes[i])) if self._shapes[i] else 1
                out[i] = red[off:off + size].reshape(self._shapes[i])
                off += size
        return out
