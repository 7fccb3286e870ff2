"""Tensor-parallel (megatron-style) layers — GSPMD sharding annotations.

Reference parity: `python/paddle/distributed/fleet/meta_parallel/
parallel_layers/mp_layers.py:30,97,170,249` (VocabParallelEmbedding,
ColumnParallelLinear, RowParallelLinear, ParallelCrossEntropy).

TPU-native design: instead of manual `_c_identity/matmul/_mp_allreduce`
(collective.py:793-927 in the reference), each layer annotates its weight
with a PartitionSpec over the 'mp' mesh axis and constrains its activations;
XLA GSPMD inserts the all-reduce/all-gather on ICI. The same layers also
work inside `shard_map` regions (manual-collective regime) — the forward
detects a bound 'mp' axis and emits explicit lax collectives, which is what
the pipeline engine uses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.layers import Layer
from ..ops._dispatch import ensure_tensor, run_op
from .collective import _in_spmd
from .topology import get_mesh


def _constrain(arr, *spec):
    """Apply a sharding constraint when tracing under a mesh (GSPMD
    regime). A constraint the mesh cannot take (an axis it lacks, a rank
    the spec does not fit) raises: swallowed, the layer would run
    replicated and say nothing."""
    mesh = get_mesh()
    if mesh is None or not isinstance(arr, jax.core.Tracer):
        return arr
    return lax.with_sharding_constraint(arr, NamedSharding(mesh, P(*spec)))


class ColumnParallelLinear(Layer):
    """Weight [in, out] sharded on out ('mp'); output stays sharded unless
    gather_output."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 gather_output=True, fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.gather_output = gather_output
        self.weight = self.create_parameter([in_features, out_features], attr=weight_attr,
                                            default_initializer=I.XavierNormal())
        self.weight.dist_attr = (None, "mp")
        self.bias = self.create_parameter([out_features], is_bias=True) if has_bias else None
        if self.bias is not None:
            self.bias.dist_attr = ("mp",)

    def forward(self, x):
        if _in_spmd("mp"):  # manual regime: local shard matmul
            out = F.linear(x, self.weight, self.bias)
            if self.gather_output:
                out = run_op(lambda a: lax.all_gather(a, "mp", axis=a.ndim - 1, tiled=True),
                             [out], "c_concat")
            return out
        out = F.linear(x, self.weight, self.bias)
        out._value = _constrain(out._value, *([None] * (out.ndim - 1) + ["mp"]))
        if self.gather_output:
            out._value = _constrain(out._value, *([None] * out.ndim))
        return out


class RowParallelLinear(Layer):
    """Weight [in, out] sharded on in ('mp'); input expected sharded on last dim."""

    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 input_is_parallel=False, fuse_matmul_bias=False, mp_group=None,
                 name=None):
        super().__init__()
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter([in_features, out_features], attr=weight_attr,
                                            default_initializer=I.XavierNormal())
        self.weight.dist_attr = ("mp", None)
        self.bias = self.create_parameter([out_features], is_bias=True) if has_bias else None

    def forward(self, x):
        x = ensure_tensor(x)
        if _in_spmd("mp"):  # manual regime: partial matmul + psum
            if not self.input_is_parallel:
                n = lax.axis_size("mp")
                idx = lax.axis_index("mp")

                def split_f(a):
                    sz = a.shape[-1] // n
                    return lax.dynamic_slice_in_dim(a, idx * sz, sz, axis=a.ndim - 1)

                x = run_op(split_f, [x], "c_split")
            partial = F.linear(x, self.weight)
            out = run_op(lambda a: lax.psum(a, "mp"), [partial], "mp_allreduce")
            if self.bias is not None:
                out = out + self.bias
            return out
        xin = x
        xin._value = _constrain(xin._value, *([None] * (x.ndim - 1) + ["mp"]))
        out = F.linear(xin, self.weight, self.bias)
        out._value = _constrain(out._value, *([None] * out.ndim))
        return out


class VocabParallelEmbedding(Layer):
    """Embedding table sharded on the vocab dim over 'mp'."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None, mp_group=None,
                 name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.weight = self.create_parameter([num_embeddings, embedding_dim],
                                            attr=weight_attr,
                                            default_initializer=I.Normal(0.0, 0.02))
        self.weight.dist_attr = ("mp", None)

    def forward(self, x):
        x = ensure_tensor(x)
        if _in_spmd("mp"):  # manual regime: mask out-of-shard ids, psum partial lookups
            n = lax.axis_size("mp")
            idx = lax.axis_index("mp")
            per = self.num_embeddings // n

            def f(w):
                ids = x._value.astype(jnp.int32)
                local = ids - idx * per
                in_shard = (local >= 0) & (local < per)
                safe = jnp.where(in_shard, local, 0)
                emb = jnp.take(w, safe, axis=0)
                emb = jnp.where(in_shard[..., None], emb, jnp.zeros((), emb.dtype))
                return lax.psum(emb, "mp")

            return run_op(f, [self.weight], "c_embedding")
        out = F.embedding(x, self.weight)
        out._value = _constrain(out._value, *([None] * out.ndim))
        return out


class ParallelCrossEntropy(Layer):
    """Vocab-parallel softmax CE (`c_softmax_with_cross_entropy_op.cu:1`).

    GSPMD regime: plain CE over logits sharded on vocab — XLA partitions the
    log-softmax reduction. Manual regime: explicit max/sum psums over 'mp'.
    """

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        input, label = ensure_tensor(input), ensure_tensor(label)
        if _in_spmd("mp"):
            n = lax.axis_size("mp")
            idx = lax.axis_index("mp")

            def f(logits):
                ids = label._value.astype(jnp.int32)
                if ids.ndim == logits.ndim:
                    ids = jnp.squeeze(ids, -1)
                vmax = lax.pmax(jnp.max(logits, -1, keepdims=True), "mp")
                ex = jnp.exp(logits - vmax)
                denom = lax.psum(jnp.sum(ex, -1, keepdims=True), "mp")
                per = logits.shape[-1]
                local = ids - idx * per
                in_shard = (local >= 0) & (local < per)
                safe = jnp.where(in_shard, local, 0)
                picked = jnp.take_along_axis(logits - vmax, safe[..., None], axis=-1)
                picked = jnp.where(in_shard[..., None], picked, jnp.zeros((), logits.dtype))
                picked = lax.psum(picked, "mp")
                return (jnp.log(denom) - picked)[..., 0][..., None]

            return run_op(f, [input], "c_softmax_with_cross_entropy")
        return F.softmax_with_cross_entropy(input, label)
