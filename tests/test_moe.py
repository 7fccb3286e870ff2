"""MoE tests: gating, capacity, count-masked a2a, EP equivalence.

Technique: dense equivalence at capacity=infinity (reference
global_scatter/gather contract), plus distributed == local on the virtual
mesh (test_collective_base.py pattern, in-process)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.parallel import create_mesh
from paddle_tpu.parallel.moe import (MoELayer, global_gather, global_scatter,
                                     moe_combine, moe_dispatch, top_k_gating)


def _r(*shape):
    return np.random.rand(*shape).astype("float32")


class TestGating:
    def test_top1_full_capacity_routes_every_token(self):
        T, E = 16, 4
        logits = jnp.asarray(_r(T, E))
        dispatch, combine, aux = top_k_gating(logits, k=1, capacity=T)
        # every token lands in exactly one (expert, slot)
        np.testing.assert_allclose(np.asarray(dispatch.sum(axis=(1, 2))),
                                   np.ones(T))
        probs = jax.nn.softmax(logits, axis=-1)
        top1 = np.asarray(jnp.max(probs, axis=-1))
        np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))), top1,
                                   rtol=1e-5)
        assert float(aux) > 0

    def test_top2_normalized_weights(self):
        T, E = 8, 4
        logits = jnp.asarray(_r(T, E))
        dispatch, combine, aux = top_k_gating(logits, k=2, capacity=T)
        np.testing.assert_allclose(np.asarray(dispatch.sum(axis=(1, 2))),
                                   2 * np.ones(T))
        # normalized: combine weights sum to 1 per token
        np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))),
                                   np.ones(T), rtol=1e-5)

    def test_capacity_drops_overflow(self):
        T, E, C = 8, 2, 2
        # all tokens prefer expert 0
        logits = jnp.asarray(np.tile([5.0, 0.0], (T, 1)).astype("float32"))
        dispatch, combine, aux = top_k_gating(logits, k=1, capacity=C)
        assert float(dispatch[:, 0].sum()) == C  # only C kept
        assert float(dispatch.sum()) == C

    def test_dispatch_combine_roundtrip_identity_expert(self):
        T, E, d = 12, 3, 8
        x = jnp.asarray(_r(T, d))
        logits = jnp.asarray(_r(T, E))
        dispatch, combine, _ = top_k_gating(logits, k=1, capacity=T,
                                            normalize=True)
        buckets = moe_dispatch(x, dispatch)
        y = moe_combine(buckets, combine)  # identity experts
        gate = np.asarray(jnp.max(jax.nn.softmax(logits, -1), axis=-1))
        np.testing.assert_allclose(np.asarray(y), np.asarray(x) * gate[:, None],
                                   rtol=1e-5)


class TestMoELayer:
    def test_single_expert_equals_dense_ffn(self):
        T, d, h = 16, 8, 32
        layer = MoELayer(d, h, num_experts=1, top_k=1)
        x = jnp.asarray(_r(T, d))
        y = np.asarray(layer(x, capacity=T))
        # dense reference: softmax over 1 expert == 1.0 gate
        ref = jax.nn.gelu(x @ layer.w1[0] + layer.b1[0]) @ layer.w2[0] + layer.b2[0]
        np.testing.assert_allclose(y, np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_return_aux_under_jit(self):
        import jax
        T, d, h, E = 16, 8, 16, 4
        layer = MoELayer(d, h, num_experts=E, top_k=1)

        @jax.jit
        def f(x):
            y, aux = layer(x, capacity=T, return_aux=True)
            return y, aux

        y, aux = f(jnp.asarray(_r(T, d)))
        assert y.shape == (T, d) and float(aux) > 0

    def test_aux_loss_balanced_vs_skewed(self):
        T, d, h, E = 64, 8, 16, 4
        layer = MoELayer(d, h, num_experts=E, top_k=1)
        layer(jnp.asarray(_r(T, d)), capacity=T)
        balanced = float(layer.aux_loss)
        # skew the gate so everything routes to expert 0
        layer.wg = layer.wg.at[:, 0].set(100.0)
        layer(jnp.asarray(_r(T, d)), capacity=T)
        skewed = float(layer.aux_loss)
        assert skewed > balanced


class TestExpertParallel:
    def test_ep_matches_local(self):
        """4-way EP over the virtual mesh == all-experts-local."""
        mesh = create_mesh({"ep": 4})
        T, d, h, E = 16, 8, 16, 4
        local = MoELayer(d, h, num_experts=E, top_k=2, seed=3)
        x = jnp.asarray(_r(T, d))
        y_local = np.asarray(local(x, capacity=T))

        dist = MoELayer(d, h, num_experts=E, top_k=2, seed=3, ep_axis="ep")

        def body(xs):
            return dist(xs, capacity=xs.shape[0])

        f = shard_map(body, mesh=mesh, in_specs=P("ep"), out_specs=P("ep"),
                      check_vma=False)
        y_dist = np.asarray(f(x))
        np.testing.assert_allclose(y_dist, y_local, rtol=1e-4, atol=1e-4)

    def test_global_scatter_gather_roundtrip_with_counts(self):
        mesh = create_mesh({"ep": 4})
        E, C, d = 4, 4, 8
        x = jnp.asarray(_r(E, C, d))
        counts = jnp.asarray(np.array([4, 2, 0, 3], np.int32))

        def body(b):
            s = global_scatter(b, local_count=paddle.to_tensor(counts),
                               group="ep")
            return global_gather(s, group="ep")._value

        f = shard_map(lambda b: body(b), mesh=mesh, in_specs=P("ep"),
                      out_specs=P("ep"), check_vma=False)
        out = np.asarray(f(jnp.tile(x, (4, 1, 1))))  # each rank same buckets
        ref = np.asarray(x).copy()
        ref[1, 2:] = 0  # count=2 masks rows 2..3
        ref[2, :] = 0   # count=0 masks all
        ref[3, 3:] = 0  # count=3 masks row 3
        np.testing.assert_allclose(out[:E], ref, rtol=1e-6)
