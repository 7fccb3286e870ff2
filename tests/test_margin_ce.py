"""margin_cross_entropy + class_center_sample (ArcFace / PartialFC pair).

Reference parity: `python/paddle/nn/functional/loss.py:1107` and
`python/paddle/nn/functional/common.py:1636` — the reference's large-scale
face-recognition stack (model-parallel margin softmax over a sharded class
dimension).

Oracle: straightforward numpy implementation of the ArcFace math; the mp
case runs the same inputs through shard_map over an 8-way 'mp' axis with
class-sharded logits and must match the single-chip value bitwise-close.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


def _np_margin_ce(logits, label, m1=1.0, m2=0.5, m3=0.0, s=64.0):
    lg = logits.copy().astype(np.float64)
    n = lg.shape[0]
    tgt = lg[np.arange(n), label]
    theta = np.arccos(np.clip(tgt, -1, 1))
    lg[np.arange(n), label] = np.cos(m1 * theta + m2) - m3
    lg *= s
    mx = lg.max(-1, keepdims=True)
    ex = np.exp(lg - mx)
    sm = ex / ex.sum(-1, keepdims=True)
    loss = -np.log(sm[np.arange(n), label])
    return loss[:, None], sm


def _cosine_logits(n, c, dim=16, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, dim).astype(np.float32)
    w = rng.randn(dim, c).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    w /= np.linalg.norm(w, axis=0, keepdims=True)
    return x @ w


class TestMarginCrossEntropy:
    def test_matches_numpy_oracle(self):
        n, c = 8, 24
        logits = _cosine_logits(n, c)
        label = np.random.RandomState(1).randint(0, c, (n,)).astype(np.int64)
        want_loss, want_sm = _np_margin_ce(logits, label)
        loss, sm = F.margin_cross_entropy(
            paddle.to_tensor(logits), paddle.to_tensor(label),
            return_softmax=True, reduction=None)
        np.testing.assert_allclose(loss.numpy(), want_loss, rtol=2e-4)
        np.testing.assert_allclose(sm.numpy(), want_sm, rtol=1e-3, atol=1e-6)

    def test_reductions_and_margins(self):
        n, c = 6, 12
        logits = _cosine_logits(n, c, seed=3)
        label = np.random.RandomState(4).randint(0, c, (n,)).astype(np.int64)
        for m1, m2, m3 in ((1.0, 0.5, 0.0), (0.9, 0.4, 0.15), (1.35, 0.0, 0.0)):
            want_loss, _ = _np_margin_ce(logits, label, m1, m2, m3)
            got = F.margin_cross_entropy(
                paddle.to_tensor(logits), paddle.to_tensor(label),
                margin1=m1, margin2=m2, margin3=m3, reduction="mean")
            np.testing.assert_allclose(
                float(got.numpy()), want_loss.mean(), rtol=2e-4)
            got_sum = F.margin_cross_entropy(
                paddle.to_tensor(logits), paddle.to_tensor(label),
                margin1=m1, margin2=m2, margin3=m3, reduction="sum")
            np.testing.assert_allclose(
                float(got_sum.numpy()), want_loss.sum(), rtol=2e-4)

    def test_gradient_flows_to_logits(self):
        n, c = 4, 10
        logits = _cosine_logits(n, c, seed=7) * 0.9   # keep off the clip edge
        label = np.arange(n).astype(np.int64)
        x = paddle.to_tensor(logits, stop_gradient=False)
        loss = F.margin_cross_entropy(x, paddle.to_tensor(label))
        loss.backward()
        g = np.asarray(x.gradient())
        assert np.isfinite(g).all() and np.abs(g).sum() > 0
        # finite-difference on one coordinate (a non-target entry)
        eps = 1e-3
        lp, lm = logits.copy(), logits.copy()
        lp[0, 5] += eps
        lm[0, 5] -= eps
        fd = (_np_margin_ce(lp, label)[0].mean()
              - _np_margin_ce(lm, label)[0].mean()) / (2 * eps)
        np.testing.assert_allclose(g[0, 5], fd, rtol=2e-2, atol=1e-4)

    def test_mp_sharded_matches_single_chip(self):
        n, c = 8, 32
        ndev = len(jax.devices())
        assert ndev >= 8
        logits = _cosine_logits(n, c, seed=9)
        label = np.random.RandomState(2).randint(0, c, (n,)).astype(np.int64)
        want_loss, want_sm = _np_margin_ce(logits, label)

        mesh = Mesh(np.array(jax.devices()[:8]), ("mp",))

        def body(lg, lb):
            out = F.margin_cross_entropy(
                paddle.Tensor(lg), paddle.Tensor(lb),
                return_softmax=True, reduction=None)
            return out[0]._value, out[1]._value

        from jax import shard_map
        f = shard_map(body, mesh=mesh, in_specs=(P(None, "mp"), P()),
                          out_specs=(P(), P(None, "mp")))
        loss, sm = f(jnp.asarray(logits), jnp.asarray(label))
        np.testing.assert_allclose(np.asarray(loss), want_loss, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(sm), want_sm, rtol=1e-3,
                                   atol=1e-6)


class TestClassCenterSample:
    def test_reference_docstring_example(self):
        paddle.seed(0)
        label = paddle.to_tensor(
            np.array([11, 5, 1, 3, 12, 2, 15, 19, 18, 19], dtype=np.int64))
        remapped, sampled = F.class_center_sample(label, 20, 6)
        sv = sampled.numpy()
        # every positive kept, remap consistent: sampled[remap[i]] == label[i]
        for l, m in zip(label.numpy(), remapped.numpy()):
            assert sv[m] == l
        assert len(sv) >= 6            # positives (9 here) can exceed samples

    def test_pads_with_negatives_to_num_samples(self):
        paddle.seed(5)
        label = paddle.to_tensor(np.array([3, 3, 3], dtype=np.int64))
        remapped, sampled = F.class_center_sample(label, 50, 8)
        sv = sampled.numpy()
        assert len(sv) == 8
        assert 3 in sv
        assert len(np.unique(sv)) == 8
        assert (remapped.numpy() == np.searchsorted(sv, 3)).all()

    def test_rejects_oversample(self):
        label = paddle.to_tensor(np.array([1, 2], dtype=np.int64))
        with pytest.raises(Exception):
            F.class_center_sample(label, 4, 10)

    def test_multi_rank_local_indices_and_consistent_remap(self):
        """PartialFC contract: every rank returns LOCAL sampled indices in
        [0, num_classes) (they gather from the local weight shard), and
        all ranks agree on the remapped labels (cumulative positions into
        the concatenation of per-rank sampled lists)."""

        class G0:
            rank, nranks = 0, 2

        class G1:
            rank, nranks = 1, 2

        lab = np.array([6, 1, 2, 5], dtype=np.int64)   # classes split 4/4
        paddle.seed(11)
        r0, s0 = F.class_center_sample(paddle.to_tensor(lab), 4, 2, group=G0())
        paddle.seed(11)
        r1, s1 = F.class_center_sample(paddle.to_tensor(lab), 4, 2, group=G1())
        assert (r0.numpy() == r1.numpy()).all()
        for s in (s0.numpy(), s1.numpy()):
            assert s.min() >= 0 and s.max() < 4
        # remap resolves through the concatenated [rank0 | rank1] lists
        concat = np.concatenate([s0.numpy(), s1.numpy() + 4])
        for l, m in zip(lab, r0.numpy()):
            assert concat[m] == l, (l, m, concat)
