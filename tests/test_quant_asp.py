"""Quantization (QAT/PTQ/int8 weight-only) + ASP 2:4 sparsity +
LookAhead/ModelAverage wrapper optimizers."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.incubate import LookAhead, ModelAverage, asp
from paddle_tpu.quantization import (PTQ, abs_max_scale, dequantize_weights,
                                     fake_quant, freeze, quant_aware,
                                     quantize_weights)


class MLP(nn.Layer):
    def __init__(self, din=16, hidden=32, nclass=4):
        super().__init__()
        self.fc1 = nn.Linear(din, hidden)
        self.act = nn.ReLU()
        self.fc2 = nn.Linear(hidden, nclass)

    def forward(self, x):
        return self.fc2(self.act(self.fc1(x)))


def _data(n=128, din=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, din)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int64)
    return x, y


class TestFakeQuant:
    def test_roundtrip_error_bounded(self):
        x = paddle.to_tensor(np.linspace(-1, 1, 64).astype(np.float32))
        s = abs_max_scale(x)
        q = fake_quant(x, s)
        err = np.abs(np.asarray(q._value) - np.asarray(x._value)).max()
        assert err <= float(s) / 2 + 1e-7  # half-ulp of the int8 grid

    def test_gradient_is_straight_through(self):
        import jax
        import jax.numpy as jnp

        def f(v):
            return fake_quant(v, 0.01).sum()

        g = jax.grad(f)(jnp.linspace(-0.5, 0.5, 16))
        np.testing.assert_allclose(np.asarray(g), 1.0)

    def test_per_channel_scale_shape(self):
        w = paddle.to_tensor(np.random.rand(8, 4).astype(np.float32))
        s = abs_max_scale(w, channel_axis=1)
        assert s.shape == (1, 4)


class TestQAT:
    def test_swap_freeze_and_train(self):
        paddle.seed(0)
        net = quant_aware(MLP())
        from paddle_tpu.quantization import QuantedLinear
        assert type(net.fc1) is QuantedLinear
        x, y = _data()
        opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                    learning_rate=1e-2)
        ce = nn.CrossEntropyLoss()
        w_before = np.asarray(net.fc1.weight._value).copy()
        losses = []
        for i in range(0, 96, 32):
            loss = ce(net(paddle.to_tensor(x[i:i+32])), paddle.to_tensor(y[i:i+32]))
            loss.backward()
            assert net.fc1.weight.grad is not None  # STE reaches the leaf
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        # QAT must actually train: weights move through the fake-quant STE
        assert np.abs(np.asarray(net.fc1.weight._value) - w_before).max() > 1e-5
        freeze(net)
        assert net.fc1._frozen_act_scale is not None
        # frozen model is deterministic (no observer updates)
        o1 = np.asarray(net(paddle.to_tensor(x[:8]))._value)
        o2 = np.asarray(net(paddle.to_tensor(x[:8]))._value)
        np.testing.assert_array_equal(o1, o2)

    def test_convert_without_calibration_raises(self):
        net = quant_aware(MLP())
        with pytest.raises(RuntimeError, match="calibrat"):
            freeze(net)

    def test_qat_descends(self):
        # end-to-end QAT convergence (the training no-op regression guard)
        paddle.seed(0)
        net = quant_aware(MLP())
        x, y = _data()
        opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                    learning_rate=1e-2)
        ce = nn.CrossEntropyLoss()
        losses = []
        for _ in range(4):
            for i in range(0, 128, 32):
                loss = ce(net(paddle.to_tensor(x[i:i+32])),
                          paddle.to_tensor(y[i:i+32]))
                loss.backward()
                opt.step()
                opt.clear_grad()
                losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])

    def test_quanted_model_trains_under_jit(self):
        # tracer path: per-batch dynamic act scales inside TrainStep's jit
        from paddle_tpu.jit import TrainStep
        paddle.seed(0)
        net = quant_aware(MLP())
        opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                    learning_rate=1e-2)
        step = TrainStep(net, nn.CrossEntropyLoss(), opt)
        x, y = _data()
        losses = [float(step(paddle.to_tensor(x[:32]), paddle.to_tensor(y[:32])))
                  for _ in range(20)]
        assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])

    def test_quantized_close_to_float(self):
        paddle.seed(0)
        net = MLP()
        x, _ = _data(seed=0)  # seeded data: the bound below is calibrated
        ref = np.asarray(net(paddle.to_tensor(x))._value)
        qnet = freeze_calibrated(net, x)
        out = np.asarray(qnet(paddle.to_tensor(x))._value)
        err = np.abs(out - ref)
        scale = np.abs(ref).max() + 1e-9
        # Per-tensor abs-max PTQ on an UNTRAINED random net concentrates
        # the int8 grid on activation outliers, so the worst element can
        # be ~10-15% of the output range (jax-version dependent through
        # rounding); the typical element stays tight. Bound both: the
        # former loosely, the latter strictly.
        assert err.max() / scale < 0.20, err.max() / scale
        assert err.mean() / scale < 0.05, err.mean() / scale


def freeze_calibrated(net, x):
    ptq = PTQ()
    qnet = ptq.quantize(net)
    for i in range(0, len(x), 32):
        qnet(paddle.to_tensor(x[i:i+32]))  # calibration pass
    return ptq.convert(qnet)


class TestWeightOnlyInt8:
    def test_artifact_and_inplace_dequant(self):
        paddle.seed(0)
        net = MLP()
        w_before = np.asarray(net.fc1.weight._value).copy()
        art = quantize_weights(net)
        assert set(art) == {"fc1.weight", "fc2.weight"}
        q, s = art["fc1.weight"]
        assert q.dtype == np.int8 and s.shape == (1, 32)
        deq = dequantize_weights(art)["fc1.weight"]
        np.testing.assert_allclose(np.asarray(net.fc1.weight._value), deq)
        rel = np.abs(deq - w_before).max() / np.abs(w_before).max()
        assert rel < 0.01  # int8 per-channel error


class TestASP:
    def test_mask_is_2_of_4(self):
        w = np.random.default_rng(0).normal(size=(16, 8)).astype(np.float32)
        mask = asp.compute_mask(w)
        assert asp.check_sparsity(w * mask)
        # exactly 2 survivors per group, and they are the top-|w| ones
        g = (mask.reshape(4, 4, 8) != 0).sum(axis=1)
        assert (g == 2).all()

    def test_prune_model_and_decorate_keeps_pattern(self):
        paddle.seed(0)
        net = MLP()
        masks = asp.prune_model(net)
        assert "fc1.weight" in masks and "fc2.weight" in masks
        assert asp.check_sparsity(np.asarray(net.fc1.weight._value))
        opt = asp.decorate(
            paddle.optimizer.Adam(parameters=net.parameters(),
                                  learning_rate=1e-2), net)
        x, y = _data()
        ce = nn.CrossEntropyLoss()
        first = last = None
        for i in range(0, 128, 32):
            loss = ce(net(paddle.to_tensor(x[i:i+32])), paddle.to_tensor(y[i:i+32]))
            loss.backward()
            opt.step()
            opt.clear_grad()
            first = first if first is not None else float(loss)
            last = float(loss)
        assert asp.check_sparsity(np.asarray(net.fc1.weight._value))
        assert last < first  # masked training still learns


class TestWrapperOptimizers:
    def test_lookahead_converges_and_syncs_slow_weights(self):
        paddle.seed(0)
        net = MLP()
        opt = LookAhead(paddle.optimizer.SGD(
            parameters=net.parameters(), learning_rate=0.1), alpha=0.5, k=2)
        x, y = _data()
        ce = nn.CrossEntropyLoss()
        losses = []
        for _ in range(3):
            for i in range(0, 128, 32):
                loss = ce(net(paddle.to_tensor(x[i:i+32])),
                          paddle.to_tensor(y[i:i+32]))
                loss.backward()
                opt.step()
                opt.clear_grad()
                losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_model_average_apply_restore(self):
        paddle.seed(0)
        net = MLP()
        ma = ModelAverage(net.parameters())
        w0 = np.asarray(net.fc1.weight._value).copy()
        ma.step()
        net.fc1.weight._value = net.fc1.weight._value + 1.0
        ma.step()
        train_w = np.asarray(net.fc1.weight._value).copy()
        ma.apply()
        np.testing.assert_allclose(np.asarray(net.fc1.weight._value),
                                   (w0 + w0 + 1.0) / 2, rtol=1e-6, atol=1e-6)
        ma.restore()
        np.testing.assert_array_equal(np.asarray(net.fc1.weight._value), train_w)

    def test_model_average_double_apply_keeps_backup(self):
        paddle.seed(0)
        net = MLP()
        ma = ModelAverage(net.parameters())
        ma.step()
        train_w = np.asarray(net.fc1.weight._value).copy()
        ma.apply()
        ma.apply()  # must not clobber the backup with averaged weights
        ma.restore()
        np.testing.assert_array_equal(np.asarray(net.fc1.weight._value), train_w)


class TestQuantPredictor:
    """Quantization wired into the inference Predictor (the reference's
    mkldnn_quantizer.cc / TRT-int8 role, export-time on TPU)."""

    def _save(self, tmp_path, precision=None):
        import os
        import paddle_tpu as paddle
        from paddle_tpu import models
        from paddle_tpu.jit import InputSpec, save
        paddle.seed(0)
        net = models.LeNet(num_classes=10)
        net.eval()
        p = str(tmp_path / f"m_{precision or 'fp32'}")
        kw = {"precision": precision} if precision else {}
        save(net, p, input_spec=[InputSpec([4, 1, 28, 28], "float32")], **kw)
        return p, os.path.getsize(p + ".pdiparams.npz")

    def test_int8_predictor_runs_close_to_fp32(self, tmp_path):
        import numpy as np
        from paddle_tpu.inference import Config, create_predictor
        p32, sz32 = self._save(tmp_path)
        p8, sz8 = self._save(tmp_path, "int8")
        assert sz8 < sz32 * 0.45, (sz8, sz32)  # int8 + scales vs fp32

        x = np.random.RandomState(0).rand(4, 1, 28, 28).astype("float32")

        def run(path, quant=False):
            cfg = Config(path)
            if quant:
                cfg.enable_quant()
            pred = create_predictor(cfg)
            h = pred.get_input_handle(pred.get_input_names()[0])
            h.copy_from_cpu(x)
            pred.run()
            return pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()

        ref = run(p32)
        got = run(p8, quant=True)
        rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
        assert rel < 0.08, rel  # weight-only int8 accuracy delta

    def test_int8_artifact_params_are_int8(self, tmp_path):
        import numpy as np
        from paddle_tpu.jit import load
        p8, _ = self._save(tmp_path, "int8")
        tl = load(p8)
        qnames = tl._meta["quantized"]
        assert qnames, "no quantized params recorded"
        by_name = dict(zip(tl._meta["param_names"], tl._params))
        for n in qnames:
            assert by_name[n].dtype == np.int8, (n, by_name[n].dtype)
        # scales shipped as extra buffers
        assert any(b.startswith("__scale__") for b in tl._meta["buffer_names"])

    def test_enable_quant_on_fp32_artifact_raises(self, tmp_path):
        import pytest as _pytest
        from paddle_tpu.inference import Config, create_predictor
        p32, _ = self._save(tmp_path)
        cfg = Config(p32)
        cfg.enable_quant()
        with _pytest.raises(Exception, match="int8 artifact"):
            create_predictor(cfg)
