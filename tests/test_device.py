"""Places name real devices: asking for a platform this process does not
have is an error, never another device under the asked name."""
import jax
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import place as _place


@pytest.fixture(autouse=True)
def _restore_place():
    was = _place._CURRENT_PLACE[0]
    yield
    _place._CURRENT_PLACE[0] = was
    jax.config.update("jax_default_device", None)


def test_tpu_place_raises_without_a_tpu():
    with pytest.raises(RuntimeError, match="names a tpu device"):
        paddle.TPUPlace(0).jax_device()


@pytest.mark.parametrize("spec", ["tpu", "tpu:0", "xla", "gpu"])
def test_set_device_of_an_absent_platform_raises_and_selects_nothing(spec):
    before = paddle.get_device()
    with pytest.raises(RuntimeError, match="this process has\\s+none"):
        paddle.set_device(spec)
    assert paddle.get_device() == before


def test_cpu_place_gives_a_cpu_device():
    dev = paddle.CPUPlace(0).jax_device()
    assert dev.platform == "cpu"
    assert paddle.set_device("cpu:1").jax_device() == jax.devices("cpu")[1]
    assert paddle.get_device() == "cpu:1"


def test_default_place_follows_the_backend():
    assert _place._default_place() == paddle.CPUPlace(0)


def test_long_sequence_attention_takes_the_kernel_only_on_a_tpu():
    """bf16 seq 1024 selects the Pallas kernel on a TPU; on the CPU the
    dispatcher takes the fused XLA path (and says so without a guard
    that could swallow a backend failure)."""
    import numpy as np
    import paddle_tpu.nn.functional as F
    from paddle_tpu import monitor
    x = paddle.to_tensor(np.zeros((1, 1024, 1, 64), np.float32)).astype(
        "bfloat16")
    paddle.set_flags({"FLAGS_monitor": True})
    monitor.reset()
    try:
        F.scaled_dot_product_attention(x, x, x, is_causal=True)
        ops = [k for k in monitor.snapshot()["counters"]
               if "attention" in k]
    finally:
        paddle.set_flags({"FLAGS_monitor": False})
        monitor.reset()
    assert any("scaled_dot_product_attention" in k for k in ops), ops
    assert not any("flash_attention" in k for k in ops), ops
