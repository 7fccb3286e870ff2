"""`to_static(..., donate_inputs=...)`: the caller that owns some of the
positional inputs gives their buffers to the program. Donated inputs are
deleted after the call, everything else is left alone, the values are the
undonated program's, and a capture without the argument lowers to the very
program it lowered to before the argument existed."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.monitor as monitor
from paddle_tpu import nn
from paddle_tpu.core import compile_cache as cc
from paddle_tpu.core import flags as _flags
from paddle_tpu.jit import to_static
from paddle_tpu.jit.functional import split_state


class Carry(nn.Layer):
    """(x, state_a, state_b) -> (y, new_a, new_b): the shape of a decode
    step, one row of each state rewritten."""

    def __init__(self):
        super().__init__()
        self.lin = nn.Linear(4, 4)

    def forward(self, x, a, b):
        y = self.lin(x)
        return y.sum(), a * 0.5 + y, b + 1.0


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    return [paddle.to_tensor(rng.standard_normal((3, 4)).astype("float32"))
            for _ in range(3)]


def _net(donate_inputs=None):
    paddle.seed(11)
    net = Carry()
    net.eval()
    to_static(net, name="carry", donate_inputs=donate_inputs)
    return net


def _values(outs):
    return [np.asarray(o.numpy()) for o in outs]


@pytest.mark.parametrize("donate, gone", [
    (slice(1, 3), [False, True, True]),
    ((2,), [False, False, True]),
    ([-2], [False, True, False]),
    (None, [False, False, False]),
], ids=["slice", "index", "negative_index", "none"])
def test_donated_inputs_are_deleted_and_values_are_the_undonated_ones(
        donate, gone):
    with paddle.no_grad():
        want = _values(_net()(*_inputs()))
        ins = _inputs()
        got = _values(_net(donate)(*ins))
    assert [t._value.is_deleted() for t in ins] == gone
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_outputs_feed_the_next_call_with_zero_retraces():
    monitor.reset()
    paddle.set_flags({"FLAGS_monitor": True})
    try:
        net = _net(slice(1, 3))
        x, a, b = _inputs()
        with paddle.no_grad():
            _, a, b = net(x, a, b)
            first = dict(monitor.snapshot()["counters"])
            for _ in range(3):
                held = (a, b)
                _, a, b = net(x, a, b)
                assert all(t._value.is_deleted() for t in held)
                assert not x._value.is_deleted()
        after = monitor.snapshot()["counters"]
    finally:
        paddle.set_flags({"FLAGS_monitor": False})
        monitor.reset()
    moved = {k: (first.get(k, 0), v) for k, v in after.items()
             if ("retrace" in k or "compile" in k or "cache_miss" in k)
             and first.get(k, 0) != v}
    assert not moved
    assert len([k for k in net.forward._jit_cache if k[0] == "jit"]) == 1


def test_recording_path_refuses_a_donating_function():
    net = _net(slice(1, 3))
    ins = _inputs()
    with pytest.raises(RuntimeError, match="donate_inputs"):
        net(*ins)                       # grad enabled, trainable weights
    assert not any(t._value.is_deleted() for t in ins)


def test_donation_survives_the_persistent_executable_path(tmp_path):
    """`_exe.acquire(..., donate=)`: a second process's executable comes
    from disk re-wrapped in `jax.jit`, and must still consume the buffers."""
    _flags.set_flags({"compile_cache_dir": str(tmp_path / "cc")})
    cc.reset_stats()
    try:
        with paddle.no_grad():
            want = _values(_net(slice(1, 3))(*_inputs()))    # fresh + store
            ins = _inputs()
            got = _values(_net(slice(1, 3))(*ins))           # from disk
        assert cc.stats()["hits"] >= 1
    finally:
        _flags.set_flags({"compile_cache_dir": ""})
        cc.reset_stats()
    assert [t._value.is_deleted() for t in ins] == [False, True, True]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def _lowered_text(net, ins):
    static = net.forward
    (jitted,) = [v for k, v in static._jit_cache.items() if k[0] == "jit"]
    trainable, frozen = split_state(net)
    arrays = [t._value for t in ins]
    return jitted.lower(*static._call_args(
        [t._value for t in trainable.values()],
        [t._value for t in frozen.values()], jax.random.key(0), arrays,
        static._donated(len(arrays)))).as_text()


def test_without_the_argument_the_program_is_the_one_it_always_was():
    """The parent's `_get_jitted` was `jax.jit(pure)` of the four-argument
    `pure` under the capture's name; an undonated capture must lower to
    that text, and carry no donation attribute."""
    net, ins = _net(), _inputs()
    with paddle.no_grad():
        net(*ins)
    static = net.forward
    text = _lowered_text(net, ins)
    assert "jax.buffer_donor" not in text and "tf.aliasing_output" not in text

    trainable, frozen = split_state(net)
    training = tuple(l.training for l in net.sublayers(include_self=True))
    pure = static._get_pure(training, list(trainable), list(frozen), {})
    assert pure.__name__ == "carry"
    as_before = jax.jit(pure).lower(
        [t._value for t in trainable.values()],
        [t._value for t in frozen.values()], jax.random.key(0),
        [t._value for t in ins]).as_text()
    assert text == as_before

    donating, ins2 = _net(slice(1, 3)), _inputs()
    with paddle.no_grad():
        donating(*ins2)
    text_d = _lowered_text(donating, _inputs())
    assert text_d != text
    assert text_d.count("tf.aliasing_output") + text_d.count(
        "jax.buffer_donor") == 2
