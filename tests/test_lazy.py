"""Lazy batching eager executor (FLAGS_lazy_eager, ops/lazy.py — ISSUE 9).

Acceptance properties:
  - bit-identity: a lazy LeNet train loop (fwd + bwd + Adam) produces the
    SAME losses, params, optimizer slots and rng state as immediate mode
  - dispatch budget: a steady-state step costs <= 3 dispatches (segment
    flush + fused backward + fused optimizer update), zero per-op
    dispatches, zero retraces — asserted via monitor counters
  - every sync point in the tpu-lint host-sync taxonomy flushes
  - FLAGS_check_nan_inf still aborts (scan deferred to the flush) and the
    TrainGuard divergence rollback keeps working under the flag
  - ops that can't be keyed/abstracted fall back to immediate dispatch
    with identical semantics
  - the disabled path costs one module-attribute check (overhead guard)
"""
import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import monitor
from paddle_tpu.core import flags as _flags
from paddle_tpu.ops import lazy as _lazy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- fixtures / helpers -----------------------------------------------------

@pytest.fixture
def with_monitor():
    _flags.set_flags({"monitor": True})
    monitor.reset()
    yield
    monitor.reset()
    _flags.set_flags({"monitor": False})


@contextlib.contextmanager
def lazy_mode(on=True):
    """Enable FLAGS_lazy_eager (and pin eager_auto_jit off so both arms of
    an A/B run the same op stream); restore on exit."""
    before = {k: _flags.flag(k) for k in ("lazy_eager", "eager_auto_jit")}
    paddle.set_flags({"FLAGS_lazy_eager": on, "FLAGS_eager_auto_jit": False})
    try:
        yield
    finally:
        _lazy.flush_pending()
        paddle.set_flags({f"FLAGS_{k}": v for k, v in before.items()})


class LeNetSmall(nn.Layer):
    """Same conv/pool/fc topology as the guard tests, over 16x16 inputs."""

    def __init__(self, num_classes=4):
        super().__init__()
        self.features = nn.Sequential(
            nn.Conv2D(1, 6, 3, stride=1, padding=1), nn.ReLU(),
            nn.MaxPool2D(2, 2),
            nn.Conv2D(6, 16, 5, stride=1, padding=0), nn.ReLU(),
            nn.MaxPool2D(2, 2))
        self.fc = nn.Sequential(
            nn.Linear(64, 32), nn.ReLU(), nn.Linear(32, num_classes))

    def forward(self, x):
        x = self.features(x)
        x = paddle.flatten(x, 1)
        return self.fc(x)


def _lenet_batches(n_batches=5, bs=8):
    rng = np.random.RandomState(0)
    out = []
    for _ in range(n_batches):
        xs = rng.rand(bs, 1, 16, 16).astype("float32") * 0.1
        ys = rng.randint(0, 4, (bs,)).astype("int64")
        out.append((xs, ys))
    return out


def _train_lenet(lazy, steps=5):
    """One eager train run; returns (losses, params, slots, rng_state)."""
    batches = _lenet_batches(steps)
    with lazy_mode(on=lazy):
        paddle.seed(0)
        np.random.seed(0)
        net = LeNetSmall()
        loss_fn = nn.CrossEntropyLoss()
        opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                    learning_rate=2e-3)
        losses = []
        for xs, ys in batches:
            x, y = paddle.to_tensor(xs), paddle.to_tensor(ys)
            loss = loss_fn(net(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))  # host sync (flushes under lazy)
        params = {k: np.asarray(v) for k, v in net.state_dict().items()}
        slots = {pid: {sn: np.asarray(sv) for sn, sv in sd.items()}
                 for pid, sd in zip(
                     sorted(range(len(opt._accumulators))),
                     opt._accumulators.values())}
        rng = paddle.get_rng_state()
    return losses, params, slots, rng


# ---- bit-identity vs immediate mode -----------------------------------------

class TestBitIdentity:
    def test_lenet_train_loop_bit_identical(self):
        """fwd + bwd + Adam for 5 steps: losses, every param, every
        optimizer slot and the rng state must match immediate mode
        BIT-FOR-BIT — lazy mode replays the same jax ops in the same
        order, just batched into one executable per segment."""
        l_im, p_im, s_im, r_im = _train_lenet(lazy=False)
        l_lz, p_lz, s_lz, r_lz = _train_lenet(lazy=True)

        assert l_im == l_lz, f"losses diverged: {l_im} vs {l_lz}"
        assert sorted(p_im) == sorted(p_lz)
        for k in p_im:
            assert np.array_equal(p_im[k], p_lz[k]), f"param {k} differs"
        assert sorted(s_im) == sorted(s_lz)
        for pid in s_im:
            assert sorted(s_im[pid]) == sorted(s_lz[pid])
            for sn in s_im[pid]:
                assert np.array_equal(s_im[pid][sn], s_lz[pid][sn]), \
                    f"optimizer slot {sn} differs"
        # rng state: (seed, count, key data, pool data)
        assert r_im[0] == r_lz[0] and r_im[1] == r_lz[1]
        assert np.array_equal(np.asarray(r_im[2]), np.asarray(r_lz[2]))

    def test_simple_chain_values_identical(self):
        x = np.linspace(-2, 2, 24).astype("float32").reshape(4, 6)
        t = paddle.to_tensor(x)
        ref = np.asarray((paddle.tanh(t * 3.0) + paddle.exp(t)).numpy())
        with lazy_mode():
            t2 = paddle.to_tensor(x)
            out = paddle.tanh(t2 * 3.0) + paddle.exp(t2)
            assert _lazy.pending_ops() > 0
            got = out.numpy()
        assert np.array_equal(ref, got)


# ---- steady-state dispatch budget (the whole point) --------------------------

class TestSteadyState:
    def test_three_dispatches_per_step_and_zero_retraces(self, with_monitor):
        """After warmup, each train step costs exactly 3 dispatches —
        lazy segment flush + fused backward + fused optimizer update —
        with ZERO per-op dispatches, zero fallbacks and zero segment
        retraces (ISSUE 9 acceptance: <=3)."""
        batches = _lenet_batches(6)
        with lazy_mode():
            paddle.seed(0)
            net = LeNetSmall()
            loss_fn = nn.CrossEntropyLoss()
            opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                        learning_rate=2e-3)

            def step(xs, ys):
                x, y = paddle.to_tensor(xs), paddle.to_tensor(ys)
                loss = loss_fn(net(x), y)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return float(loss)

            for xs, ys in batches[:3]:   # warmup: traces + slot init
                step(xs, ys)
            before = dict(monitor.snapshot().get("counters", {}))
            n = 0
            for xs, ys in batches[3:]:
                step(xs, ys)
                n += 1
            after = dict(monitor.snapshot().get("counters", {}))

        d = lambda k: after.get(k, 0) - before.get(k, 0)
        dispatches = (d("lazy.dispatches") + d("autograd.fused_backward")
                      + d("optimizer.fused_dispatches"))
        assert dispatches == 3 * n, (
            f"steady-state step costs {dispatches / n} dispatches "
            f"(budget: 3) — {after}")
        assert d("dispatch.op_count") == 0, "per-op dispatch leaked through"
        assert d("lazy.fallback_ops") == 0
        assert d("jit.lazy_segment.traces") == 0, "steady-state trace"
        assert d("jit.lazy_segment.retraces") == 0, "steady-state RETRACE"
        assert d("lazy.cache_hits") == d("lazy.flushes") > 0
        assert d("lazy.ops_deferred") == d("lazy.ops_flushed") > 0

    def test_segment_cache_keyed_by_shape(self, with_monitor):
        """A new input shape is a new segment signature: one trace, then
        cache hits again — mirroring jit/train_step retrace accounting."""
        with lazy_mode():
            def f(shape):
                t = paddle.to_tensor(np.ones(shape, "float32"))
                return (t * 2.0 + 1.0).numpy()

            f((4, 4))                                 # trace A
            before = dict(monitor.snapshot().get("counters", {}))
            f((4, 4))                                 # hit A
            f((8, 4))                                 # trace B (retrace)
            f((8, 4))                                 # hit B
            after = dict(monitor.snapshot().get("counters", {}))
        d = lambda k: after.get(k, 0) - before.get(k, 0)
        assert d("lazy.cache_hits") == 2
        assert d("jit.lazy_segment.retraces") == 1


# ---- sync points: the tpu-lint host-sync taxonomy ----------------------------

def _deferred_pair():
    t = paddle.to_tensor(np.arange(6, dtype="float32").reshape(2, 3))
    out = t * 2.0 + 1.0
    assert _lazy.pending_ops() > 0, "op was not deferred"
    return t, out


EXPECTED = np.arange(6, dtype="float32").reshape(2, 3) * 2.0 + 1.0


class TestSyncPoints:
    """Every sync point in the host-sync taxonomy must flush the pending
    segment and return values identical to immediate mode."""

    def test_numpy(self):
        with lazy_mode():
            _, out = _deferred_pair()
            got = out.numpy()
            assert _lazy.pending_ops() == 0
            assert np.array_equal(got, EXPECTED)

    def test_item(self):
        with lazy_mode():
            _, out = _deferred_pair()
            assert out.sum().item() == float(EXPECTED.sum())
            assert _lazy.pending_ops() == 0

    def test_tolist(self):
        with lazy_mode():
            _, out = _deferred_pair()
            assert out.tolist() == EXPECTED.tolist()
            assert _lazy.pending_ops() == 0

    def test_float_builtin(self):
        with lazy_mode():
            _, out = _deferred_pair()
            assert float(out.sum()) == float(EXPECTED.sum())
            assert _lazy.pending_ops() == 0

    def test_int_builtin_nondiff(self):
        with lazy_mode():
            _, out = _deferred_pair()
            idx = paddle.argmax(paddle.flatten(out))   # deferred, nondiff
            assert int(idx) == int(EXPECTED.argmax())
            assert _lazy.pending_ops() == 0

    def test_bool_control_flow(self):
        with lazy_mode():
            _, out = _deferred_pair()
            if (out.sum() > 0.0):                      # tensor-branch sync
                hit = True
            else:
                hit = False
            assert hit and _lazy.pending_ops() == 0

    def test_repr(self):
        with lazy_mode():
            _, out = _deferred_pair()
            s = repr(out)
            assert _lazy.pending_ops() == 0
            assert "11." in s                          # EXPECTED[1, 2]

    def test_np_asarray(self):
        with lazy_mode():
            _, out = _deferred_pair()
            got = np.asarray(out)
            assert _lazy.pending_ops() == 0
            assert np.array_equal(got, EXPECTED)

    def test_backward(self):
        with lazy_mode():
            t = paddle.to_tensor(np.ones((2, 3), "float32"))
            t.stop_gradient = False
            loss = (t * 3.0).sum()
            assert _lazy.pending_ops() > 0
            loss.backward()                            # flushes forward
            assert _lazy.pending_ops() == 0
            assert np.allclose(np.asarray(t.grad), 3.0)

    def test_paddle_grad(self):
        with lazy_mode():
            t = paddle.to_tensor(np.ones((2, 3), "float32"))
            t.stop_gradient = False
            loss = (t * 5.0).sum()
            assert _lazy.pending_ops() > 0
            (g,) = paddle.grad(loss, [t])
            assert _lazy.pending_ops() == 0
            assert np.allclose(np.asarray(g.numpy()), 5.0)

    def test_paddle_sync(self):
        with lazy_mode():
            _, out = _deferred_pair()
            paddle.sync()
            assert _lazy.pending_ops() == 0
            assert type(out._value) is not _lazy._LazyValue
            assert np.array_equal(np.asarray(out._value), EXPECTED)

    def test_block_until_ready(self):
        with lazy_mode():
            _, out = _deferred_pair()
            out._value.block_until_ready()
            assert _lazy.pending_ops() == 0

    def test_disable_flag_flushes(self):
        """Turning FLAGS_lazy_eager off mid-flight is itself a sync point
        — nothing may stay pending once the mode is off."""
        with lazy_mode():
            _, out = _deferred_pair()
            paddle.set_flags({"FLAGS_lazy_eager": False})
            assert _lazy.pending_ops() == 0
            assert np.array_equal(out.numpy(), EXPECTED)


# ---- FLAGS_check_nan_inf: deferred scan at the flush -------------------------

class TestNanInfInterplay:
    def test_deferred_scan_raises_at_flush_naming_the_op(self):
        """The per-op NaN scan cannot run at defer time (there is no value
        yet); it re-runs over the flushed outputs, so the abort names the
        producing op but fires at the sync point."""
        _flags.set_flags({"check_nan_inf": True})
        try:
            with lazy_mode():
                t = paddle.to_tensor(np.zeros((4,), "float32"))
                bad = paddle.log(t)          # log(0) = -inf, deferred
                assert _lazy.pending_ops() > 0   # did NOT raise at defer
                with pytest.raises(FloatingPointError, match="log"):
                    bad.numpy()
                assert _lazy.pending_ops() == 0
        finally:
            _flags.set_flags({"check_nan_inf": False})

    def test_guard_rollback_still_works_under_lazy_flag(self):
        """TrainGuard's divergence detection reads the loss on the host —
        a sync point — so a NaN batch still rolls back and is skipped with
        FLAGS_lazy_eager on (jitted TrainStep internals trace as usual;
        deferral only applies to eager dispatch)."""
        from paddle_tpu.guard import GuardConfig, TrainGuard
        from paddle_tpu.jit.train_step import TrainStep
        with lazy_mode():
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
            opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                        learning_rate=1e-2)
            step = TrainStep(net, nn.MSELoss(), opt, n_model_inputs=1)
            rng = np.random.RandomState(1)
            x = paddle.to_tensor(rng.rand(8, 4).astype("float32"))
            y = paddle.to_tensor(rng.rand(8, 1).astype("float32"))
            xnan = paddle.to_tensor(np.full((8, 4), np.nan, "float32"))
            with TrainGuard(step, config=GuardConfig(snapshot_interval=1,
                                                     max_bad_steps=3)) as g:
                g.set_cursor(0, 0)
                l0 = g.step(x, y)
                assert l0 is not None and np.isfinite(l0)
                good = {k: np.asarray(v)
                        for k, v in step.state_dict()["params"].items()}
                g.set_cursor(0, 1)
                assert g.step(xnan, y) is None       # rolled back + skipped
                after = {k: np.asarray(v)
                         for k, v in step.state_dict()["params"].items()}
                for k in good:
                    assert np.array_equal(good[k], after[k]), \
                        f"rollback missed param {k}"
                g.set_cursor(0, 2)
                l2 = g.step(x, y)
                assert l2 is not None and np.isfinite(l2)


# ---- fallbacks: unkeyable / traced ops stay correct ---------------------------

class TestFallbacks:
    def test_uncacheable_closure_falls_back(self, with_monitor):
        """A function whose closure can't be value-keyed (autograd._freeze
        raises _Uncacheable) dispatches immediately — same result, tape
        intact, counted in lazy.fallback_ops."""
        from paddle_tpu.ops._dispatch import run_op

        class Opaque:
            pass

        o = Opaque()

        def fn(a):
            assert o is not None      # closure over an unkeyable object
            return a * 4.0

        with lazy_mode():
            before = monitor.counter("lazy.fallback_ops").get()
            t = paddle.to_tensor(np.ones((3,), "float32"))
            t.stop_gradient = False
            out = run_op(fn, [t], "opaque_mul")
            assert monitor.counter("lazy.fallback_ops").get() > before
            assert type(out._value) is not _lazy._LazyValue  # immediate
            assert np.allclose(out.numpy(), 4.0)
            out.sum().backward()
            assert np.allclose(np.asarray(t.grad), 4.0)

    def test_to_static_traced_region_unaffected(self):
        """Inside a jax trace the inputs are tracers: deferral must step
        aside and let the trace see the ops (a deferred tracer would leak
        out of its trace context)."""
        @paddle.jit.to_static
        def f(a):
            return paddle.tanh(a) * 2.0

        x = np.linspace(-1, 1, 8).astype("float32")
        ref = np.asarray(f(paddle.to_tensor(x)).numpy())
        with lazy_mode():
            got = f(paddle.to_tensor(x))
            out = np.asarray(got.numpy())
            assert _lazy.pending_ops() == 0
        assert np.allclose(ref, out)

    def test_mixed_lazy_inputs_into_fallback_op(self, with_monitor):
        """A fallback op consuming a still-pending tensor forces its
        inputs to materialize first (partial flush), not an error."""
        from paddle_tpu.ops._dispatch import run_op

        class Opaque:
            pass

        o = Opaque()

        def fn(a):
            assert o is not None
            return a + 10.0

        with lazy_mode():
            t = paddle.to_tensor(np.ones((3,), "float32"))
            mid = t * 2.0                  # deferred
            assert _lazy.pending_ops() > 0
            out = run_op(fn, [mid], "opaque_add")
            assert np.allclose(out.numpy(), 12.0)


# ---- inplace op_ variants -----------------------------------------------------

class TestInplace:
    def test_inplace_alias_rebound_at_flush(self):
        with lazy_mode():
            t = paddle.to_tensor(np.ones((2, 2), "float32"))
            t.add_(paddle.to_tensor(np.full((2, 2), 2.0, "float32")))
            assert _lazy.pending_ops() > 0
            assert np.allclose(t.numpy(), 3.0)
            assert type(t._value) is not _lazy._LazyValue

    def test_zero_on_pending_tensor(self):
        with lazy_mode():
            t = paddle.to_tensor(np.ones((2, 2), "float32"))
            u = t * 7.0
            u.zero_()                       # resolves then zeros
            assert np.allclose(u.numpy(), 0.0)


# ---- disabled-path overhead guard (PR 1 style) --------------------------------

class TestOverheadGuard:
    def test_disabled_path_adds_one_attribute_check(self):
        """CI guard: FLAGS_lazy_eager=0 must keep run_op within a generous
        wall-time bound of the uninstrumented impl — the gate is a single
        module-attribute check, no segment, no allocation."""
        from paddle_tpu.ops import _dispatch
        assert _lazy._ACTIVE is False
        x = paddle.to_tensor(np.ones((4, 4), "float32"))
        paddle.add(x, x)                    # warm the op cache

        def loop_run_op():
            t0 = time.perf_counter()
            for _ in range(200):
                paddle.add(x, x)
            return time.perf_counter() - t0

        import jax.numpy as jnp

        def loop_impl():
            t0 = time.perf_counter()
            for _ in range(200):
                _dispatch._run_op_impl(jnp.add, [x, x], "add")
            return time.perf_counter() - t0

        loop_run_op(), loop_impl()          # warmup both paths
        t_instr = min(loop_run_op() for _ in range(3))
        t_base = min(loop_impl() for _ in range(3))
        assert t_instr < t_base + 0.05, (
            f"disabled lazy path too slow: {t_instr:.4f}s vs "
            f"{t_base:.4f}s baseline")


# ---- bench: an unusable backend is a failure, not weather ---------------------

class TestBenchOutage:
    def test_unusable_backend_exits_nonzero(self):
        """bench.py once turned a backend that would not start into an
        `{"outage": true}` line and rc=0, which also hid OOMs and compile
        errors. A run that measured nothing must not look like a run."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "bogus_backend"
        p = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                           capture_output=True, text=True, env=env,
                           cwd=REPO, timeout=180)
        assert p.returncode != 0, p.stdout[-2000:]
        assert "bogus_backend" in p.stderr
        assert '"outage"' not in p.stdout and '"metric"' not in p.stdout

    def test_an_arm_that_raises_makes_main_exit_nonzero(self, monkeypatch,
                                                        capsys):
        """Every other arm still reports; the run then fails."""
        sys.path.insert(0, REPO)
        import bench
        for name in [n for n in vars(bench) if n.startswith("bench_")]:
            monkeypatch.setattr(bench, name, lambda backend: {"ran": True})
        monkeypatch.setattr(bench, "bench_ernie_train",
                            lambda backend: {"samples_per_sec": 1.0})

        def boom(backend):
            raise MemoryError("RESOURCE_EXHAUSTED: out of HBM")
        monkeypatch.setattr(bench, "bench_llm", boom)
        import jax
        cache_was = jax.config.jax_compilation_cache_dir
        try:
            with pytest.raises(SystemExit) as exc:
                bench.main()
        finally:   # main() switches JAX's persistent cache on; tests don't
            jax.config.update("jax_compilation_cache_dir", cache_was)
        assert exc.value.code not in (0, None) and "llm" in str(exc.value)
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert "RESOURCE_EXHAUSTED" in doc["extra"]["llm"]["error"]
        assert doc["extra"]["net"] == {"ran": True}

    def test_unknown_device_kind_has_no_peak(self):
        """MFU against a guessed peak is a wrong number: the CPU (or a
        chip nobody entered with its source) raises."""
        sys.path.insert(0, REPO)
        import bench
        with pytest.raises(KeyError, match="no published peak"):
            bench._peak_flops()
        assert bench.PEAKS["TPU v5 lite"]["bf16_flops"] == 1.97e14
