"""Training guard plane: preemption-safe resume, step watchdog, divergence
rollback, cross-rank desync detection (paddle_tpu.guard).

Chaos technique: the `guard.step` / `guard.snapshot` fault sites
(paddle_tpu.faults) wedge, crash, and tear the guard's own seams; the
acceptance property throughout is the JAX/Orbax-style discipline — an
interrupted run restored from the last-good generation produces
bit-identical params to an uninterrupted one.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import faults, monitor
from paddle_tpu.core import flags as _flags
from paddle_tpu.guard import (DesyncDetector, DivergedError, GuardConfig,
                              PreemptedError, RankDesyncError,
                              StepStalledError, StepWatchdog, TrainGuard,
                              fingerprint, load_guard_state, save_guard_state)
from paddle_tpu.jit.train_step import TrainStep


# ---- fixtures / helpers -----------------------------------------------------

@pytest.fixture
def with_monitor():
    _flags.set_flags({"monitor": True})
    monitor.reset()
    yield
    monitor.reset()
    _flags.set_flags({"monitor": False})


class LeNetSmall(nn.Layer):
    """LeNet topology over 16x16 inputs — same conv/pool/fc structure as
    the book test, sized for fast chaos loops."""

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


def _lenet_batches(n_batches=6, bs=8):
    rng = np.random.RandomState(0)
    out = []
    for _ in range(n_batches):
        xs = rng.rand(bs, 1, 16, 16).astype("float32") * 0.1
        ys = rng.randint(0, 4, (bs,)).astype("int64")
        for i, c in enumerate(ys):
            r, col = divmod(int(c), 2)
            xs[i, 0, r * 8:r * 8 + 6, col * 8:col * 8 + 6] += 1.0
        out.append((paddle.to_tensor(xs), paddle.to_tensor(ys)))
    return out


def _make_lenet_step(seed=0, lr=2e-3):
    paddle.seed(seed)
    np.random.seed(seed)
    net = LeNetSmall()
    opt = paddle.optimizer.Adam(parameters=net.parameters(), learning_rate=lr)
    return net, TrainStep(net, nn.CrossEntropyLoss(), opt, n_model_inputs=1)


def _make_linear_step(seed=0):
    paddle.seed(seed)
    np.random.seed(seed)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
    opt = paddle.optimizer.Adam(parameters=net.parameters(), learning_rate=1e-2)
    return net, TrainStep(net, nn.MSELoss(), opt, n_model_inputs=1)


def _linear_batches(n=8, bs=8):
    rng = np.random.RandomState(1)
    return [(paddle.to_tensor(rng.rand(bs, 4).astype("float32")),
             paddle.to_tensor(rng.rand(bs, 1).astype("float32")))
            for _ in range(n)]


def _run_guarded_epochs(guard, batches, epochs, start=(0, 0)):
    for epoch in range(epochs):
        for b, (x, y) in enumerate(batches):
            if (epoch, b) < tuple(start):
                continue
            guard.set_cursor(epoch, b)
            guard.step(x, y)


def _assert_params_equal(sd_a, sd_b):
    assert sorted(sd_a["params"]) == sorted(sd_b["params"])
    for n in sd_a["params"]:
        assert np.array_equal(sd_a["params"][n], sd_b["params"][n]), \
            f"param {n} differs"


# ---- preemption-safe auto-resume -------------------------------------------

class TestPreemptionResume:
    def test_sigterm_mid_epoch_then_resume_bit_identical(self, tmp_path):
        """kill -TERM during epoch 1, resume in 'a new process' (fresh
        model/optimizer/TrainStep objects), finish: final params must be
        bit-identical to an uninterrupted 2-epoch run."""
        batches = _lenet_batches(3)
        # run A: uninterrupted
        _, step_a = _make_lenet_step()
        with TrainGuard(step_a, config=GuardConfig(snapshot_interval=0)) as ga:
            _run_guarded_epochs(ga, batches, epochs=2)
        final_a = step_a.state_dict()

        # run B: SIGTERM arrives during epoch 1; the in-flight step
        # finishes, the loop state is committed, PreemptedError raised
        ckpt = str(tmp_path / "guard")
        _, step_b = _make_lenet_step()
        with TrainGuard(step_b, ckpt_dir=ckpt,
                        config=GuardConfig(snapshot_interval=0)) as gb:
            with pytest.raises(PreemptedError) as ei:
                for epoch in range(2):
                    for b, (x, y) in enumerate(batches):
                        gb.set_cursor(epoch, b)
                        if (epoch, b) == (1, 1):
                            os.kill(os.getpid(), signal.SIGTERM)
                        gb.step(x, y)
        assert ei.value.cursor == (1, 2)
        assert ei.value.ckpt_dir == ckpt

        # "relaunch": everything rebuilt from scratch with a DIFFERENT
        # seed — resume must overwrite params, slots, rng and step count
        _, step_c = _make_lenet_step(seed=123)
        with TrainGuard(step_c, ckpt_dir=ckpt,
                        config=GuardConfig(snapshot_interval=0)) as gc:
            start = gc.resume()
            assert start == (1, 2)
            _run_guarded_epochs(gc, batches, epochs=2, start=start)
        final_c = step_c.state_dict()
        _assert_params_equal(final_a, final_c)
        assert np.array_equal(final_a["rng_key"], final_c["rng_key"])
        assert final_a["step_count"] == final_c["step_count"]

    def test_sigint_also_preempts_and_counts(self, with_monitor):
        _, step = _make_linear_step()
        x, y = _linear_batches(1)[0]
        with TrainGuard(step, config=GuardConfig(snapshot_interval=0)) as g:
            g.set_cursor(0, 0)
            g.step(x, y)
            os.kill(os.getpid(), signal.SIGINT)
            # no ckpt_dir: still raises (typed), just doesn't persist
            with pytest.raises(PreemptedError) as ei:
                g.set_cursor(0, 1)
                g.step(x, y)
        assert ei.value.ckpt_dir is None
        assert monitor.counter("guard.preempts").get() == 1

    def test_signal_handlers_restored_on_close(self):
        prev_term = signal.getsignal(signal.SIGTERM)
        prev_int = signal.getsignal(signal.SIGINT)
        _, step = _make_linear_step()
        g = TrainGuard(step)
        g.install_signal_handlers()
        assert signal.getsignal(signal.SIGTERM) is not prev_term
        g.close()
        assert signal.getsignal(signal.SIGTERM) is prev_term
        assert signal.getsignal(signal.SIGINT) is prev_int

    def test_resume_without_checkpoint_is_fresh_start(self, tmp_path):
        _, step = _make_linear_step()
        with TrainGuard(step, ckpt_dir=str(tmp_path / "none")) as g:
            assert g.resume() is None

    def test_scaler_and_scheduler_round_trip(self, tmp_path):
        from paddle_tpu.amp import GradScaler
        from paddle_tpu.optimizer import lr as lr_mod
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 4), nn.Linear(4, 1))
        sched = lr_mod.StepDecay(learning_rate=0.1, step_size=2)
        opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                    learning_rate=sched)
        step = TrainStep(net, nn.MSELoss(), opt, n_model_inputs=1)
        scaler = GradScaler(init_loss_scaling=512.0)
        scaler._good_steps, scaler._bad_steps, scaler._found_inf = 7, 1, True
        x, y = _linear_batches(1)[0]
        with TrainGuard(step, ckpt_dir=str(tmp_path / "g"),
                        scaler=scaler) as g:
            g.set_cursor(0, 0)
            g.step(x, y)
            sched.step()
            sched.step()
            g.checkpoint()
        # relaunch with virgin scaler + scheduler
        paddle.seed(1)
        net2 = nn.Sequential(nn.Linear(4, 4), nn.Linear(4, 1))
        sched2 = lr_mod.StepDecay(learning_rate=0.1, step_size=2)
        opt2 = paddle.optimizer.Adam(parameters=net2.parameters(),
                                     learning_rate=sched2)
        step2 = TrainStep(net2, nn.MSELoss(), opt2, n_model_inputs=1)
        scaler2 = GradScaler(init_loss_scaling=2.0 ** 15)
        with TrainGuard(step2, ckpt_dir=str(tmp_path / "g"),
                        scaler=scaler2) as g2:
            assert g2.resume() == (0, 1)
        assert scaler2.get_loss_scaling() == 512.0
        assert scaler2._good_steps == 7 and scaler2._bad_steps == 1
        assert scaler2._found_inf is True
        assert sched2.last_epoch == sched.last_epoch
        assert opt2.get_lr() == opt.get_lr()


# ---- step watchdog ----------------------------------------------------------

class TestStepWatchdog:
    def test_injected_hang_surfaces_within_2x_deadline(self):
        """`guard.step:delay` wedges the step; the caller gets a typed
        StepStalledError with the last-known phase well within 2x the
        deadline, and the NEXT step runs on a fresh runner."""
        _, step = _make_linear_step()
        batches = _linear_batches(2)
        step(*batches[0])  # compile OUTSIDE the deadline (a cold first
        g = TrainGuard(step, config=GuardConfig(step_timeout_s=0.4,  # step
                                                snapshot_interval=0))  # is
        try:  # the auto-calibration regime's job, not this test's)
            g.set_cursor(0, 0)
            g.step(*batches[0])
            with faults.inject("guard.step:delay:delay=1.5:times=1"):
                t0 = time.monotonic()
                with pytest.raises(StepStalledError) as ei:
                    g.step(*batches[0])
                elapsed = time.monotonic() - t0
            assert elapsed < 0.8, f"stall surfaced in {elapsed}s (2x deadline)"
            assert ei.value.phase == "dispatch"
            assert ei.value.deadline_s == pytest.approx(0.4)
            # recovery: a fresh runner serves the next step
            loss = g.step(*batches[1])
            assert loss is not None and np.isfinite(loss)
        finally:
            g.close(grace_s=3.0)
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("guard-") and t.is_alive()]

    def test_auto_calibrated_deadline_from_trailing_median(self):
        wd = StepWatchdog(timeout_s=0.0, warmup_steps=3, factor=5.0,
                          min_timeout_s=0.05)
        try:
            assert wd.deadline() is None  # warmup: unarmed
            for _ in range(3):
                wd.run(time.sleep, 0.02)
            dl = wd.deadline()
            assert dl is not None and 0.05 <= dl < 0.5
            with pytest.raises(StepStalledError):
                wd.run(time.sleep, dl + 1.0)
        finally:
            wd.close(grace_s=3.0)

    def test_step_exception_propagates_and_counts(self, with_monitor):
        _, step = _make_linear_step()
        x, y = _linear_batches(1)[0]
        with TrainGuard(step, config=GuardConfig(snapshot_interval=0)) as g:
            g.set_cursor(0, 0)
            g.step(x, y)
            with faults.inject("guard.step:error:times=1"):
                with pytest.raises(faults.InjectedFault):
                    g.step(x, y)
            assert monitor.counter("guard.step_errors").get() == 1
            # the loop survives: next step is clean
            assert np.isfinite(g.step(x, y))

    def test_stale_result_from_wedged_step_is_discarded(self):
        """A wedged step that eventually completes must not leak its
        result into a later step's wait."""
        wd = StepWatchdog(timeout_s=0.15, warmup_steps=1)
        try:
            with pytest.raises(StepStalledError):
                wd.run(lambda: (time.sleep(0.4), "stale")[1])
            out = wd.run(lambda: "fresh")
            assert out == "fresh"
        finally:
            wd.close(grace_s=2.0)


# ---- divergence guard -------------------------------------------------------

class TestDivergenceGuard:
    def test_nan_step_rolls_back_and_skips(self, with_monitor):
        """Injected NaN batch: params/slots/rng restored from the rolling
        last-good snapshot, batch skipped, counters visible, and the loss
        recovers on the next clean batch."""
        _, step = _make_linear_step()
        batches = _linear_batches(4)
        g = TrainGuard(step, config=GuardConfig(snapshot_interval=1,
                                                max_bad_steps=3))
        try:
            for i, (x, y) in enumerate(batches[:3]):
                g.set_cursor(0, i)
                g.step(x, y)
            before = step.state_dict()
            xnan = paddle.to_tensor(np.full((8, 4), np.nan, "float32"))
            assert g.step(xnan, batches[0][1]) is None  # skipped
            after = step.state_dict()
            _assert_params_equal(before, after)
            assert np.array_equal(before["rng_key"], after["rng_key"])
            assert before["step_count"] == after["step_count"]
            assert monitor.counter("guard.bad_steps").get() == 1
            assert monitor.counter("guard.rollbacks").get() == 1
            assert monitor.counter("guard.steps").get() == 3
            loss = g.step(*batches[3])
            assert loss is not None and np.isfinite(loss)
        finally:
            g.close()

    def test_nan_with_traced_check_nan_inf_also_rolls_back(self):
        """FLAGS_check_nan_inf traces the finite check INTO the step and
        raises FloatingPointError after committing donated buffers — the
        guard must treat that exactly like a host-detected NaN."""
        _flags.set_flags({"check_nan_inf": True})
        try:
            _, step = _make_linear_step()
            batches = _linear_batches(2)
            with TrainGuard(step, config=GuardConfig(snapshot_interval=1,
                                                     max_bad_steps=3)) as g:
                g.set_cursor(0, 0)
                g.step(*batches[0])
                before = step.state_dict()
                xnan = paddle.to_tensor(np.full((8, 4), np.nan, "float32"))
                assert g.step(xnan, batches[0][1]) is None
                _assert_params_equal(before, step.state_dict())
        finally:
            _flags.set_flags({"check_nan_inf": False})

    def test_loss_spike_triggers_rollback(self):
        _, step = _make_linear_step()
        batches = _linear_batches(4)
        with TrainGuard(step, config=GuardConfig(snapshot_interval=1,
                                                 loss_spike_ratio=10.0,
                                                 max_bad_steps=3)) as g:
            for i, (x, y) in enumerate(batches):
                g.set_cursor(0, i)
                g.step(x, y)
            before = step.state_dict()
            xhuge = paddle.to_tensor(
                np.full((8, 4), 1e4, "float32"))  # finite but absurd
            assert g.step(xhuge, batches[0][1]) is None
            _assert_params_equal(before, step.state_dict())

    def test_diverged_after_max_consecutive_bad_steps(self):
        _, step = _make_linear_step()
        x, y = _linear_batches(1)[0]
        xnan = paddle.to_tensor(np.full((8, 4), np.nan, "float32"))
        with TrainGuard(step, config=GuardConfig(snapshot_interval=1,
                                                 max_bad_steps=3)) as g:
            g.set_cursor(0, 0)
            g.step(x, y)
            assert g.step(xnan, y) is None
            assert g.step(xnan, y) is None
            with pytest.raises(DivergedError) as ei:
                g.step(xnan, y)
        assert ei.value.bad_steps == 3
        # a good step in between resets the consecutive counter
        _, step2 = _make_linear_step()
        with TrainGuard(step2, config=GuardConfig(snapshot_interval=1,
                                                  max_bad_steps=2)) as g2:
            g2.set_cursor(0, 0)
            g2.step(x, y)
            assert g2.step(xnan, y) is None
            g2.step(x, y)  # good: resets streak
            assert g2.step(xnan, y) is None  # streak = 1 again, no raise


# ---- cross-rank desync ------------------------------------------------------

class _DictStore:
    """In-process store: the set/get surface of TCPStore over a dict."""

    def __init__(self):
        self._d = {}
        self._lock = threading.Lock()

    def set(self, key, value):
        with self._lock:
            self._d[key] = value if isinstance(value, bytes) \
                else str(value).encode()

    def get(self, key):
        with self._lock:
            return self._d[key]


class TestDesyncDetection:
    def test_in_sync_ranks_pass(self, with_monitor):
        store = _DictStore()
        arrs = {"w": np.arange(12, dtype="float32").reshape(3, 4)}
        dets = [DesyncDetector(store, r, 3, timeout_s=5.0) for r in range(3)]
        outs = [None] * 3

        def run(r):
            outs[r] = dets[r].check(1, dict(arrs))

        ts = [threading.Thread(target=run, args=(r,)) for r in range(3)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert all(len(set(o.values())) == 1 for o in outs)
        assert monitor.counter("guard.desync_checks").get() == 3

    def test_minority_rank_named_on_all_ranks(self):
        store = _DictStore()
        good = {"w": np.arange(12, dtype="float32").reshape(3, 4)}
        bad = {"w": good["w"].copy()}
        bad["w"][1, 1] = np.nextafter(bad["w"][1, 1], np.float32(99.0))
        errs = [None] * 3

        def run(r):
            det = DesyncDetector(store, r, 3, timeout_s=5.0)
            try:
                det.check(7, bad if r == 2 else good)
            except RankDesyncError as e:
                errs[r] = e

        ts = [threading.Thread(target=run, args=(r,)) for r in range(3)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        for e in errs:
            assert e is not None
            assert e.offenders == [2]
            assert e.step == 7

    def test_two_rank_tie_breaks_toward_rank0(self):
        fps = {0: 111, 1: 222}
        assert DesyncDetector._vote(fps) == [1]
        assert DesyncDetector._vote({0: 5, 1: 5}) == []

    def test_fingerprint_sensitivity(self):
        a = {"w": np.zeros(8, "float32"), "b": np.ones(3, "float32")}
        b = {"w": np.zeros(8, "float32"), "b": np.ones(3, "float32")}
        assert fingerprint(a) == fingerprint(b)
        b["w"][0] = np.float32(1e-45)  # one denormal bit of drift
        assert fingerprint(a) != fingerprint(b)
        # name changes count too (layout drift)
        c = {"w2": np.zeros(8, "float32"), "b": np.ones(3, "float32")}
        assert fingerprint(a) != fingerprint(c)

    def test_world_size_one_is_noop(self):
        det = DesyncDetector(store=None, rank=0, world_size=1)
        out = det.check(1, {"w": np.zeros(3, "float32")})
        assert set(out) == {0}

    def test_two_process_desync_names_bad_rank(self):
        from paddle_tpu import _native
        if not _native.available():
            pytest.skip("native TCPStore unavailable")
        runner = os.path.join(os.path.dirname(__file__),
                              "guard_desync_2proc_runner.py")
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "JAX_", "XLA_", "PALLAS_",
                                    "TPU_", "PYTHONPATH"))}
        procs = [subprocess.Popen(
            [sys.executable, runner, str(r), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True) for r in range(2)]
        outs = []
        for p in procs:
            try:
                out, err = p.communicate(timeout=150)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("2-process desync runner timed out")
            assert p.returncode == 0, f"runner failed:\n{err[-2000:]}"
            outs.append(json.loads(out.strip().splitlines()[-1]))
        for o in outs:
            assert o["round1"] == "ok"
            assert o["round2"] == "desync", o
            assert o["offenders"] == [1], o  # rank 1 diverged, rank 1 named
            assert o["step"] == 2


# ---- crash-atomic guard checkpoints ----------------------------------------

class TestGuardCheckpointAtomicity:
    def test_crash_between_payload_and_commit_keeps_previous(self, tmp_path):
        d = str(tmp_path / "g")
        save_guard_state(d, {"w": np.arange(4, dtype="float32")},
                         {"gen": 1})
        with faults.inject("guard.snapshot:error:times=1"):
            with pytest.raises(faults.InjectedFault):
                save_guard_state(d, {"w": np.full(4, 9.0, "float32")},
                                 {"gen": 2})
        arrays, meta = load_guard_state(d)
        assert meta["gen"] == 1  # commit record still points at gen 1
        np.testing.assert_array_equal(arrays["w"],
                                      np.arange(4, dtype="float32"))

    def test_torn_payload_falls_back_to_previous_generation(
            self, tmp_path, with_monitor):
        d = str(tmp_path / "g")
        save_guard_state(d, {"w": np.arange(4, dtype="float32")},
                         {"gen": 1})
        with faults.inject("guard.snapshot.write:torn:times=1"):
            save_guard_state(d, {"w": np.full(4, 9.0, "float32")},
                             {"gen": 2})  # commits, but payload is torn
        with pytest.warns(UserWarning, match="falling back"):
            arrays, meta = load_guard_state(d)
        assert meta["gen"] == 1
        np.testing.assert_array_equal(arrays["w"],
                                      np.arange(4, dtype="float32"))
        assert monitor.counter("guard.ckpt_fallbacks").get() == 1

    def test_bfloat16_round_trips(self, tmp_path):
        import ml_dtypes
        d = str(tmp_path / "g")
        w = np.arange(6).astype(ml_dtypes.bfloat16)
        save_guard_state(d, {"w": w}, {})
        arrays, _ = load_guard_state(d)
        assert arrays["w"].dtype == np.dtype(ml_dtypes.bfloat16)
        np.testing.assert_array_equal(arrays["w"], w)


# ---- hapi integration + satellites ------------------------------------------

class TestHapiIntegration:
    def _fit_once(self, ckpt_dir, preempt_at=None, epochs=2):
        from paddle_tpu.hapi.model import Model
        paddle.seed(0)
        np.random.seed(0)
        net = LeNetSmall()
        model = Model(net)
        opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                    learning_rate=2e-3)
        model.prepare(optimizer=opt, loss=nn.CrossEntropyLoss())
        rng = np.random.RandomState(0)
        xs = rng.rand(12, 1, 16, 16).astype("float32")
        ys = rng.randint(0, 4, (12,)).astype("int64")
        data = [(xs[i], ys[i]) for i in range(12)]
        guard = TrainGuard(model._train_step, ckpt_dir=ckpt_dir,
                           config=GuardConfig(snapshot_interval=0))
        killer = None
        if preempt_at is not None:
            calls = {"n": 0}
            orig = guard.step

            def counting_step(*b):
                calls["n"] += 1
                if calls["n"] == preempt_at:
                    os.kill(os.getpid(), signal.SIGTERM)
                return orig(*b)

            guard.step = counting_step
            killer = calls
        try:
            guard.install_signal_handlers()
            guard.resume()
            model.fit(data, batch_size=4, epochs=epochs, shuffle=False,
                      verbose=0, guard=guard)
        finally:
            guard.close()
        return model._train_step.state_dict(), killer

    def test_fit_with_guard_resumes_bit_identical(self, tmp_path):
        final_a, _ = self._fit_once(None)
        with pytest.raises(PreemptedError):
            self._fit_once(str(tmp_path / "g"), preempt_at=4)
        final_b, _ = self._fit_once(str(tmp_path / "g"))
        _assert_params_equal(final_a, final_b)
        assert np.array_equal(final_a["rng_key"], final_b["rng_key"])

    def test_fit_guard_requires_prepared_train_step(self):
        from paddle_tpu.hapi.model import Model
        model = Model(nn.Linear(2, 2))
        _, step = _make_linear_step()
        with TrainGuard(step) as g:
            with pytest.raises(ValueError, match="prepare"):
                model.fit([(np.zeros(2, "float32"),)], guard=g)


class TestSatellites:
    def test_model_save_is_crash_atomic(self, tmp_path, monkeypatch):
        """hapi save path commits through sharded_io's tmp+fsync+rename —
        the committed name either holds the full payload or the previous
        one, and no .tmp residue survives."""
        import paddle_tpu.framework.io as fio
        from paddle_tpu.framework import sharded_io
        calls = []
        real = sharded_io.atomic_write

        def spy(path, data):
            calls.append(path)
            real(path, data)

        monkeypatch.setattr(sharded_io, "atomic_write", spy)
        path = str(tmp_path / "m.pdparams")
        with open(path, "wb") as f:
            f.write(b"previous generation")
        state = {"w": paddle.to_tensor(np.ones((2, 2), "float32"))}
        fio.save(state, path)
        assert calls == [path]
        assert not os.path.exists(path + ".tmp")
        loaded = fio.load(path, return_numpy=True)
        np.testing.assert_array_equal(loaded["w"], np.ones((2, 2)))

    def test_grad_scaler_state_round_trips_streaks(self):
        from paddle_tpu.amp import GradScaler
        s = GradScaler(init_loss_scaling=1024.0, incr_every_n_steps=4,
                       decr_every_n_nan_or_inf=2)
        s._good_steps, s._bad_steps, s._found_inf = 3, 1, True
        sd = s.state_dict()
        s2 = GradScaler()
        s2.load_state_dict(sd)
        assert s2.get_loss_scaling() == 1024.0
        assert s2._good_steps == 3 and s2._bad_steps == 1
        assert s2._found_inf is True
        # the restored streak continues exactly: one more inf -> shrink
        s2._decr_every = 2
        s2._found_inf = True
        s2.update()
        assert s2.get_loss_scaling() == 512.0

    def test_grad_scaler_emits_amp_counters(self, with_monitor):
        from paddle_tpu.amp import GradScaler
        from paddle_tpu.core.tensor import Parameter
        import jax.numpy as jnp
        p = Parameter(jnp.ones((2,)), name="p")
        p.grad = jnp.asarray(np.array([np.inf, 1.0], "float32"))
        opt = paddle.optimizer.SGD(parameters=[p], learning_rate=0.1)
        s = GradScaler(init_loss_scaling=4.0, decr_every_n_nan_or_inf=1)
        s.unscale_(opt)
        s.step(opt)  # found_inf: skip + shrink
        assert monitor.counter("amp.skipped_steps").get() == 1
        assert monitor.counter("amp.scale_updates").get() == 1

    def test_early_stopping_nan_is_strict_regression(self):
        from paddle_tpu.hapi.callbacks import EarlyStopping

        class _M:
            stop_training = False

        es = EarlyStopping(monitor="loss", patience=0)
        es.set_model(_M())
        es.on_eval_end({"loss": float("nan")})
        assert es.stopped and es.model.stop_training
        # NaN is never adopted as `best`
        m2 = _M()
        es2 = EarlyStopping(monitor="loss", patience=2)
        es2.set_model(m2)
        es2.on_eval_end({"loss": float("nan")})
        assert es2.best is None and es2.wait == 1
        es2.on_eval_end({"loss": 1.0})
        assert es2.best == 1.0 and es2.wait == 0
        es2.on_eval_end({"loss": float("inf")})
        assert es2.best == 1.0 and es2.wait == 1


# ---- counters visibility ----------------------------------------------------

class TestGuardObservability:
    def test_recoveries_visible_via_guard_counters(self, with_monitor):
        _, step = _make_linear_step()
        batches = _linear_batches(3)
        xnan = paddle.to_tensor(np.full((8, 4), np.nan, "float32"))
        with TrainGuard(step, config=GuardConfig(snapshot_interval=1,
                                                 max_bad_steps=5)) as g:
            for i, (x, y) in enumerate(batches):
                g.set_cursor(0, i)
                g.step(x, y)
            g.step(xnan, batches[0][1])
        snap = monitor.snapshot()["counters"]
        assert snap["guard.steps"] == 3
        assert snap["guard.bad_steps"] == 1
        assert snap["guard.rollbacks"] == 1
        assert snap["guard.snapshots"] >= 3

    def test_checkpoint_and_resume_counters(self, tmp_path, with_monitor):
        _, step = _make_linear_step()
        x, y = _linear_batches(1)[0]
        with TrainGuard(step, ckpt_dir=str(tmp_path / "g"),
                        config=GuardConfig(snapshot_interval=0)) as g:
            g.set_cursor(0, 0)
            g.step(x, y)
            g.checkpoint()
        _, step2 = _make_linear_step()
        with TrainGuard(step2, ckpt_dir=str(tmp_path / "g")) as g2:
            g2.resume()
        snap = monitor.snapshot()["counters"]
        assert snap["guard.checkpoints"] == 1
        assert snap["guard.resumes"] == 1


# ---- multi-step preemption soak (slow) --------------------------------------

@pytest.mark.slow
def test_preemption_soak_every_interrupt_point_bit_identical(tmp_path):
    """Interrupt at EVERY step index of a 2-epoch LeNet run, resume each
    time: all interrupted timelines converge to the uninterrupted params."""
    batches = _lenet_batches(3)
    _, step_ref = _make_lenet_step()
    with TrainGuard(step_ref, config=GuardConfig(snapshot_interval=0)) as g:
        _run_guarded_epochs(g, batches, epochs=2)
    ref = step_ref.state_dict()
    n_steps = 2 * len(batches)
    for kill_at in range(1, n_steps):
        ckpt = str(tmp_path / f"g{kill_at}")
        _, step_b = _make_lenet_step()
        with TrainGuard(step_b, ckpt_dir=ckpt,
                        config=GuardConfig(snapshot_interval=0)) as gb:
            with pytest.raises(PreemptedError):
                n = 0
                for epoch in range(2):
                    for b, (x, y) in enumerate(batches):
                        gb.set_cursor(epoch, b)
                        n += 1
                        if n == kill_at:
                            os.kill(os.getpid(), signal.SIGTERM)
                        gb.step(x, y)
        _, step_c = _make_lenet_step(seed=kill_at)
        with TrainGuard(step_c, ckpt_dir=ckpt,
                        config=GuardConfig(snapshot_interval=0)) as gc:
            start = gc.resume()
            _run_guarded_epochs(gc, batches, epochs=2, start=start)
        _assert_params_equal(ref, step_c.state_dict())
