"""Test harness: force an 8-device virtual CPU mesh so sharding/collective
tests run without TPU hardware (SURVEY.md §4 test strategy). Tests run on
the CPU; the chip is reached only through the chip tool (chip_smoke.py).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert jax.default_backend() == "cpu", f"tests must run on CPU, got {jax.default_backend()}"
assert jax.device_count() == 8, f"expected 8 virtual CPU devices, got {jax.device_count()}"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: soak/long-concurrency tests carry the
    # marker and only run in the full suite
    config.addinivalue_line(
        "markers", "slow: long-running soak tests, deselected in tier-1")
    config.addinivalue_line(
        "markers", "chaos: fault-injection tests (paddle_tpu.faults); "
        "auto-applied to everything in test_faults.py")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) == "test_faults.py":
            item.add_marker(pytest.mark.chaos)


@pytest.fixture(autouse=True)
def _seed_all():
    np.random.seed(0)
    import paddle_tpu as paddle
    paddle.seed(0)
    yield


@pytest.fixture(autouse=True)
def _no_guard_leak():
    """The guard plane installs SIGTERM/SIGINT handlers and spawns
    `guard-*` watchdog runner threads; either leaking out of a test would
    corrupt every later test (a stray handler swallows ctrl-C / pytest's
    own teardown signals, a wedged runner pins the interpreter). Assert
    both are back to their pre-test state — and restore the handlers, so
    one offender cannot cascade."""
    import signal
    import threading
    before = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    yield
    leaked = {s: signal.getsignal(s) for s in before
              if signal.getsignal(s) is not before[s]}
    for s, _ in leaked.items():
        signal.signal(s, before[s])
    guard_threads = [t.name for t in threading.enumerate()
                     if t.name.startswith("guard-") and t.is_alive()]
    assert not leaked, (
        f"guard signal handlers leaked out of the test: {sorted(leaked)} "
        f"(TrainGuard.close()/restore_signal_handlers() not called?)")
    assert not guard_threads, (
        f"guard watchdog threads leaked out of the test: {guard_threads} "
        f"(StepWatchdog.close() not called, or a step is still wedged?)")


@pytest.fixture(autouse=True)
def _no_fault_leak():
    """An injection spec leaking out of one test would fail arbitrary
    later tests with injected resets — assert FLAGS_fault_inject and the
    programmatic registry are back to their pre-test state after EVERY
    test (and restore them, so one offender cannot cascade)."""
    from paddle_tpu import faults
    from paddle_tpu.core import flags as _flags
    flag_before = _flags.flag("fault_inject")
    active_before = faults.active()
    yield
    flag_after = _flags.flag("fault_inject")
    active_after = faults.active()
    if flag_after != flag_before:
        _flags.set_flags({"fault_inject": flag_before})
    if active_after != active_before:
        faults.clear(flag_specs=False, programmatic=True)
        if flag_before:
            _flags.set_flags({"fault_inject": flag_before})
    assert flag_after == flag_before, (
        f"FLAGS_fault_inject leaked out of the test: {flag_after!r} "
        f"(was {flag_before!r})")
    assert active_after == active_before, (
        f"fault specs leaked out of the test: {active_after} "
        f"(was {active_before})")


def _reap_autoscaler(errors):
    """A leaked autoscaler keeps its control loop scaling a dead fleet —
    and holds every ReplicaAgent its pool spawned. Reaped FIRST: close()
    also stops the pool's spawned handles, so the fleet/telemetry planes
    below see a quiet world."""
    from paddle_tpu.serving import autoscaler as _autoscaler
    leaked = [a for a in list(_autoscaler._LIVE)
              if not getattr(a, "_closed", True)]
    for a in leaked:
        try:
            a.close()
        except Exception:
            pass
    if leaked:
        errors.append(
            f"{len(leaked)} autoscaler(s) leaked out of the test "
            f"(Autoscaler.close() never reached): "
            f"{[type(o).__name__ for o in leaked]}")


def _reap_fleet(errors):
    """A fleet router or replica agent leaking out of a test keeps its
    health/heartbeat/watcher threads probing dead endpoints under every
    later test."""
    from paddle_tpu.serving import fleet as _fleet
    from paddle_tpu.serving import online as _online
    leaked = [obj for obj in list(_fleet._LIVE)
              if not getattr(obj, "_closed", True)]
    leaked += [g for g in list(_online._LIVE)
               if g._thread is not None and g._thread.is_alive()]
    for obj in leaked:
        try:
            obj.close() if hasattr(obj, "close") else obj.stop(drain=False)
        except Exception:
            pass
    if leaked:
        errors.append(
            f"{len(leaked)} fleet object(s) leaked out of the test "
            f"(router.close()/agent.stop() never reached): "
            f"{[type(o).__name__ for o in leaked]}")


def _reap_telemetry(errors):
    """A leaked exporter keeps pushing this process's metrics (and holds
    the module-default slot) under every later test; a leaked collector
    keeps its accept/conn/reap threads and the rendezvous record alive."""
    from paddle_tpu.obs import telemetry as _telemetry
    leaked = [obj for obj in list(_telemetry._LIVE)
              if getattr(obj, "_thread", None) is not None
              or getattr(obj, "_listener", None) is not None]
    for obj in leaked:
        try:
            obj.stop()
        except Exception:
            pass
    if _telemetry._DEFAULT is not None:
        _telemetry._DEFAULT = None
    if leaked:
        errors.append(
            f"{len(leaked)} telemetry object(s) leaked out of the test "
            f"(exporter.stop()/collector.stop() never reached): "
            f"{[type(o).__name__ for o in leaked]}")


def _reap_ps(errors):
    """A PS server, HA node, or WAL writer leaking out of a test keeps
    accept/replication/communicator threads (and an open WAL segment)
    alive under every later test."""
    from paddle_tpu.distributed.ps import delta as _ps_delta
    from paddle_tpu.distributed.ps import ha as _ps_ha
    from paddle_tpu.distributed.ps import service as _ps_service
    from paddle_tpu.distributed.ps import wal as _ps_wal
    leaked = [n for n in list(_ps_ha._LIVE)
              if not getattr(n, "_closed", True)]
    leaked += [s for s in list(_ps_service._LIVE)
               if not getattr(s, "_closed", True)
               and not s._stop.is_set()]
    leaked += [w for w in list(_ps_wal._LIVE_WRITERS) if not w.closed]
    leaked += [d for d in list(_ps_delta._LIVE)
               if d._thread is not None and d._thread.is_alive()]
    for obj in leaked:
        try:
            obj.stop() if hasattr(obj, "stop") else obj.close()
        except Exception:
            pass
    if leaked:
        errors.append(
            f"{len(leaked)} PS object(s) leaked out of the test "
            f"(server.stop()/node.stop()/writer.close() never reached): "
            f"{[type(o).__name__ for o in leaked]}")


def _check_lazy(errors, flag_before):
    """A pending lazy segment (FLAGS_lazy_eager, ops/lazy.py) leaking out
    of a test would materialize inside some unrelated later test — or
    worse, leave the flag on so every later test runs deferred."""
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.ops import lazy as _lazy
    flag_after = _flags.flag("lazy_eager")
    pending = _lazy.pending_ops()
    if pending:
        _lazy.flush_pending()
        errors.append(
            f"{pending} deferred op(s) leaked out of the test "
            "(paddle.sync() / flush_pending() not reached?)")
    if flag_after != flag_before:
        _flags.set_flags({"lazy_eager": flag_before})
        errors.append(
            f"FLAGS_lazy_eager leaked out of the test: {flag_after!r} "
            f"(was {flag_before!r})")


def _check_obs(errors):
    """An enabled obs plane leaking out of a test would add a
    block_until_ready fence to every later jitted step."""
    from paddle_tpu import obs as _obs
    from paddle_tpu.core import flags as _flags
    leaked = [n for n in ("obs_timeline", "obs_flight_recorder")
              if _flags.flag(n)]
    if leaked:
        _flags.set_flags({n: False for n in leaked})
        _obs.reset()
        errors.append(f"obs flags leaked out of the test: {leaked}")


@pytest.fixture(autouse=True)
def _no_thread_leak():
    """ONE teardown for every threaded plane (ISSUE 20): the per-plane
    `_no_{autoscaler,fleet,telemetry,ps,lazy,obs}_leak` fixtures unified
    onto the syncwatch ThreadRegistry. Every plane reaps its leftovers
    FIRST (so one offender cannot cascade into later tests) with its
    original assert message preserved; then the registry — which every
    paddle_tpu thread now spawns through (`syncwatch.Thread`, lint rule
    `unregistered-thread`) — polls for quiescence and names any still-live
    thread by owner module + spawn stack, which the old name-list checks
    never could."""
    import time
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.utils import syncwatch as _syncwatch
    lazy_flag_before = _flags.flag("lazy_eager")
    before = {r["ident"] for r in _syncwatch.live_threads()}
    yield
    errors = []
    # reap order matters: the autoscaler's close() stops the agents its
    # pool spawned, so it runs before the fleet/telemetry checks
    _reap_autoscaler(errors)
    _reap_fleet(errors)
    _reap_telemetry(errors)
    _reap_ps(errors)
    _check_lazy(errors, lazy_flag_before)
    _check_obs(errors)
    for _ in range(20):  # reaped threads need a beat to exit
        live = [r for r in _syncwatch.live_threads()
                if r["ident"] not in before]
        if not live:
            break
        time.sleep(0.1)
    for r in live:
        spawned = "".join(r.get("spawned") or ["  <no spawn stack>\n"])
        errors.append(
            f"thread {r['name']!r} (owner {r['owner']}) leaked out of "
            f"the test; spawned at:\n{spawned}")
    assert not errors, "\n".join(errors)


@pytest.fixture(autouse=True)
def _no_trace_leak():
    """An unclosed request span leaking out of a test would (a) pin its
    trace in the buffer's open-set forever and (b) leave a stale span on
    the thread stack so an unrelated later test's spans parent under it.
    Assert the tracing plane is idle and FLAGS_trace is back to its
    pre-test state after EVERY test (and restore, so one offender cannot
    cascade)."""
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.obs import trace as _trace
    flag_before = _flags.flag("trace")
    depth_before = _trace.active_depth()
    yield
    flag_after = _flags.flag("trace")
    depth_after = _trace.active_depth()
    if flag_after != flag_before:
        _flags.set_flags({"trace": flag_before})
    if depth_after != depth_before:
        _trace.reset()
    assert flag_after == flag_before, (
        f"FLAGS_trace leaked out of the test: {flag_after!r} "
        f"(was {flag_before!r})")
    assert depth_after == depth_before, (
        f"{depth_after - depth_before} open span(s) leaked out of the "
        "test (Span.end() never reached — error path missing a close?)")


