"""Profiler tests: host-event collection, statistics report, chrome export.

Reference: profiler.py scheduler states + profiler_statistic.py report +
chrometracing_logger.cc artifact."""
import json
import os

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.profiler import (Profiler, ProfilerState, RecordEvent,
                                 load_profiler_result, make_scheduler)


def test_scheduler_states():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1)
    states = [sched(i) for i in range(4)]
    assert states == [ProfilerState.CLOSED, ProfilerState.READY,
                      ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN]
    assert sched(10) == ProfilerState.CLOSED  # repeat exhausted


def test_scheduler_skip_first():
    sched = make_scheduler(closed=1, ready=1, record=1, skip_first=3)
    # the first skip_first steps are CLOSED regardless of cycle position
    assert [sched(i) for i in range(3)] == [ProfilerState.CLOSED] * 3
    assert [sched(i) for i in range(3, 6)] == [
        ProfilerState.CLOSED, ProfilerState.READY,
        ProfilerState.RECORD_AND_RETURN]


def test_scheduler_repeat_cycles():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=2)
    cycle = [ProfilerState.CLOSED, ProfilerState.READY,
             ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN]
    assert [sched(i) for i in range(8)] == cycle * 2
    # after `repeat` full cycles the scheduler stays CLOSED forever
    assert all(sched(i) == ProfilerState.CLOSED for i in range(8, 16))


def test_scheduler_unbounded_when_repeat_zero():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=0)
    cycle = [ProfilerState.CLOSED, ProfilerState.READY,
             ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN]
    assert [sched(i) for i in range(12)] == cycle * 3


def test_op_events_and_summary():
    x = paddle.to_tensor(np.random.rand(8, 8).astype("float32"))
    with Profiler(timer_only=True) as prof:
        for _ in range(3):
            y = paddle.matmul(x, x)
            paddle.tanh(y)
        with RecordEvent("my_region"):
            paddle.add(x, x)
        prof.step()
    names = {e.name for e in prof.events()}
    assert "matmul" in names and "my_region" in names
    rep = prof.summary()
    assert "matmul" in rep and "Calls" in rep and "Ratio" in rep
    # matmul ran 3 times
    assert sum(1 for e in prof.events() if e.name == "matmul") == 3


def test_chrome_export_roundtrip(tmp_path):
    x = paddle.to_tensor(np.random.rand(4).astype("float32"))
    with Profiler(timer_only=True) as prof:
        paddle.exp(x)
    p = str(tmp_path / "trace.json")
    prof.export(p)
    data = load_profiler_result(p)
    assert any(ev["name"] == "exp" for ev in data["traceEvents"])
    # host spans are complete events; the monitor plane rides along as ONE
    # metadata event (ph "M") carrying the counter snapshot
    assert all(ev["ph"] in ("X", "M") for ev in data["traceEvents"])
    assert sum(ev["ph"] == "M" for ev in data["traceEvents"]) == 1


def test_hook_removed_after_stop():
    from paddle_tpu.ops import _dispatch
    with Profiler(timer_only=True):
        pass
    assert _dispatch._PROFILE_HOOK is None


def test_summary_renders_min_column():
    x = paddle.to_tensor(np.random.rand(8, 8).astype("float32"))
    with Profiler(timer_only=True) as prof:
        for _ in range(3):
            paddle.tanh(x)
    rep = prof.summary()
    header = [ln for ln in rep.splitlines() if "Calls" in ln][0]
    assert "Min" in header and "Max" in header
    # Min column sits between Avg and Max, matching value order per row
    assert header.index("Avg") < header.index("Min") < header.index("Max")


def test_nested_profilers_chain_and_out_of_order_stop():
    """Out-of-order stop of nested profilers must not clobber the inner
    hook; while both are active, BOTH observe ops (stack discipline)."""
    from paddle_tpu.ops import _dispatch
    x = paddle.to_tensor(np.random.rand(4).astype("float32"))
    outer = Profiler(timer_only=True).start()
    inner = Profiler(timer_only=True).start()
    paddle.exp(x)
    outer.stop()          # OUT OF ORDER: inner must keep observing
    paddle.tanh(x)
    inner.stop()
    assert _dispatch._PROFILE_HOOK is None
    inner_names = {e.name for e in inner.events()}
    outer_names = {e.name for e in outer.events()}
    assert {"exp", "tanh"} <= inner_names
    assert "exp" in outer_names and "tanh" not in outer_names


def test_on_trace_ready_called_once_at_stop(tmp_path):
    """The handler runs when the trace is READY (stop), not at __init__;
    export_chrome_tracing's dir still takes effect."""
    calls = []

    def handler(prof):
        calls.append(prof)

    prof = Profiler(timer_only=True, on_trace_ready=handler)
    assert calls == []                    # not invoked at construction
    prof.start()
    assert calls == []
    prof.stop()
    assert calls == [prof]                # exactly once, at trace-ready

    from paddle_tpu.profiler import export_chrome_tracing
    d = str(tmp_path / "trace_dir")
    p2 = Profiler(timer_only=True,
                  on_trace_ready=export_chrome_tracing(d))
    assert p2._export_dir == d            # dir seeded without calling
    with p2:
        pass
    assert p2._export_dir == d


def test_no_directory_means_a_fresh_temp_directory(tmp_path, monkeypatch):
    """A Profiler given no directory writes its device trace under the
    temp directory, never into the working tree (ROADMAP D13)."""
    import shutil
    import tempfile
    monkeypatch.chdir(tmp_path)
    prof = Profiler()
    assert prof._export_dir is None
    with prof:
        pass
    try:
        assert os.path.isdir(prof._export_dir)
        assert os.path.dirname(prof._export_dir) == tempfile.gettempdir()
        assert os.listdir(tmp_path) == []
    finally:
        shutil.rmtree(prof._export_dir, ignore_errors=True)


class TestDeviceMemory:
    def test_memory_stats_surface(self):
        import paddle_tpu as paddle
        import numpy as np
        paddle.device.reset_max_memory_allocated()
        base = paddle.device.memory_allocated()
        keep = paddle.to_tensor(np.ones((256, 1024), "float32"))  # 1 MB
        stats = paddle.device.memory_stats()
        assert stats["allocated.current"] >= base + 1_000_000
        assert paddle.device.max_memory_allocated() >= stats["allocated.current"]
        assert paddle.device.device_count() >= 1
        assert ":" in paddle.device.get_device()
        del keep

    def test_peak_is_monotonic_until_reset(self):
        import paddle_tpu as paddle
        import numpy as np
        paddle.device.reset_max_memory_allocated()
        t = paddle.to_tensor(np.ones((512, 1024), "float32"))  # 2 MB
        peak_with = paddle.device.max_memory_allocated()
        del t
        assert paddle.device.max_memory_allocated() >= peak_with
        paddle.device.reset_max_memory_allocated()
        assert paddle.device.max_memory_allocated() <= peak_with

    def test_per_device_peaks_and_sharded_accounting(self):
        import paddle_tpu as paddle
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.parallel import create_mesh
        mesh = create_mesh({"dp": 8})
        import gc
        gc.collect()
        paddle.device.reset_max_memory_allocated(0)
        paddle.device.reset_max_memory_allocated(1)
        # delta-based: earlier tests in a long run may hold live arrays on
        # these devices, so absolute bounds are order-dependent flakes
        base0 = paddle.device.memory_allocated(0)
        base1 = paddle.device.max_memory_allocated(1)
        big = jax.device_put(jnp.ones((8, 1024, 128), jnp.float32),
                             NamedSharding(mesh, P("dp")))   # 4MB over 8
        s0 = paddle.device.memory_allocated(0) - base0
        # each device holds ~1/8 of the array, not the whole 4MB
        assert s0 < 2_000_000, s0
        # device-1 peak must not inherit device-0 allocations
        only0 = jax.device_put(jnp.ones((1024, 1024), jnp.float32),
                               jax.devices()[0])             # 4MB on dev 0
        _ = paddle.device.memory_stats(0)
        p1 = paddle.device.max_memory_allocated(1) - base1
        assert p1 < 3_000_000, p1
        del big, only0
