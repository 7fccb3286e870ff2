"""paddle_tpu.monitor tests: stats registry, spans, retrace accounting,
exporters, the profiler merge, and the FLAGS_monitor=0 overhead guard.

Reference roles: platform/monitor.h (STAT registry),
platform/profiler/event_tracing.h (spans), profiler_statistic.py (report).
"""
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import monitor


@pytest.fixture()
def monitored():
    """Enable FLAGS_monitor on a clean registry; always restore."""
    monitor.reset()
    paddle.set_flags({"FLAGS_monitor": True})
    try:
        yield
    finally:
        paddle.set_flags({"FLAGS_monitor": False})
        monitor.reset()


def _mse(out, lbl):
    return ((out - lbl) ** 2).mean()


class TestRegistry:
    def test_counter_gauge_histogram(self, monitored):
        monitor.count("x.count", 2)
        monitor.count("x.count")
        monitor.gauge_set("x.depth", 7)
        for v in (0.5e-3, 2e-3, 4e-3):
            monitor.observe("x.dur", v)
        snap = monitor.snapshot()
        assert snap["counters"]["x.count"] == 3
        assert snap["gauges"]["x.depth"] == 7
        h = snap["histograms"]["x.dur"]
        assert h["count"] == 3
        assert h["min"] == pytest.approx(0.5e-3)
        assert h["max"] == pytest.approx(4e-3)
        assert abs(h["sum"] - 6.5e-3) < 1e-9
        # cumulative buckets: everything <= 1e-2
        assert h["buckets"][1e-2] == 3
        assert h["buckets"][1e-3] == 1

    def test_thread_safety_counter(self, monitored):
        import threading
        c = monitor.counter("race")

        def bump():
            for _ in range(1000):
                c.add(1)

        ts = [threading.Thread(target=bump) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.get() == 8000

    def test_reset_and_flag_sync(self):
        paddle.set_flags({"FLAGS_monitor": True})
        assert monitor.enabled() and monitor._ENABLED
        monitor.count("tmp")
        monitor.reset()
        assert monitor.snapshot()["counters"].get("tmp", 0) == 0
        paddle.set_flags({"FLAGS_monitor": False})
        assert not monitor.enabled() and not monitor._ENABLED

    def test_event_ring_bounded(self, monitored):
        for i in range(400):
            monitor.log_event("e", i=i)
        evs = monitor.events()
        assert len(evs) == 256          # ring cap
        assert evs[-1]["i"] == 399


class TestDispatchPlane:
    def test_op_counts_and_durations(self, monitored):
        x = paddle.to_tensor(np.random.rand(8, 8).astype("float32"))
        for _ in range(3):
            paddle.matmul(x, x)
        snap = monitor.snapshot()
        assert snap["counters"]["dispatch.op.matmul"] == 3
        assert snap["counters"]["dispatch.op_count"] >= 3
        assert snap["histograms"]["dispatch.dur.matmul"]["count"] == 3

    def test_backward_walk_counts(self, monitored):
        p = paddle.to_tensor(np.ones((4,), "float32"), stop_gradient=False)
        ((p * p).sum()).backward()
        snap = monitor.snapshot()
        assert snap["counters"]["autograd.backward_count"] == 1
        assert snap["counters"]["autograd.nodes_walked"] >= 2
        assert snap["histograms"]["autograd.backward_dur"]["count"] == 1

    def test_optimizer_step_timing(self, monitored):
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(parameters=net.parameters())
        x = paddle.to_tensor(np.random.rand(8, 4).astype("float32"))
        net(x).mean().backward()
        opt.step()
        snap = monitor.snapshot()
        assert snap["counters"]["optimizer.steps"] == 1
        assert snap["histograms"]["optimizer.step_dur"]["count"] == 1


class TestSpans:
    def test_span_records_and_feeds_profiler(self, monitored):
        from paddle_tpu.profiler import Profiler
        with Profiler(timer_only=True) as prof:
            with monitor.span("stage_a"):
                time.sleep(0.001)
        snap = monitor.snapshot()
        assert snap["counters"]["span.stage_a.count"] == 1
        assert snap["histograms"]["span.stage_a.dur"]["min"] > 0
        # the span landed on the profiler's host-event stream too
        assert any(e.name == "stage_a" and e.kind == "span"
                   for e in prof.events())

    def test_span_disabled_is_noop(self):
        paddle.set_flags({"FLAGS_monitor": False})
        s1 = monitor.span("z")
        s2 = monitor.span("z")
        assert s1 is s2                 # shared null context, no allocation
        with s1:
            pass
        assert "span.z.count" not in monitor.snapshot()["counters"]


class TestJitRetrace:
    def test_train_step_loop_with_shape_change(self, monitored):
        """Acceptance scenario: a 3-step jit.train_step loop with one
        mid-loop shape change -> op counts, >=1 collective byte counter,
        and EXACTLY one retrace recorded with the offending signature."""
        paddle.seed(0)
        net = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(parameters=net.parameters())
        step = paddle.jit.TrainStep(net, _mse, opt)
        xa = paddle.to_tensor(np.random.rand(16, 8).astype("float32"))
        ya = paddle.to_tensor(np.random.rand(16, 4).astype("float32"))
        step(xa, ya)
        step(xa, ya)                     # same signature: cached
        xb = paddle.to_tensor(np.random.rand(32, 8).astype("float32"))
        yb = paddle.to_tensor(np.random.rand(32, 4).astype("float32"))
        step(xb, yb)                     # mid-loop shape change: RETRACE
        # an eager op + a collective ride along (2-device-mesh stand-in:
        # eager single-controller regime; bytes = logical payload)
        import paddle_tpu.distributed as dist
        t = paddle.to_tensor(np.ones((8, 8), "float32"))
        dist.all_reduce(t)

        snap = monitor.snapshot()
        assert snap["counters"]["jit.train_step.traces"] == 1
        assert snap["counters"]["jit.train_step.retraces"] == 1
        assert snap["counters"]["jit.train_step.steps"] == 3
        assert snap["counters"]["dispatch.op_count"] >= 1
        assert snap["counters"]["collective.bytes"] >= 8 * 8 * 4
        assert snap["counters"]["collective.c_allreduce.count"] == 1
        retraces = [e for e in snap["events"] if e["event"] == "jit.retrace"]
        assert len(retraces) == 1
        assert retraces[0]["kind"] == "train_step"
        assert any("32" in s for s in retraces[0]["signature"])

    def test_to_static_retrace_counter(self, monitored):
        @paddle.jit.to_static
        def f(x):
            return x * 2 + 1

        f(paddle.to_tensor(np.ones((4,), "float32")))
        f(paddle.to_tensor(np.ones((4,), "float32")))   # cached
        f(paddle.to_tensor(np.ones((6,), "float32")))   # retrace
        snap = monitor.snapshot()
        assert snap["counters"]["jit.to_static.traces"] == 1
        assert snap["counters"]["jit.to_static.retraces"] == 1

    def test_retrace_counter_exactly_once_eager_train(self, monitored):
        """Retrace counter increments exactly once when the input shape
        changes once across a small eager train loop."""
        paddle.seed(0)
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(parameters=net.parameters())
        step = paddle.jit.TrainStep(net, _mse, opt)
        for n in (8, 8, 16, 16, 16):
            x = paddle.to_tensor(np.random.rand(n, 4).astype("float32"))
            y = paddle.to_tensor(np.random.rand(n, 2).astype("float32"))
            step(x, y)
        assert monitor.snapshot()["counters"]["jit.train_step.retraces"] == 1


class TestCollectivePlane:
    def test_spmd_collective_bytes_on_mesh(self, monitored):
        """Byte accounting inside a real shard_map SPMD region (2-device
        submesh of the 8-device virtual CPU mesh)."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        import paddle_tpu.distributed as dist
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

        def body(x):
            t = paddle.Tensor(x)
            return dist.all_reduce(t)._value

        f = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                      check_vma=False)
        out = f(jnp.ones((4, 8), jnp.float32))
        np.testing.assert_allclose(np.asarray(out), 2.0)
        snap = monitor.snapshot()
        assert snap["counters"]["collective.c_allreduce.count"] >= 1
        # per-shard payload is [2, 8] f32 = 64 bytes
        assert snap["counters"]["collective.bytes"] >= 64

    def test_fleet_executor_message_gauges(self, monitored):
        from paddle_tpu.distributed.fleet_executor import FleetExecutor
        exe = FleetExecutor([lambda x: x + 1, lambda x: x * 2])
        outs = exe.run([np.float32(i) for i in range(4)])
        assert [float(o) for o in outs] == [2.0, 4.0, 6.0, 8.0]
        snap = monitor.snapshot()
        assert snap["counters"]["fleet.msg.data"] >= 8   # 4 in + 4 forwarded
        assert snap["counters"]["fleet.msg.credit"] >= 4
        assert any(k.startswith("fleet.inbox_depth.")
                   for k in snap["gauges"])

    def test_dataloader_queue_wait_histogram(self, monitored):
        from paddle_tpu.io import DataLoader

        class DS:
            def __len__(self):
                return 16

            def __getitem__(self, i):
                return np.full((4,), i, "float32")

        loader = DataLoader(DS(), batch_size=4, num_workers=1,
                            use_buffer_reader=False)
        batches = list(loader)
        assert len(batches) == 4
        h = monitor.snapshot()["histograms"]["io.dataloader.queue_wait"]
        assert h["count"] >= 1


class TestExporters:
    def test_report_renders_all_sections(self, monitored):
        monitor.count("a.ops", 5)
        monitor.gauge_set("a.depth", 3)
        monitor.observe("a.dur", 1e-3)
        rep = monitor.report()
        assert "a.ops" in rep and "a.depth" in rep and "a.dur" in rep
        assert "Counter" in rep and "Gauge" in rep and "Histogram" in rep

    def test_json_export_roundtrip(self, monitored, tmp_path):
        monitor.count("j.ops", 2)
        p = monitor.export_json(str(tmp_path / "mon.json"))
        data = json.load(open(p))
        assert data["counters"]["j.ops"] == 2
        assert set(data) >= {"counters", "gauges", "histograms", "events"}

    def test_prometheus_text_format(self, monitored, tmp_path):
        monitor.count("p.ops", 4)
        monitor.gauge_set("p.depth", 2)
        monitor.observe("p.dur", 5e-4)
        txt = monitor.prometheus_text()
        assert "# TYPE paddle_tpu_p_ops counter" in txt
        assert "paddle_tpu_p_ops 4" in txt
        assert "# TYPE paddle_tpu_p_depth gauge" in txt
        assert "# TYPE paddle_tpu_p_dur histogram" in txt
        assert 'paddle_tpu_p_dur_bucket{le="+Inf"} 1' in txt
        assert "paddle_tpu_p_dur_count 1" in txt
        p = monitor.export_prometheus(str(tmp_path / "mon.prom"))
        assert open(p).read() == txt

    def test_profiler_export_carries_monitor_metadata(self, monitored,
                                                      tmp_path):
        from paddle_tpu.profiler import Profiler
        x = paddle.to_tensor(np.random.rand(4).astype("float32"))
        with Profiler(timer_only=True) as prof:
            paddle.exp(x)
        p = str(tmp_path / "trace.json")
        prof.export(p)
        data = json.load(open(p))
        # both planes in ONE artifact: host spans + counter metadata
        assert any(ev["ph"] == "X" for ev in data["traceEvents"])
        meta = [ev for ev in data["traceEvents"]
                if ev.get("ph") == "M" and ev["name"] == "paddle_tpu.monitor"]
        assert len(meta) == 1
        assert meta[0]["args"]["counters"]["dispatch.op.exp"] >= 1
        assert data["monitor"]["counters"]["dispatch.op.exp"] >= 1


class TestOverheadGuard:
    def test_disabled_leaves_no_hooks_and_is_cheap(self):
        """CI guard: FLAGS_monitor=0 must install NO hooks and keep run_op
        within a generous wall-time bound of the uninstrumented impl."""
        from paddle_tpu.ops import _dispatch
        paddle.set_flags({"FLAGS_monitor": False})
        monitor.reset()
        assert _dispatch._PROFILE_HOOK is None
        assert monitor._ENABLED is False
        x = paddle.to_tensor(np.ones((4, 4), "float32"))
        paddle.add(x, x)                 # warm the op cache

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

        loop_run_op(), loop_impl()       # warmup both paths
        t_instr = min(loop_run_op() for _ in range(3))
        t_base = min(loop_impl() for _ in range(3))
        # generous: the disabled path adds two attribute checks; anything
        # near this bound means a hook or timer leaked onto the fast path
        assert t_instr < 3.0 * t_base + 0.05, (t_instr, t_base)
        # and nothing was recorded
        assert monitor.snapshot()["counters"].get("dispatch.op_count", 0) == 0
