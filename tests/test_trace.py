"""Request-scoped distributed tracing (obs/trace.py): span model, 26-byte
wire context, tail-sampled trace ring, the 'PDTC' serving-wire seam with
bit-identical back-compat for untraced peers, fault-path span closure,
the FLAGS_trace=0 overhead guard, and the cross-process e2e socket test
(one traced client request -> ONE trace_id across both processes, visible
in the flight-recorder dump and its chrome-trace export)."""
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.monitor as monitor
from paddle_tpu import faults
from paddle_tpu.core import flags as _flags
from paddle_tpu.obs import trace
from paddle_tpu.serving import (DeadlineExceededError, EngineConfig,
                                ServingEngine)


@pytest.fixture()
def traced():
    monitor.reset()
    trace.reset()
    paddle.set_flags({"FLAGS_monitor": True, "FLAGS_trace": True})
    yield trace
    paddle.set_flags({"FLAGS_monitor": False, "FLAGS_trace": False})
    trace.reset()
    monitor.reset()


# ---------------------------------------------------------------------------
# span model
# ---------------------------------------------------------------------------

class TestSpanModel:
    def test_stack_parents_nested_spans(self, traced):
        with trace.span("outer") as outer:
            assert trace.current() is outer
            with trace.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        assert trace.current() is None
        docs = trace.traces()
        assert len(docs) == 1 and len(docs[0]["spans"]) == 2

    def test_explicit_ctx_wins_over_stack(self, traced):
        remote = trace.TraceContext(trace.new_trace_id(),
                                    trace.new_span_id())
        with trace.span("ambient"):
            sp = trace.span("child", ctx=remote)
            assert sp.trace_id == remote.trace_id
            assert sp.parent_id == remote.span_id
            sp.end()

    def test_exception_sets_error_status(self, traced):
        with pytest.raises(RuntimeError):
            with trace.span("boom"):
                raise RuntimeError("injected")
        (doc,) = trace.bad_traces()
        assert doc["status"] == trace.STATUS_ERROR
        assert "RuntimeError" in doc["spans"][0]["attrs"]["error"]

    def test_end_is_idempotent(self, traced):
        sp = trace.span("once")
        sp.end(status=trace.STATUS_DEADLINE)
        sp.end(status=trace.STATUS_ERROR)    # error paths may race reply
        (doc,) = trace.bad_traces()
        assert doc["spans"][0]["status"] == trace.STATUS_DEADLINE

    def test_links_reference_without_parenting(self, traced):
        a = trace.span("req_a")
        b = trace.span("batch")
        b.link(a)
        assert b.links == [(a.trace_id, a.span_id)]
        assert b.trace_id != a.trace_id
        a.end()
        b.end()

    def test_server_span_requires_wire_ctx(self, traced):
        # absence of 'PDTC' means "no trace": no server-side garbage traces
        assert trace.server_span("serving.request", None) is trace.NULL_SPAN
        ctx = trace.TraceContext(trace.new_trace_id(), trace.new_span_id())
        sp = trace.server_span("serving.request", ctx)
        assert sp.trace_id == ctx.trace_id
        sp.end()

    def test_disabled_returns_shared_null_span(self):
        assert not trace.enabled()
        s1 = trace.span("a")
        s2 = trace.span("b", attrs={"k": 1})
        assert s1 is s2 is trace.NULL_SPAN
        assert s1.ctx() is None
        s1.end(status=trace.STATUS_ERROR)     # all no-ops
        with s1 as s:
            s.set(x=1).link_ctx(None)
        assert trace.traces() == []

    def test_disabled_path_is_attribute_check(self):
        """PR-1-style overhead guard: FLAGS_trace off must keep span()
        a single module-attribute check returning a shared object."""
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            trace.span("hot")
        t_gate = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n):
            pass
        t_base = time.perf_counter() - t0
        assert t_gate < t_base + 0.05


# ---------------------------------------------------------------------------
# wire context
# ---------------------------------------------------------------------------

class TestWireContext:
    def test_pack_unpack_round_trip(self):
        ctx = trace.TraceContext(trace.new_trace_id(),
                                 trace.new_span_id(), flags=3)
        raw = trace.pack_ctx(ctx)
        assert len(raw) == trace.CTX_WIRE_LEN == 26
        assert trace.unpack_ctx(raw) == ctx

    def test_unknown_version_rejected(self):
        ctx = trace.TraceContext(trace.new_trace_id(), trace.new_span_id())
        raw = bytes([99]) + trace.pack_ctx(ctx)[1:]
        with pytest.raises(ValueError, match="version"):
            trace.unpack_ctx(raw)

    def test_recv_trace_frame_tolerates_garbage(self):
        """A corrupt 'PDTC' body must yield None, never break serving."""
        from paddle_tpu.utils.net import recv_trace_frame
        a, b = socket.socketpair()
        try:
            a.sendall(bytes([99]) * trace.CTX_WIRE_LEN)
            assert recv_trace_frame(b) is None
        finally:
            a.close()
            b.close()

    def test_send_trace_frame_layout(self):
        from paddle_tpu.utils.net import TRACE_MAGIC, send_trace_frame
        ctx = trace.TraceContext(trace.new_trace_id(), trace.new_span_id())
        a, b = socket.socketpair()
        try:
            send_trace_frame(a, ctx)
            raw = b.recv(4 + trace.CTX_WIRE_LEN)
            (magic,) = struct.unpack("<I", raw[:4])
            assert magic == TRACE_MAGIC == 0x50445443
            assert trace.unpack_ctx(raw[4:]) == ctx
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# tail-sampled ring
# ---------------------------------------------------------------------------

class TestTailSampling:
    def test_healthy_storm_cannot_evict_bad_traces(self, traced):
        paddle.set_flags({"FLAGS_trace_ring": 4})
        try:
            for i in range(3):
                trace.span(f"bad{i}").end(status=trace.STATUS_DEADLINE)
            for i in range(50):                    # healthy overload storm
                trace.span(f"ok{i}").end()
            payload = trace.ring_payload()
            assert len(payload["ring"]) == 4       # evictable, bounded
            assert len(payload["kept"]) == 3       # protected: all survive
            assert all(d["status"] == trace.STATUS_DEADLINE
                       for d in payload["kept"])
        finally:
            paddle.set_flags({"FLAGS_trace_ring": 64})

    def test_one_bad_span_promotes_whole_trace(self, traced):
        with trace.span("root"):
            trace.span("child").end(status=trace.STATUS_ERROR)
        (doc,) = trace.bad_traces()
        assert doc["status"] == trace.STATUS_ERROR
        assert len(doc["spans"]) == 2

    def test_span_counters_feed_monitor(self, traced):
        trace.span("a").end()
        trace.span("b").end(status=trace.STATUS_REJECTED)
        counters = monitor.snapshot()["counters"]
        assert counters["trace.spans"] == 2
        assert counters["trace.spans.rejected"] == 1

    def test_chrome_events_from_ring(self, traced):
        with trace.span("req"):
            trace.span("stage").end()
        events = trace.trace_chrome_events(trace.traces())
        assert len(events) == 2
        assert all(e["ph"] == "X" and e["cat"] == "trace" for e in events)
        assert len({e["args"]["trace_id"] for e in events}) == 1


# ---------------------------------------------------------------------------
# serving engine integration (one process)
# ---------------------------------------------------------------------------

class TestEngineSpans:
    def test_request_trace_covers_queue_batch_dispatch(self, traced):
        eng = ServingEngine(lambda a: a * 2.0,
                            EngineConfig(warmup_on_start=False,
                                         batch_timeout_ms=5)).start()
        try:
            with trace.span("client.send") as sp:
                fut = eng.submit([np.ones((1, 4), np.float32)],
                                 trace_ctx=sp.ctx())
                fut.result(timeout=10)
        finally:
            eng.stop()
        docs = [d for d in trace.traces()
                if any(s["name"] == "client.send" for s in d["spans"])]
        assert len(docs) == 1
        names = {s["name"] for s in docs[0]["spans"]}
        assert {"client.send", "serving.queue_wait", "serving.batch",
                "serving.dispatch"} <= names

    def test_batch_span_links_coalesced_members(self, traced):
        release = threading.Event()

        def slow(a):
            release.wait(5)
            return a

        eng = ServingEngine(slow, EngineConfig(warmup_on_start=False,
                                               batch_timeout_ms=40,
                                               max_batch_size=4)).start()
        try:
            futs = []
            for _ in range(3):
                with trace.span("client.send") as sp:
                    futs.append(eng.submit([np.ones((1, 4), np.float32)],
                                           trace_ctx=sp.ctx()))
            release.set()
            for f in futs:
                f.result(timeout=10)
        finally:
            release.set()
            eng.stop()
        batches = [s for d in trace.traces() for s in d["spans"]
                   if s["name"] == "serving.batch"]
        assert batches
        assert sum(len(b["links"]) for b in batches) == 3

    def test_deadline_expiry_closes_queue_wait_deadline(self, traced):
        hold = threading.Event()

        def stall(a):
            hold.wait(5)
            return a

        eng = ServingEngine(stall, EngineConfig(warmup_on_start=False,
                                                batch_timeout_ms=1,
                                                max_batch_size=1,
                                                num_workers=1)).start()
        try:
            with trace.span("client.send") as sp:
                first = eng.submit([np.ones((1, 4), np.float32)],
                                   trace_ctx=sp.ctx())
            with trace.span("client.send") as sp:
                doomed = eng.submit([np.ones((1, 4), np.float32)],
                                    deadline_ms=30, trace_ctx=sp.ctx())
            with pytest.raises(DeadlineExceededError):
                doomed.result(timeout=10)
            hold.set()
            first.result(timeout=10)
        finally:
            hold.set()
            eng.stop()
        bad = trace.bad_traces()
        qw = [s for d in bad for s in d["spans"]
              if s["name"] == "serving.queue_wait"]
        assert any(s["status"] == trace.STATUS_DEADLINE for s in qw)

    def test_dispatch_fault_closes_spans_with_error(self, traced):
        """Injected conn-reset at serving.dispatch: every span still
        closes (the autouse _no_trace_leak fixture enforces depth 0) and
        the trace lands in the protected ring with status=error."""
        eng = ServingEngine(lambda a: a, EngineConfig(
            warmup_on_start=False, batch_timeout_ms=5)).start()
        try:
            with faults.inject("serving.dispatch:conn_reset"):
                with trace.span("client.send") as sp:
                    fut = eng.submit([np.ones((1, 4), np.float32)],
                                     trace_ctx=sp.ctx())
                with pytest.raises(Exception):
                    fut.result(timeout=10)
        finally:
            eng.stop()
        bad = trace.bad_traces()
        assert bad, "faulted request must land in the protected ring"
        disp = [s for d in bad for s in d["spans"]
                if s["name"] == "serving.dispatch"]
        assert disp and all(s["status"] == trace.STATUS_ERROR
                            for s in disp)
        assert trace.active_depth() == 0


# ---------------------------------------------------------------------------
# ps.rpc seam
# ---------------------------------------------------------------------------

class TestPsRpcSpans:
    def test_rpc_fault_closes_span_with_error(self, traced):
        """ps.rpc.send conn-reset with retries exhausted: the ps.rpc.*
        span must close with status=error (no leak), and a successful
        retried call closes ok with the retry count."""
        from paddle_tpu.distributed.ps import PsClient, PsServer
        srv = PsServer()
        srv.add_sparse_table("emb", dim=4, lr=0.5)
        srv.run()
        client = PsClient([f"{srv.host}:{srv.port}"], max_retries=2,
                          backoff_ms=1.0, call_timeout=30.0)
        client.register_sparse_dim("emb", 4)
        try:
            with faults.inject("ps.rpc.send:conn_reset"):   # unlimited
                with pytest.raises(OSError):
                    client.pull_sparse("emb", [1, 2])
            bad = [s for d in trace.bad_traces() for s in d["spans"]
                   if s["name"].startswith("ps.rpc.")]
            assert bad and bad[0]["status"] == trace.STATUS_ERROR
            assert trace.active_depth() == 0
            trace.reset()
            with faults.inject("ps.rpc.send:conn_reset:times=1"):
                client.pull_sparse("emb", [1, 2])
            ok = [s for d in trace.traces() for s in d["spans"]
                  if s["name"] == "ps.rpc.pull_sparse"]
            assert ok and ok[-1]["status"] == trace.STATUS_OK
            assert ok[-1]["attrs"]["retries"] >= 1
        finally:
            client.close()
            srv.stop()


# wire back-compat (untraced requests bit-identical to pre-PDTC) moved to
# tests/test_net.py::TestGoldenBytesMatrix — the serving row of the
# per-plane golden-bytes matrix that covers all four wire planes.


# ---------------------------------------------------------------------------
# flight recorder + CLI
# ---------------------------------------------------------------------------

class TestDumpAndCli:
    def test_v3_dump_carries_ring_and_renders(self, traced, tmp_path):
        from paddle_tpu import obs
        from paddle_tpu.monitor import _main
        with trace.span("client.send"):
            trace.span("serving.dispatch").end()
        trace.span("doomed").end(status=trace.STATUS_DEADLINE)
        path = obs.dump(str(tmp_path / "d.json"), reason="manual")
        doc = json.load(open(path))
        assert doc["schema"] == "paddle_tpu.flight_recorder/5"
        assert len(doc["traces"]["kept"]) == 1
        assert _main(["show", path]) == 0
        out_trace = str(tmp_path / "d.trace.json")
        assert _main(["trace", path, "-o", out_trace]) == 0
        events = json.load(open(out_trace))["traceEvents"]
        assert any(e.get("cat") == "trace" for e in events)


# ---------------------------------------------------------------------------
# cross-process e2e: one trace_id across the socket
# ---------------------------------------------------------------------------

class TestCrossProcessE2E:
    def test_one_traced_request_one_trace_id_across_processes(
            self, traced, tmp_path):
        """THE acceptance drill: a traced client request against a traced
        server in a REAL child process yields a single trace_id whose
        spans cover client-send (here) and queue_wait/batch/dispatch/
        reply (there) — recovered from the server's flight-recorder dump
        and its chrome-trace export."""
        from paddle_tpu.inference.server import PredictorClient
        from paddle_tpu.monitor import _main
        runner = os.path.join(os.path.dirname(__file__),
                              "serving_trace_runner.py")
        port_file = str(tmp_path / "port")
        dump_path = str(tmp_path / "server_dump.json")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "JAX_", "XLA_", "PALLAS_",
                                    "TPU_", "PYTHONPATH"))}
        proc = subprocess.Popen(
            [sys.executable, runner, port_file, dump_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=env, text=True)
        try:
            deadline = time.time() + 120
            while not os.path.exists(port_file):
                assert proc.poll() is None, proc.stderr.read()[-2000:]
                assert time.time() < deadline, "server never published port"
                time.sleep(0.05)
            host, port = open(port_file).read().split()
            x = np.arange(4, dtype=np.float32).reshape(1, 4)
            c = PredictorClient(host, int(port), timeout=60)
            status, outs = c.run([x])
            c.close()
            assert status == 0
            np.testing.assert_allclose(outs[0], x * 2.0)
            out, err = proc.communicate(input="done\n", timeout=120)
        except BaseException:
            proc.kill()
            raise
        assert proc.returncode == 0, err[-2000:]

        # the client-side root span for our request
        client_docs = [d for d in trace.traces()
                       if any(s["name"] == "client.send"
                              for s in d["spans"])]
        assert len(client_docs) == 1
        tid = client_docs[0]["trace_id"]

        # the server-side half, out of the child's flight recorder
        doc = json.load(open(dump_path))
        assert doc["schema"] == "paddle_tpu.flight_recorder/5"
        ring = doc["traces"]["ring"] + doc["traces"]["kept"]
        server_docs = [d for d in ring if d["trace_id"] == tid]
        assert len(server_docs) == 1, (
            f"expected exactly one server trace {tid}, got "
            f"{[d['trace_id'] for d in ring]}")
        names = {s["name"] for s in server_docs[0]["spans"]}
        assert {"serving.request", "serving.queue_wait", "serving.batch",
                "serving.dispatch", "serving.reply"} <= names
        # every server span belongs to the client's trace
        assert all(s["trace_id"] == tid for s in server_docs[0]["spans"])

        # chrome-trace export carries the request plane
        out_trace = str(tmp_path / "server_dump.trace.json")
        assert _main(["trace", dump_path, "-o", out_trace]) == 0
        events = json.load(open(out_trace))["traceEvents"]
        lane = [e for e in events
                if e.get("args", {}).get("trace_id") == tid]
        assert {e["name"] for e in lane} >= {"serving.request",
                                             "serving.dispatch"}
