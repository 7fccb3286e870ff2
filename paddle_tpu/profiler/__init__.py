"""paddle.profiler parity over the JAX/XLA profiler.

Reference parity: `python/paddle/profiler/profiler.py:224` (Profiler with
scheduler states CLOSED/READY/RECORD, `export_chrome_tracing`:128), the
statistics report (`profiler_statistic.py:1`), and the C++ host/device
tracers (`platform/profiler/host_tracer.cc`, `chrometracing_logger.cc`).

Two planes, as in the reference:
  - HOST: op-dispatch events hooked into `ops._dispatch.run_op` plus user
    `RecordEvent` ranges, collected in-process; `summary()` renders the
    per-op statistics table, `export()` writes chrome://tracing JSON.
  - DEVICE: the XLA profiler trace (TraceMe + device timeline) written to
    the trace dir for TensorBoard — the CUPTI-tracer role.
"""
from __future__ import annotations

import enum
import json
import os
import tempfile
import threading
import time

import jax

from ..monitor import Span


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    total = closed + ready + record

    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        prof._export_dir = dir_name

    # Profiler.__init__ reads the dir off the handler WITHOUT calling it, so
    # the handler itself runs only when a trace is ready (at stop) — the
    # reference's on_trace_ready contract (profiler.py:224).
    handler._export_dir = dir_name
    return handler


class _HostEvent:
    __slots__ = ("name", "start", "end", "tid", "kind")

    def __init__(self, name, start, end, tid, kind):
        self.name, self.start, self.end = name, start, end
        self.tid, self.kind = tid, kind

    @property
    def dur(self):
        return self.end - self.start


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        # dir-only peek: the handler itself runs when the trace is READY
        # (stop()), never here — see export_chrome_tracing
        self._export_dir = getattr(on_trace_ready, "_export_dir", None)
        self._active = False
        self.step_num = 0
        self._step_times = []
        self._t0 = None
        self._events: list = []
        self._lock = threading.Lock()

    # ---- lifecycle ----
    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def start(self):
        self._t0 = time.time()
        global _EXTERNAL_HOOK
        from ..ops import _dispatch
        if not _ACTIVE_STACK:
            # chain any hook a non-profiler party installed before us
            _EXTERNAL_HOOK = _dispatch._PROFILE_HOOK
        if self not in _ACTIVE_STACK:
            _ACTIVE_STACK.append(self)
        _dispatch._PROFILE_HOOK = _dispatch_hook
        if not self._timer_only:
            if self._export_dir is None:
                # no directory given: a fresh one under the temp directory,
                # never the working tree (read it back off `_export_dir`)
                self._export_dir = tempfile.mkdtemp(prefix="profiler_log_")
            os.makedirs(self._export_dir, exist_ok=True)
            try:
                jax.profiler.start_trace(self._export_dir)
                self._active = True
            except Exception:
                self._active = False
        return self

    def stop(self):
        # Stack discipline with out-of-order tolerance: remove THIS profiler
        # from the active set wherever it sits; the shared dispatcher hook
        # keeps feeding every remaining profiler, so stopping an outer
        # profiler never clobbers an inner one's hook (and nested profilers
        # both observe ops while both are active).
        global _EXTERNAL_HOOK
        from ..ops import _dispatch
        if self in _ACTIVE_STACK:
            _ACTIVE_STACK.remove(self)
        if not _ACTIVE_STACK:
            _dispatch._PROFILE_HOOK = _EXTERNAL_HOOK
            _EXTERNAL_HOOK = None
        if self._active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._active = False
        if self._on_trace_ready:
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.time()
        if self._t0 is not None:
            self._step_times.append(now - self._t0)
        self._t0 = now
        self.step_num += 1

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        import numpy as np
        ts = np.asarray(self._step_times[-10:])
        return f"avg step {ts.mean()*1000:.2f} ms (last {len(ts)})"

    # ---- host events ----
    def _record_op(self, name, start, end, kind="op"):
        with self._lock:
            self._events.append(_HostEvent(name, start, end,
                                           threading.get_ident(), kind))

    def events(self):
        return list(self._events)

    # ---- statistics report (profiler_statistic.py role) ----
    def summary(self, sorted_by="total", op_detail=True, thread_sep=False,
                time_unit="ms"):
        scale = {"s": 1.0, "ms": 1e3, "us": 1e6}.get(time_unit, 1e3)
        stats = {}
        for e in self._events:
            s = stats.setdefault(e.name, [0, 0.0, float("inf"), 0.0])
            s[0] += 1
            s[1] += e.dur
            s[2] = min(s[2], e.dur)
            s[3] = max(s[3], e.dur)
        total = sum(s[1] for s in stats.values()) or 1e-12
        keyfn = (lambda kv: -kv[1][1]) if sorted_by in ("total", None) \
            else (lambda kv: -kv[1][0])
        lines = [
            "-" * 87,
            f"{'Name':<30}{'Calls':>7}{'Total(' + time_unit + ')':>14}"
            f"{'Avg':>9}{'Min':>9}{'Max':>9}{'Ratio':>8}",
            "-" * 87,
        ]
        for name, (cnt, tot, mn, mx) in sorted(stats.items(), key=keyfn):
            lines.append(
                f"{name[:29]:<30}{cnt:>7}{tot * scale:>14.3f}"
                f"{tot / cnt * scale:>9.3f}{mn * scale:>9.3f}"
                f"{mx * scale:>9.3f}{tot / total:>8.1%}")
        lines.append("-" * 87)
        if self._step_times:
            lines.append(self.step_info())
        return "\n".join(lines)

    # ---- chrome trace export (chrometracing_logger.cc role) ----
    def export(self, path, format="json"):
        events = []
        for e in self._events:
            events.append({"name": e.name, "ph": "X", "cat": e.kind,
                           "ts": e.start * 1e6, "dur": e.dur * 1e6,
                           "pid": os.getpid(), "tid": e.tid})
        # merge the obs plane: step-timeline phase spans ride along on their
        # own tids so one trace shows ops AND per-step phase attribution
        from .. import obs as _obs
        if _obs._TL_ENABLED:
            events.extend(_obs.timeline().chrome_events())
        # merge the stats plane: monitor counters ride along as metadata so
        # ONE artifact carries both spans and counters
        from .. import monitor as _monitor
        snap = _monitor.snapshot()
        events.append({"name": "paddle_tpu.monitor", "ph": "M",
                       "pid": os.getpid(), "tid": 0, "args": snap})
        os.makedirs(os.path.dirname(os.path.abspath(path)) or ".",
                    exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms",
                       "monitor": snap}, f, default=str)
        return path


_ACTIVE_STACK: list = []
# hook that was installed on ops._dispatch before the first profiler started
# (chained by the dispatcher, restored when the last profiler stops)
_EXTERNAL_HOOK = None


def _dispatch_hook(name, start, end, kind="op"):
    """The ONE hook installed on ops._dispatch while any profiler is active:
    fans events out to every active profiler (nested profilers all observe
    ops) and chains to the pre-existing external hook, if any."""
    for p in tuple(_ACTIVE_STACK):
        p._record_op(name, start, end, kind)
    if _EXTERNAL_HOOK is not None:
        _EXTERNAL_HOOK(name, start, end)


def RecordEvent(name, event_type=None):
    """Host-side instrumentation range (`platform/profiler/event_tracing.h`):
    a `monitor.Span` of kind "user" that does not wait for `FLAGS_monitor`.
    It lands in every active Profiler's host events and, as a
    `TraceAnnotation`, on the XLA profiler's timeline."""
    return Span(name, "user")


def load_profiler_result(filename):
    with open(filename) as f:
        return json.load(f)
