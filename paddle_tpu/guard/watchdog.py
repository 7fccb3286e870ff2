"""Step watchdog — converts a hung step into a typed StepStalledError.

A hung XLA dispatch (wedged collective, lost device, stuck host callback)
blocks the calling thread in C and cannot be interrupted in place, so the
watchdog runs each step on a dedicated runner thread and bounds the wait on
the caller side: when the deadline expires the caller gets a
`StepStalledError` carrying the last-known phase while the wedged runner is
abandoned (a fresh runner serves subsequent steps; a late result from the
abandoned one is discarded by sequence number).

The deadline is `FLAGS_guard_step_timeout_s` when set, otherwise
auto-calibrated as `max(FLAGS_guard_min_timeout_s, FLAGS_guard_timeout_factor
x trailing-median step duration)` once `FLAGS_guard_warmup_steps` steps have
completed — compile-heavy first steps inflate the median far less than the
max, and the factor absorbs retraces. With no deadline yet (warmup, auto
mode) steps run inline on the caller thread: zero overhead, no thread.
"""
from __future__ import annotations

import queue
import statistics
import threading
import time
from typing import List, Optional

from .. import monitor as _monitor
from .. import obs as _obs
from .errors import StepStalledError
from ..utils import syncwatch as _syncwatch


class StepWatchdog:
    """Deadline supervisor for one training loop. Not thread-safe: one
    loop, one watchdog. `run(fn, *args)` executes fn under the current
    deadline; `phase(name)` tags progress so a stall names where it hung;
    `close()` joins the runner (and any wedged stragglers) within a grace
    period so tests never leak `guard-*` threads."""

    def __init__(self, timeout_s: float = 0.0, warmup_steps: int = 5,
                 factor: float = 10.0, min_timeout_s: float = 30.0,
                 history: int = 64):
        self._timeout = float(timeout_s)
        self._warmup = int(warmup_steps)
        self._factor = float(factor)
        self._min_timeout = float(min_timeout_s)
        self._durations: List[float] = []
        self._history = int(history)
        self._phase = "idle"
        self._step = 0
        self._seq = 0
        self._jobs: Optional[queue.Queue] = None
        self._results: Optional[queue.Queue] = None
        self._runner: Optional[threading.Thread] = None
        self._wedged: List[threading.Thread] = []
        self._closed = False

    # ---- phase + deadline ----
    def phase(self, name: str) -> None:
        self._phase = name
        if _obs._ENABLED:
            # timeline marker: a wedge between phase spans still gets its
            # last-known position into the flight-recorder dump
            _obs.mark(name)

    def record(self, duration_s: float) -> None:
        self._durations.append(float(duration_s))
        if len(self._durations) > self._history:
            del self._durations[:-self._history]

    def deadline(self) -> Optional[float]:
        """Current per-step deadline in seconds, or None (not armed yet)."""
        if self._timeout > 0:
            return self._timeout
        if len(self._durations) >= max(1, self._warmup):
            med = statistics.median(self._durations)
            return max(self._min_timeout, self._factor * med)
        return None

    # ---- runner thread ----
    def _ensure_runner(self) -> None:
        if self._runner is not None and self._runner.is_alive():
            return
        self._jobs = queue.Queue()
        self._results = queue.Queue()
        jobs, results = self._jobs, self._results

        def loop():
            while True:
                job = jobs.get()
                if job is None:
                    return
                seq, fn, args, kwargs = job
                try:
                    results.put((seq, True, fn(*args, **kwargs)))
                except BaseException as e:  # noqa: BLE001 — marshalled to caller
                    results.put((seq, False, e))

        self._runner = _syncwatch.Thread(target=loop, daemon=True,
                                        name="guard-watchdog-runner")
        self._runner.start()

    def run(self, fn, *args, **kwargs):
        """Execute fn under the current deadline; raises StepStalledError
        on expiry, re-raises fn's own exception otherwise."""
        if self._closed:
            raise RuntimeError("StepWatchdog is closed")
        self._step += 1
        dl = self.deadline()
        t0 = time.monotonic()
        if dl is None:  # warmup / auto not armed: inline, no thread
            out = fn(*args, **kwargs)
            self.record(time.monotonic() - t0)
            return out
        self._ensure_runner()
        self._seq += 1
        seq = self._seq
        self._jobs.put((seq, fn, args, kwargs))
        while True:
            remaining = dl - (time.monotonic() - t0)
            if remaining <= 0:
                break
            try:
                rseq, ok, val = self._results.get(timeout=remaining)
            except queue.Empty:
                break
            if rseq != seq:
                continue  # stale result from a previously-wedged step
            self.record(time.monotonic() - t0)
            if ok:
                return val
            raise val
        # deadline expired: abandon the runner (it is blocked inside fn).
        # The sentinel makes it exit its loop if/when fn ever returns —
        # without it the straggler would block forever on the dead queue.
        self._jobs.put(None)
        self._wedged.append(self._runner)
        self._runner = None
        if _monitor._ENABLED:
            _monitor.count("guard.stalls")
        err = StepStalledError(phase=self._phase, deadline_s=dl,
                               step=self._step)
        if _obs._FR_ENABLED:
            # black box FIRST, while the wedged step is still in flight —
            # the dump's inflight_phase/open_step name where it hung
            _obs.record_event("guard.stall", phase=self._phase,
                              step=self._step, deadline_s=dl)
            _obs.dump_on_error(err)
        raise err

    # ---- lifecycle ----
    def alive_threads(self) -> List[threading.Thread]:
        out = [t for t in self._wedged if t.is_alive()]
        if self._runner is not None and self._runner.is_alive():
            out.append(self._runner)
        return out

    def close(self, grace_s: float = 5.0) -> None:
        """Stop the runner and join stragglers. A still-wedged thread after
        the grace period is left daemonized (it cannot be killed) but is
        reported via the return-less assert in tests' leak guard."""
        self._closed = True
        if self._runner is not None and self._jobs is not None:
            self._jobs.put(None)
        deadline = time.monotonic() + grace_s
        for t in ([self._runner] if self._runner else []) + self._wedged:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        self._wedged = [t for t in self._wedged if t.is_alive()]
        if self._runner is not None and not self._runner.is_alive():
            self._runner = None
