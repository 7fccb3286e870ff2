"""What every cell shares: finding a cell's files by name, watching JAX
compile, tracing a slice of the window, and turning evidence into the
per-layer metrics through their readers.

Nothing here knows a model or a traffic mix. A cell is data
(`workloads/<name>.json`, `configs/<config>.json`); its runner, traffic
generator and each metric's reader are modules found by the names in those
files, so a later PR adds files and edits none.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import threading
import time
from typing import Callable, Optional

from . import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")   # git-ignored
TRACE_SECONDS = 3.0      # the traced slice: the last seconds of the window


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, base: str = "") -> dict:
    """The cell's file with its configuration loaded under `config_sizes`.
    `base` is a directory under benchmarks/ that holds `workloads/` and
    `configs/` of its own: the tiny cells of the CPU rehearsal."""
    cell = load_json(base, "workloads", name + ".json")
    cell["name"] = name
    cell["config_sizes"] = load_json(base, "configs",
                                     cell["config"] + ".json")
    return cell


def place_cache() -> str:
    """JAX's persistent compilation cache where the repo's one rule puts it
    (`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`), holding
    every program however quick its compile, so that the second run of a
    cell in a checkout compiles nothing."""
    import jax
    from paddle_tpu.core.compile_cache import place_jax_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return place_jax_cache()


def module(kind: str, name: str):
    """`runners/<name>.py`, `traffic/<name>.py` or `readers/<name>.py`."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


class CompileWatch:
    """Counts what JAX lowers and compiles through `jax.monitoring` (as
    chip_smoke.Lowerings): every compile or persistent-cache fetch starts
    with one lowering, so a flat count over a span means it compiled
    nothing. Also sums the backend compile-or-load seconds and counts the
    persistent cache's hits and misses."""

    LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.lowerings = 0
        self.backend_compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_kw):
        if event == self.LOWERING:
            self.lowerings += 1
        elif event == self.BACKEND:
            self.backend_compiles += 1
            self.compile_s += seconds

    def _event(self, event, **_kw):
        if event == self.HIT:
            self.cache_hits += 1
        elif event == self.MISS:
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"lowerings": self.lowerings,
                "backend_compiles": self.backend_compiles,
                "compile_s": self.compile_s, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class Tracer:
    """Profiles the last `TRACE_SECONDS` of the window. `arm(t_end)` starts
    a thread that sleeps until `t_end - TRACE_SECONDS` (a `perf_counter`
    time) and starts the profiler there, off the measuring thread;
    `stop()` ends the trace. `bench.trace_window` marks the slice inside
    the trace. The Python tracer is off: it slows the host it observes."""

    def __init__(self, out_dir: str = TRACE_DIR):
        self.out_dir = out_dir
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    def arm(self, t_end: float) -> None:
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        self._thread = threading.Thread(
            target=self._trace, args=(t_end - TRACE_SECONDS,),
            name="bench-tracer", daemon=True)
        self._thread.start()

    def _trace(self, t_start: float) -> None:
        # the annotation opens and closes on this thread: a TraceMe span
        # is paired within one thread's buffer
        import jax
        if self._stop.wait(max(0.0, t_start - time.perf_counter())):
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
                self._stop.wait()
        finally:
            jax.profiler.stop_trace()

    def stop(self) -> None:
        """End the slice and wait until the trace is written."""
        if self._thread is not None:
            self._stop.set()
            self._thread.join()
            self._thread = None

    def summary(self) -> Optional[dict]:
        path = trace_reduce.find_xplane(self.out_dir)
        if path is None:
            return None
        return trace_reduce.reduce(trace_reduce.load(path))


class Context:
    """What the harness hands a runner."""

    def __init__(self, device: str, seed: int, seconds: float, trace: bool,
                 watch: CompileWatch, tracer: Optional[Tracer] = None,
                 say: Callable[[str], None] = print):
        self.device = device          # "tpu"; the CPU rehearsal passes "cpu"
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.watch = watch
        self.tracer = tracer if trace else None
        self.say = say
        self.window_watch = [None, None]

    def window_opens(self) -> None:
        """The runner marks where the measured work starts and ends; what
        JAX lowered or compiled between the two marks makes `correct`
        false."""
        self.window_watch[0] = self.watch.snapshot()

    def window_closes(self) -> None:
        self.window_watch[1] = self.watch.snapshot()

    def compiled_in_window(self) -> dict:
        before, after = self.window_watch
        return {k: after[k] - before[k]
                for k in ("lowerings", "backend_compiles")}


def per_layer_metrics(bench: dict, cell_name: str, evidence: dict) -> dict:
    """Every per-layer metric `BENCHMARK.json` lists for this cell, through
    the reader its `layer_metrics/<name>.json` names. A reader that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        spec = load_json("layer_metrics", m["name"] + ".json")
        value = module("readers", spec["reader"]).read(
            evidence, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_block(devices, chips: int, trace_summary: Optional[dict],
                 program_temp_bytes: int = 0) -> dict:
    """The contract's `device` object: the device as JAX reports it and the
    peak memory on the fullest chip. On this TPU runtime the allocator's
    `peak_bytes_in_use` counts live arrays only, not the temporaries a
    program holds while it runs (my chip run, PR 25: 1.46 GB beside a train
    step whose temporaries are 6.2 GB), so a runner may hand over the
    temporaries of its main program as XLA's memory analysis gives them
    (`program_temp_bytes`), and they are added."""
    used = devices[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    block = {"platform": used[0].platform, "kind": used[0].device_kind,
             "count": len(devices),
             "memory_peak_bytes": int(max(peaks)) + int(program_temp_bytes),
             "allocator_peak_bytes": int(max(peaks))}
    if trace_summary is not None:
        block["busy_s"] = trace_summary["busy_s"]
        block["window_s"] = trace_summary["window_s"]
    return block
