"""Reader `step_span_roofline`: the device time of the operations that
carry a NAME (`moe_experts`) inside whole executions of one named program
(`jit_llm_decode`) as a share of their memory roofline, with the bytes of
EACH SUCH EXECUTION taken from the program's own span in which the host
read that execution's result: the span's attributes (`LLMEngine` sets the
counts a decode step reports on its `llm.decode.read`) go into a cost
function that the metric's file NAMES. Bytes and time come from the same
steps of the same traced slice; no counter of the window enters.

Matching. The scheduler reads every decode execution exactly once, in the
order they ran, and a read ends only after its execution has ended. So the
executions wholly inside the slice, in order, are each given the first
read not yet given that ends after the execution does; an execution with
no such read in the trace (its read fell after the profiler stopped) is
left out of both sums. The operations of an execution are those wholly
inside its interval on the same device plane (`named_op_roofline`).

args: `op`, `per` (a program's name on `XLA Modules`), `span` (the host
span's name), `attrs` (the span attributes handed to the cost function, in
its order), `cost` ("<module>.<function>" under `benchmarks/`, called as
function(config sizes, *attrs)), `workload` (the cell whose configuration
is read). Returns 100 x sum of bytes / hbm_bytes_per_s / sum of seconds at
the peak of the device the trace was taken on (`peaks.json`); None with no
trace, no whole execution of `per`, no such operation, or no span that
carries the attributes (a program before they existed).
"""
from __future__ import annotations

import bisect
import functools
import importlib
from typing import List, Optional, Tuple

from .. import flops, harness, trace_reduce
from . import named_op, program_trace


def match(runs: List[Tuple[float, float]],
          reads: List[Tuple[float, float, tuple]]) -> List[tuple]:
    """Pairs (run, the read's values): each run, in order of its end, takes
    the first read not yet taken whose end is not before the run's."""
    ends = sorted(reads, key=lambda r: r[1])
    out, j = [], 0
    for run in sorted(runs, key=lambda r: r[1]):
        while j < len(ends) and ends[j][1] < run[1]:
            j += 1
        if j == len(ends):
            break
        out.append((run, ends[j][2]))
        j += 1
    return out


def _span_values(data, span: str, attrs: tuple) -> list:
    """(start, end, values of `attrs`) of every host event named `span`
    that carries all of them."""
    out = []
    for plane in data.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != span:
                    continue
                stats = dict(e.stats)
                if all(a in stats for a in attrs):
                    out.append((float(e.start_ns),
                                float(e.start_ns + e.duration_ns),
                                tuple(float(stats[a]) for a in attrs)))
    return out


@functools.lru_cache(maxsize=4)
def steps(path: str, op: str, per: str, span: str, attrs: tuple
          ) -> Optional[List[Tuple[float, tuple]]]:
    """(seconds of `op` inside the execution, the read's values) of every
    matched whole execution of `per` in the slice; None without them."""
    import jax
    t = program_trace.parse(path)
    if t["window"] is None:
        return None
    lo, hi = t["window"]
    data = jax.profiler.ProfileData.from_file(path)
    reads = _span_values(data, span, attrs)
    mine = named_op.matcher(op)
    out, found = [], False
    for plane in data.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if trace_reduce.MODULES_LINE not in lines \
                or trace_reduce.OPS_LINE not in lines:
            continue
        runs = sorted(
            (float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in lines[trace_reduce.MODULES_LINE].events
            if (e.name == per or e.name.startswith(per + "("))
            and e.start_ns >= lo and e.start_ns + e.duration_ns <= hi)
        if not runs:
            continue
        starts = [a for a, _ in runs]
        busy = [0.0] * len(runs)
        for e in lines[trace_reduce.OPS_LINE].events:
            if not mine(e.name):
                continue
            a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and b <= runs[i][1]:
                busy[i] += b - a
                found = True
        at = {run: s for run, s in zip(runs, busy)}
        out += [(at[run] / 1e9, values) for run, values in match(runs, reads)]
    return out if found and out else None


def read(evidence, op, per, span, attrs, cost, workload):
    if not evidence.get("trace"):
        return None
    path = trace_reduce.find_xplane(harness.TRACE_DIR)
    if path is None:
        return None
    got = steps(path, op, per, span, tuple(attrs))
    if got is None:
        return None
    seconds = sum(s for s, _ in got)
    if seconds <= 0:
        return None
    cfg = harness.load_cell(workload)["config_sizes"]
    module, function = cost.rsplit(".", 1)
    fn = getattr(importlib.import_module(f"benchmarks.{module}"), function)
    nbytes = sum(fn(cfg, *values) for _, values in got)
    import jax
    peak = flops.peak(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / peak / seconds
