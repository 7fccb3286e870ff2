"""Reader `named_op_roofline`: the device time of the operations that carry
a NAME (a Pallas kernel's own, e.g. `moe_experts`) INSIDE the executions of
one named program, per such execution; and that time as a share of the
kernel's memory roofline, with the bytes from a cost function that the
metric's file NAMES.

`readers/named_op.py` sums a named operation over the whole traced slice
and divides by the executions of `per`: where another program holds the
same kernel (a prefill's expert layers beside a decode step's) its calls
are counted in. Here an event counts only if it lies wholly inside a whole
execution of `per` (the `XLA Modules` line of the same device plane) that
lies wholly inside the slice. An operation's event is named by its HLO
text; `named_op.matcher` decides what belongs to `op`.

args: `field`, `op`, `per` (a program's name on `XLA Modules`), and for
the roofline `cost`: "<module>.<function>" under `benchmarks/`, called as
function(config sizes, x); `workload`: the cell whose configuration and
engine are read; and where x comes from, one of
  `engine`: a key of the cell's `engine` group (e.g. "num_slots")
  `counters`: {"plus": <counter>, "per": <counter>}: how much the first
              grew over the window for each unit of the second (the
              monitor's snapshots at the window's edges)
fields:
  "ms_per_run"      device ms of the named operations per execution of `per`
  "roofline_pct"    100 x bytes / hbm_bytes_per_s / that time, at the peak
                    of the device the trace was taken on (`peaks.json`)
Returns None with no trace, no such operation, no whole execution of `per`
in the slice, or no such counters (a program before they existed).
"""
from __future__ import annotations

import bisect
import functools
import importlib
from typing import Optional

from .. import flops, harness, trace_reduce
from . import monitor_counter_ratio, named_op, program_trace


def seconds_inside(events, runs) -> float:
    """The seconds of `events` [(start, end)] that lie wholly inside one of
    `runs` [(start, end)], which do not overlap."""
    runs = sorted(runs)
    starts = [a for a, _ in runs]
    total = 0.0
    for a, b in events:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and b <= runs[i][1]:
            total += b - a
    return total / 1e9


@functools.lru_cache(maxsize=4)
def ms_per_run(path: str, op: str, per: str) -> Optional[float]:
    import jax
    t = program_trace.parse(path)
    if t["window"] is None:
        return None
    lo, hi = t["window"]
    mine = named_op.matcher(op)
    runs_of = {name: [(a, b) for n, a, b in d["modules"]
                      if a >= lo and b <= hi
                      and (n == per or n.startswith(per + "("))]
               for name, d in t["events"]["devices"].items()}
    count = sum(len(r) for r in runs_of.values())
    if not count:
        return None
    seconds, found = 0.0, False
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name not in runs_of:
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            events = [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                      for e in line.events if mine(e.name)]
            found = found or bool(events)
            seconds += seconds_inside(events, runs_of[plane.name])
    return 1000.0 * seconds / count if found else None


def read(evidence, field, op, per, cost=None, workload=None, engine=None,
         counters=None):
    if field not in ("ms_per_run", "roofline_pct"):
        raise ValueError(f"named_op_roofline: unknown field {field!r}")
    if field == "roofline_pct" and (cost is None or workload is None
                                    or (engine is None) == (counters is None)):
        raise ValueError("named_op_roofline: a roofline share needs `cost`, "
                         "`workload` and one of `engine` or `counters`")
    if not evidence.get("trace"):
        return None
    path = trace_reduce.find_xplane(harness.TRACE_DIR)
    if path is None:
        return None
    ms = ms_per_run(path, op, per)
    if ms is None or field == "ms_per_run":
        return ms
    cell = harness.load_cell(workload)
    if engine is not None:
        x = cell["engine"][engine]
    else:
        x = monitor_counter_ratio.read(evidence, [counters["plus"]],
                                       counters["per"])
        if x is None:
            return None
    module, function = cost.rsplit(".", 1)
    nbytes = getattr(importlib.import_module(f"benchmarks.{module}"),
                     function)(cell["config_sizes"], x)
    import jax
    peak = flops.peak(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / peak / (ms / 1000.0)
