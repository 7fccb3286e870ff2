"""Reader `named_op`: the device time of the operations that carry a NAME
(a Pallas kernel's own, e.g. `power_retention_step`), read off the device
plane's `XLA Ops` line of the traced slice, per whole execution of a named
program; and that time as a share of the kernel's memory roofline.

An operation's event is named by its HLO text, `%<name>.<n> = ...`; an
event belongs to `op` when its name is `%<op>` or `%<op>.<n>`. Reads the
slice's `.xplane.pb` (`harness.TRACE_DIR`) itself. Returns None with no
trace, no such operation (a commit before the kernel existed) or no whole
execution of `per` in the slice.

args: `op`, `per` (a program's name on `XLA Modules`), `field`:
  "ms_per_run"      device ms of the named operations per execution of `per`
  "roofline_pct"    100 x bytes / hbm_bytes_per_s / that time, with the bytes
                    from `retention_cost.step_bytes(config, num_slots, part)`
                    of `workload`'s configuration and engine, and the peak
                    of the device the trace was taken on (`peaks.json`)
"""
from __future__ import annotations

import functools
import re
from typing import Optional, Tuple

from .. import flops, harness, retention_cost, trace_reduce
from . import program_trace


def matcher(op: str):
    """Matches the event names `%<op>` and `%<op>.<n>` (then a space)."""
    return re.compile(r"^%?" + re.escape(op) + r"(\.\d+)?( |$)").match


@functools.lru_cache(maxsize=4)
def op_seconds(path: str, op: str, lo: float, hi: float
               ) -> Tuple[int, float]:
    """(events, seconds) of the operations named `op` that lie wholly
    inside [lo, hi] ns, averaged over the device planes."""
    import jax
    mine = matcher(op)
    data = jax.profiler.ProfileData.from_file(path)
    count, total, planes = 0, 0.0, 0
    for plane in data.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        planes += 1
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for e in line.events:
                a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
                if a >= lo and b <= hi and mine(e.name):
                    count += 1
                    total += b - a
    planes = max(planes, 1)
    return count // planes, total / planes / 1e9


def ms_per_run(path: str, op: str, per: str) -> Optional[float]:
    t = program_trace.parse(path)
    if t["window"] is None:
        return None
    lo, hi = t["window"]
    runs, _ = program_trace.module_runs(t["events"], per, lo, hi)
    count, seconds = op_seconds(path, op, lo, hi)
    if not runs or not count:
        return None
    return 1000.0 * seconds / runs


def read(evidence, field, op, per, workload=None, part="matrix"):
    if field not in ("ms_per_run", "roofline_pct"):
        raise ValueError(f"named_op: unknown field {field!r}")
    if not evidence.get("trace"):
        return None
    path = trace_reduce.find_xplane(harness.TRACE_DIR)
    if path is None:
        return None
    ms = ms_per_run(path, op, per)
    if ms is None or field == "ms_per_run":
        return ms
    import jax
    cell = harness.load_cell(workload)
    nbytes = retention_cost.step_bytes(
        cell["config_sizes"], cell["engine"]["num_slots"], part)
    peak = flops.peak(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return retention_cost.roofline_share_pct(nbytes, ms / 1000.0, peak)
