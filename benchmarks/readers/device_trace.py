"""Reader `device_trace`: a number of the reduced device trace
(`trace_reduce.reduce`), i.e. of the profiler's device plane, never of a
host clock.

args: `field`:
  "idle_share_pct"     100 * (1 - union of device-op intervals / traced slice)
  "ms_per_run"         mean device time of one whole execution, inside the
                       slice, of the program that took most of its time
                       (for a train cell: the step program), from the
                       `XLA Modules` line
"""
from __future__ import annotations


def read(evidence, field):
    trace = evidence.get("trace")
    if not trace:
        return None
    if field == "idle_share_pct":
        return trace["idle_share_pct"]
    if field == "ms_per_run":
        if not trace["modules"]:
            return None
        _, runs, seconds = trace["modules"][0]
        return 1000.0 * seconds / runs
    raise ValueError(f"device_trace: unknown field {field!r}")
