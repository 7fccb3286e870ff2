"""From a profiler trace (`.xplane.pb`) to device numbers.

Reads with `jax.profiler.ProfileData.from_file` and nothing else. Two steps,
so the arithmetic can be checked on a hand-written event list:

    events  = load(path)          # planes/lines -> plain lists of intervals
    summary = reduce(events)      # busy, idle share, per-op totals, gaps

What is read from the trace (TPU, jax 0.9; `describe(path)` prints the
planes and lines of a trace so this can be checked by eye):

- device planes are named `/device:TPU:<n>`; on each, the line `XLA Ops`
  holds one event per executed HLO operation and the line `XLA Modules`
  one per executed program;
- the plane `/host:CPU` holds one line per host thread; the benchmark's own
  `jax.profiler.TraceAnnotation`s are the events whose name starts with
  `bench.`. `bench.trace_window` spans the traced part of the measured
  window and sets `window_s`; without it the window is the extent of the
  device events.

Busy time is the union of the op intervals on a device, clipped to the
window; the idle share is 1 - busy / window, averaged over the device planes.
A gap is the time between two busy intervals. A gap inside a program's
interval is the device's own (`within_program`); a gap between programs is
labelled with the `bench.*` annotation open on the host at its middle, or
`unattributed`.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]          # name, start_ns, end_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "bench."
WINDOW_ANNOTATION = "bench.trace_window"


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` dir."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _intervals(line, prefix: str = "") -> List[Interval]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events if e.name.startswith(prefix)]


def load(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
        "host": [Interval of bench.* annotations]}"""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices[plane.name] = {
                    "ops": _intervals(lines[OPS_LINE]),
                    "modules": (_intervals(lines[MODULES_LINE])
                                if MODULES_LINE in lines else [])}
            else:
                raise ValueError(
                    f"{path}: device plane {plane.name!r} has no line "
                    f"{OPS_LINE!r} (has {sorted(lines)}); read "
                    "`describe(path)` and teach this file the new layout")
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host += _intervals(ln, HOST_PREFIX)
    return {"devices": devices, "host": host}


def describe(path: str, per_line: int = 4) -> str:
    """Planes, lines, event counts and the first events: read this before
    trusting `load` on a trace from a new JAX or a new device."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = [path]
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r} lines={len(lines)}")
        for ln in lines:
            evs = list(ln.events)
            out.append(f"  LINE {ln.name!r} events={len(evs)}")
            for e in evs[:per_line]:
                out.append(f"    {e.name[:100]!r} start_ns={e.start_ns} "
                           f"dur_ns={e.duration_ns}")
    return "\n".join(out)


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_kind(name: str, width: int = 72) -> str:
    """`%fusion.7 = bf16[128,768]{1,0:T(8,128)} fusion(...)` ->
    `fusion bf16[128,768]`: the opcode and the result type without layouts,
    so that the same operation of every layer adds up under one name."""
    if " = " not in name:
        return name[:width]
    rhs = _LAYOUT.sub("", name.split(" = ", 1)[1])
    if rhs.startswith("("):                      # a tuple type
        depth = 0
        for i, c in enumerate(rhs):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        rtype, rest = rhs[:i + 1], rhs[i + 1:].lstrip()
    else:
        rtype, _, rest = rhs.partition(" ")
    return f"{rest.split('(', 1)[0]} {rtype}"[:width]


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _clip(ivs: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ivs
            if b > lo and a < hi]


def _label_at(t: float, host: Sequence[Interval]) -> str:
    """The innermost (latest-opened) bench.* annotation open at time t."""
    open_now = [iv for iv in host if iv[1] <= t < iv[2]
                and iv[0] != WINDOW_ANNOTATION]
    return max(open_now, key=lambda iv: iv[1])[0] if open_now \
        else "unattributed"


def reduce(events: dict, top: int = 10) -> Optional[dict]:
    """The device numbers of one trace, or None when no device operation
    is in it. Seconds throughout; shares in percent."""
    devices = events["devices"]
    host = events["host"]
    window = [iv for iv in host if iv[0] == WINDOW_ANNOTATION]
    all_ops = [iv for d in devices.values() for iv in d["ops"]]
    if not all_ops:
        return None
    if window:
        lo, hi = window[0][1], window[0][2]
    else:
        lo = min(a for _, a, _ in all_ops)
        hi = max(b for _, _, b in all_ops)
    window_ns = hi - lo
    if window_ns <= 0:
        return None

    busy_ns, op_ns, gap_ns, n_gaps = [], {}, {}, 0
    module_ns: Dict[str, List[float]] = {}
    longest = ("", 0.0)
    for dev in devices.values():
        ops = _clip(dev["ops"], lo, hi)
        modules = _clip(dev["modules"], lo, hi)
        for n, a, b in ops:
            k = op_kind(n)
            op_ns[k] = op_ns.get(k, 0.0) + (b - a)
        for n, a, b in dev["modules"]:
            if a >= lo and b <= hi:              # whole executions only
                cnt_tot = module_ns.setdefault(n, [0.0, 0.0])
                cnt_tot[0] += 1
                cnt_tot[1] += b - a
        merged = _union([(a, b) for _, a, b in ops])
        busy_ns.append(sum(b - a for a, b in merged))
        inside = _union([(a, b) for _, a, b in modules])
        starts = [a for a, _ in inside]
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):       # the gaps
            if b - a <= 0:
                continue
            n_gaps += 1
            mid = (a + b) / 2
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid < inside[i][1]:
                label = "within_program"
            else:
                label = _label_at(mid, host)
            gap_ns[label] = gap_ns.get(label, 0.0) + (b - a)
            if b - a > longest[1]:
                longest = (label, b - a)
    n_dev = len(devices)
    busy_s = sum(busy_ns) / n_dev / 1e9
    window_s = window_ns / 1e9

    def ranked(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[n, v / n_dev / 1e9] for n, v in rows]

    modules = sorted(([n, int(c), t / n_dev / 1e9]
                      for n, (c, t) in module_ns.items()),
                     key=lambda r: -r[2])
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share_pct": 100.0 * (1.0 - busy_s / window_s),
        "devices": n_dev, "gaps": n_gaps,
        "device_ops": ranked(op_ns),            # [[name, seconds], ...]
        "idle_gaps": ranked(gap_ns),            # [[label, seconds], ...]
        "longest_gap": [longest[0], longest[1] / 1e9],
        # whole executions inside the slice: [[program, runs, seconds]]
        "modules": modules[:top],
    }


if __name__ == "__main__":
    import argparse
    import json
    ap = argparse.ArgumentParser(description="describe or reduce a trace")
    ap.add_argument("trace", help="an .xplane.pb, or a start_trace dir")
    ap.add_argument("--describe", action="store_true")
    a = ap.parse_args()
    p = a.trace if a.trace.endswith(".pb") else find_xplane(a.trace)
    if p is None:
        raise SystemExit(f"no .xplane.pb under {a.trace}")
    print(describe(p) if a.describe else json.dumps(reduce(load(p)), indent=1))
