"""Reader `program_trace`: the program's own spans (`paddle_tpu.monitor.span`,
which are `jax.profiler.TraceAnnotation`s) read off the same trace, and the
same clock, as the device plane.

Reads the slice's `.xplane.pb` (`harness.TRACE_DIR`) itself, in the layout
`trace_reduce` documents and with its constants: the device planes'
operation and program intervals, the `bench.trace_window` annotation, and
from `/host:CPU` the events named `llm.*` / `jit.*` of the ONE line that
holds `llm.step`, the scheduler thread. Handler and client threads label nothing. Returns None
with no trace, and None where the program has no such span or program name
(a commit before the spans existed).

Three numbers come out of it:

- the between-program idle time of the device, the gaps `trace_reduce.reduce`
  counts (union of ops, less the gaps inside a module's interval), with each
  gap's time SPLIT BY OVERLAP over the spans open on the scheduler thread,
  and not given whole to the one span open at its middle. A part of a gap
  belongs to the group of the innermost span over it that has one: `admit`
  (`llm.admit` and everything under it), `dispatch` (`llm.decode.dispatch`
  and its `jit.to_static.*` children), `read` (`llm.decode.read`), `emit`
  (`llm.emit` and the rest of `llm.step`), `park` (`llm.park`: nothing to
  run), `other` (a span outside all of these); what no span covers is
  `unattributed`. The groups add up to the between-program idle time;
- the mean duration of a named span, over the spans wholly inside the slice;
- the device time of one whole execution of a NAMED program (`XLA Modules`
  events are named `jit_<name>(<fingerprint>)`).

args: `field`:
  "module_ms"          `module`: mean device ms per whole execution
  "span_ms"            `span`: mean ms of that span
  "idle_ms"            `under` (a group above), `per` (a module name): the
                       group's idle ms per whole execution of that program,
                       so that the groups of one `per` add
  "unattributed_pct"   100 * unattributed / between-program idle time

`python3 -m benchmarks.readers.program_trace <.xplane.pb | trace dir>` prints
the split by group and by innermost span, the spans' means and the named
programs of a trace: the view a `perf_opt` issue sizes its suspects on.
"""
from __future__ import annotations

import bisect
import functools
from typing import Dict, List, Optional, Sequence, Tuple

from .. import harness, trace_reduce
from ..trace_reduce import Interval, _clip, _union

SPAN_PREFIXES = ("llm.", "jit.")
ANCHOR = "llm.step"
UNATTRIBUTED = "unattributed"
# span name -> group, tried from the innermost span of a path outwards
GROUPS = {"llm.admit": "admit", "llm.decode.dispatch": "dispatch",
          "llm.decode.read": "read", "llm.step": "emit", "llm.park": "park"}

Segment = Tuple[float, float, str]            # start_ns, end_ns, group


def scheduler_spans(lines: Sequence[Sequence[Interval]]) -> List[Interval]:
    """The `llm.*` / `jit.*` events of the one line (thread) that holds
    `llm.step`; [] when no line does."""
    def steps(line):
        return sum(1 for n, _, _ in line if n == ANCHOR)
    best = max(lines, key=steps, default=())
    if not steps(best):
        return []
    return [iv for iv in best if iv[0].startswith(SPAN_PREFIXES)]


def group_of(path: Sequence[str]) -> str:
    """The group of a span path (outermost span first): that of the
    innermost span that names one."""
    for name in reversed(path):
        if name in GROUPS:
            return GROUPS[name]
    return "other"


def segments(spans: Sequence[Interval], label=group_of) -> List[Segment]:
    """The spans of one thread flattened to disjoint, sorted segments, each
    under `label(path)` of the spans open over it (default: their group).
    Spans of one thread nest, so the open ones sorted by start are the path
    from the outermost in."""
    marks = sorted([(a, 1, i) for i, (_, a, b) in enumerate(spans) if b > a]
                   + [(b, 0, i) for i, (_, a, b) in enumerate(spans) if b > a])
    out: List[Segment] = []
    open_now = set()
    prev = 0.0
    for t, opens, i in marks:                 # at one time, ends come first
        if open_now and t > prev:
            path = [spans[j][0] for j in sorted(
                open_now, key=lambda j: (spans[j][1], -spans[j][2]))]
            out.append((prev, t, label(path)))
        prev = t
        if opens:
            open_now.add(i)
        else:
            open_now.discard(i)
    return out


def between_program_gaps(dev: dict, lo: float, hi: float
                         ) -> List[Tuple[float, float]]:
    """The idle intervals of one device inside [lo, hi] that lie between
    programs: `trace_reduce.reduce`'s gaps, less those whose middle is
    inside a module's interval (`within_program` there)."""
    busy = _union([(a, b) for _, a, b in _clip(dev["ops"], lo, hi)])
    inside = _union([(a, b) for _, a, b in _clip(dev["modules"], lo, hi)])
    starts = [a for a, _ in inside]
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a <= 0:
            continue
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if not (i >= 0 and mid < inside[i][1]):
            gaps.append((a, b))
    return gaps


def split(gaps: Sequence[Tuple[float, float]], segs: Sequence[Segment]
          ) -> Dict[str, float]:
    """ns of the gaps under each group, by overlap; the rest of every gap
    is `unattributed`. The values add up to the gaps' total."""
    starts = [s[0] for s in segs]
    out: Dict[str, float] = {UNATTRIBUTED: 0.0}
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            part = min(b, segs[i][1]) - max(a, segs[i][0])
            if part > 0:
                out[segs[i][2]] = out.get(segs[i][2], 0.0) + part
                covered += part
            i += 1
        out[UNATTRIBUTED] += (b - a) - covered
    return out


def window(events: dict) -> Optional[Tuple[float, float]]:
    """The slice, as `trace_reduce.reduce` takes it: the
    `bench.trace_window` annotation, else the extent of the device ops."""
    marks = [iv for iv in events["host"]
             if iv[0] == trace_reduce.WINDOW_ANNOTATION]
    if marks:
        return marks[0][1], marks[0][2]
    ops = [iv for d in events["devices"].values() for iv in d["ops"]]
    if not ops:
        return None
    return min(a for _, a, _ in ops), max(b for _, _, b in ops)


def module_runs(events: dict, module: str, lo: float, hi: float
                ) -> Tuple[float, float]:
    """(whole executions inside the slice, their ns) of the program named
    `module`, averaged over the device planes."""
    runs = [b - a for d in events["devices"].values()
            for n, a, b in d["modules"]
            if a >= lo and b <= hi
            and (n == module or n.startswith(module + "("))]
    n_dev = max(len(events["devices"]), 1)
    return len(runs) / n_dev, sum(runs) / n_dev


def idle_split(events: dict, spans: Sequence[Interval], lo: float,
               hi: float, label=group_of) -> Dict[str, float]:
    """Between-program idle ns by group (or by another `label` of the span
    path), averaged over the device planes."""
    segs = segments(spans, label)
    total: Dict[str, float] = {}
    for dev in events["devices"].values():
        for k, v in split(between_program_gaps(dev, lo, hi), segs).items():
            total[k] = total.get(k, 0.0) + v
    n_dev = max(len(events["devices"]), 1)
    return {k: v / n_dev for k, v in total.items()}


def load(path: str) -> Tuple[dict, List[Interval]]:
    """(`events`, `spans`) in one pass over the file. `events` has the
    shape `trace_reduce.load` gives, with the operations' names left empty:
    only their intervals are read here, and converting half a million HLO
    strings is most of that function's time. `spans` are
    `scheduler_spans` of the host plane's lines."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[Interval] = []
    lines: List[List[Interval]] = []
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            by_name = {ln.name: ln for ln in plane.lines}
            ops, modules = (by_name.get(trace_reduce.OPS_LINE),
                            by_name.get(trace_reduce.MODULES_LINE))
            devices[plane.name] = {
                "ops": [("", float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in (ops.events if ops else ())],
                "modules": trace_reduce._intervals(modules) if modules
                else []}
        elif plane.name == trace_reduce.HOST_PLANE:
            lines = [trace_reduce._intervals(ln) for ln in plane.lines]
            host = [iv for ln in lines for iv in ln
                    if iv[0] == trace_reduce.WINDOW_ANNOTATION]
    return {"devices": devices, "host": host}, scheduler_spans(lines)


@functools.lru_cache(maxsize=1)
def parse(path: str) -> dict:
    """One parse and one split per trace file, however many metrics read
    it: `events`, `spans` (`load`), `window`, and `idle` (None without
    spans or device operations)."""
    events, spans = load(path)
    win = window(events)
    idle = idle_split(events, spans, *win) if spans and win else None
    return {"events": events, "spans": spans, "window": win, "idle": idle}


def read(evidence, field, module=None, span=None, under=None, per=None):
    if field not in ("module_ms", "span_ms", "idle_ms", "unattributed_pct"):
        raise ValueError(f"program_trace: unknown field {field!r}")
    if not evidence.get("trace"):
        return None
    path = trace_reduce.find_xplane(harness.TRACE_DIR)
    if path is None:
        return None
    t = parse(path)
    if t["window"] is None:
        return None
    lo, hi = t["window"]
    if field == "module_ms":
        runs, ns = module_runs(t["events"], module, lo, hi)
        return ns / runs / 1e6 if runs else None
    if field == "span_ms":
        durs = [b - a for n, a, b in t["spans"]
                if n == span and a >= lo and b <= hi]
        return sum(durs) / len(durs) / 1e6 if durs else None
    idle = t["idle"]
    if idle is None:
        return None
    if field == "idle_ms":
        runs, _ = module_runs(t["events"], per, lo, hi)
        return idle.get(under, 0.0) / runs / 1e6 if runs else None
    total = sum(idle.values())
    return 100.0 * idle[UNATTRIBUTED] / total if total else None


def _count_mean_ms(durations: Dict[str, List[float]]) -> dict:
    return {n: [len(d), sum(d) / len(d) / 1e6] for n, d in durations.items()}


if __name__ == "__main__":
    # python3 -m benchmarks.readers.program_trace <.xplane.pb | trace dir>:
    # the split by group and by innermost span, span means, named programs
    import json
    import sys
    arg = sys.argv[1]
    t = parse(arg if arg.endswith(".pb") else trace_reduce.find_xplane(arg))
    lo, hi = t["window"]
    spans: Dict[str, List[float]] = {}
    for n, a, b in t["spans"]:
        if a >= lo and b <= hi:
            spans.setdefault(n, []).append(b - a)
    programs: Dict[str, List[float]] = {}
    for dev in t["events"]["devices"].values():
        for n, a, b in dev["modules"]:
            if a >= lo and b <= hi:
                programs.setdefault(n.split("(")[0], []).append(b - a)
    by_span = idle_split(t["events"], t["spans"], lo, hi,
                         lambda path: path[-1])
    print(json.dumps({
        "window_s": (hi - lo) / 1e9,
        "idle_s_by_group": {k: v / 1e9 for k, v in (t["idle"] or {}).items()},
        "idle_s_by_innermost_span": {k: v / 1e9 for k, v in by_span.items()},
        "span_count_mean_ms": _count_mean_ms(spans),
        "program_runs_mean_ms": _count_mean_ms(programs)}, indent=1))
