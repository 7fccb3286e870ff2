"""Reader `bench_span`: a statistic of one of the benchmark's own spans,
which the runner recorded on the host clock inside the window.

args: `span` (its name), `stat` ("mean" or "p95"), `scale` (default 1:
spans named `*_ms` are already in milliseconds, the others in seconds).
"""
from __future__ import annotations

import statistics

from ..stats import pct


def read(evidence, span, stat="mean", scale=1.0):
    values = (evidence.get("spans") or {}).get(span)
    if not values:
        return None
    if stat == "mean":
        return scale * statistics.fmean(values)
    if stat == "p95":
        return scale * pct(values, 95)
    raise ValueError(f"bench_span: unknown stat {stat!r}")
