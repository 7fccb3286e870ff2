"""`open_loop`: requests sent on a schedule, whatever the server does.

Mix parameters (one JSON object, the cell file's `mix`):

    rate_rps        mean arrivals per second (fixed in the cell, never searched)
    prompt_tokens   {"median", "sigma", "min", "max"}  lognormal, clipped
    output_tokens   the same, for max_new_tokens (no EOS: the length is exact)
    ramp_s          unmeasured arrivals before the window, so occupancy is
                    stationary when it opens
    drain_s         how long requests due in the window are waited for after it
    schedule_seed   fixes the mix's one realisation (below)

**Every seed offers the same work.** The window holds n = round(rate x
seconds) requests. Their prompt lengths, output lengths and inter-arrival
gaps are the n stratified quantiles of the three distributions (lognormal,
lognormal, exponential; gaps scaled to sum to the window exactly), paired
and ordered once by `schedule_seed`. That is one period of a periodic
arrival process. `--seed` picks the phase at which the window opens (so
the order differs from seed to seed, the set never does), the token ids,
and, in the runner, the weights. The ramp replays the end of the period
before the window. A run-to-run difference is then the system's, not the
draw's: with plain Poisson draws the 95th percentile of some tens of
requests moves by tens of percent between seeds.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float              # relative to the window's start; < 0 = ramp
    prompt: np.ndarray        # int32 token ids
    max_new: int
    measured: bool            # due inside [0, seconds)


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified quantiles of lognormal(median, sigma), clipped, ints."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])
                   ).astype(np.int64)


def period(mix: dict, seconds: float):
    """One period: (arrival offsets in [0, seconds), prompt lengths, output
    lengths), all of length n, the same for every seed."""
    n = max(1, int(round(float(mix["rate_rps"]) * seconds)))
    order = np.random.default_rng(int(mix["schedule_seed"]))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = order.permutation(gaps) * (seconds / gaps.sum())
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts = order.permutation(_lognormal_quantiles(mix["prompt_tokens"], n))
    outputs = order.permutation(_lognormal_quantiles(mix["output_tokens"], n))
    return offsets, prompts, outputs


def generate(mix: dict, seconds: float, seed: int, vocab: int
             ) -> List[Request]:
    """The requests of one run in due order: the ramp, then the window."""
    offsets, prompts, outputs = period(mix, seconds)
    n = len(offsets)
    rng = np.random.default_rng(seed)
    phase = offsets[int(rng.integers(n))]
    ramp = float(mix["ramp_s"])
    reqs: List[Request] = []
    for i in range(n):
        due = (offsets[i] - phase) % seconds
        laps = [0] + [m for m in range(1, int(ramp // seconds) + 2)
                      if due - m * seconds >= -ramp]
        for m in laps:
            reqs.append(Request(
                due_s=float(due - m * seconds),
                prompt=rng.integers(0, vocab, int(prompts[i])
                                    ).astype(np.int32),
                max_new=int(outputs[i]), measured=(m == 0)))
    reqs.sort(key=lambda r: r.due_s)
    return reqs
