"""The one-server rate sweep that finds a serve cell's knee. Not part of the
driver's command: run it once on the chip when a serve cell is added, write
its table into PERF.md and 0.8 x the knee into the cell file's `rate_rps`.

    python3 benchmarks/sweep.py --workload gpt2_large.serve_decode \
        --rates 1.5,2,2.5,3,3.5 --seconds 15 --repeats 2

One process, one engine, one warm-up; each rate is offered for `--seconds`
(after the mix's ramp) with the cell's own lengths, then the queue is left
to drain. For every rate and repeat it prints one JSON line: the client's
tails, the tokens per second completed, what failed or was shed, and the
mean time to first token of the first and of the last third of arrivals
(the client-side face of the queue wait).

The knee is the highest swept rate at which nothing failed or was shed and
the last third's time to first token is not above the first third's by more
than the two repeats of that rate differ from each other.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--drain", type=float, default=60.0,
                    help="seconds an over-full queue is given to empty")
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU; nothing was run", file=sys.stderr)
        return 2
    from benchmarks import harness
    harness.place_cache()
    from benchmarks.runners import llm_serve
    t0 = time.perf_counter()
    cell = harness.load_cell(args.workload)
    cell["mix"]["drain_s"] = args.drain
    ctx = harness.Context("tpu", args.seed, args.seconds, False,
                          harness.CompileWatch())
    state = llm_serve.build(cell, ctx)
    try:
        llm_serve.warm(state)
        print(f"sweep: set-up {time.perf_counter() - t0:.1f}s", flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            for rep in range(args.repeats):
                shed0 = state["engine"].stats()["counters"]["shed"]
                run = llm_serve.offer(state, args.seconds, rate_rps=rate)
                out = llm_serve.client_numbers(run, args.seconds)
                ttft = out.pop("ttft_ms")
                out.pop("late_ms")
                third = max(1, len(ttft) // 3)
                out.update(
                    rate_rps=rate, repeat=rep,
                    ttft_first_third_ms=statistics.fmean(ttft[:third]),
                    ttft_last_third_ms=statistics.fmean(ttft[-third:]),
                    shed=state["engine"].stats()["counters"]["shed"] - shed0,
                    compiled=ctx.compiled_in_window())
                print("SWEEP " + json.dumps(out), flush=True)
    finally:
        llm_serve.close(state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
