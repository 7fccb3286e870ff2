"""`sweep.py` for a serve cell of any runner: the runner is the one the
cell file names under `runner` (it has to expose `build`, `warm`, `offer`,
`client_numbers` and `close`, as `runners/llm_serve.py` and
`runners/brumby_serve.py` do). Same arguments, same `SWEEP {...}` lines,
same rule for the knee (see `sweep.py` and the README).

    python3 benchmarks/sweep_cell.py --workload brumby_14b.serve_long_prompt \
        --rates 1,1.5,2,2.5 --seconds 20 --repeats 2
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
    t0 = time.perf_counter()
    cell = harness.load_cell(args.workload)
    cell["mix"]["drain_s"] = args.drain
    runner = harness.module("runners", cell["runner"])
    ctx = harness.Context("tpu", args.seed, args.seconds, False,
                          harness.CompileWatch())
    state = runner.build(cell, ctx)
    try:
        runner.warm(state)
        print(f"sweep: set-up {time.perf_counter() - t0:.1f}s; checks "
              f"{state['checks']}", flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            for rep in range(args.repeats):
                shed0 = state["engine"].stats()["counters"]["shed"]
                run = runner.offer(state, args.seconds, rate_rps=rate)
                out = runner.client_numbers(run, args.seconds)
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
        runner.close(state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
