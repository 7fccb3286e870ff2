"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    (or: python3 -m benchmarks.run ...)

One process, no child that touches JAX. Set-up (build, weights, compile or
cache load, warm wave, reference check) is timed from the start of the
process to the start of the measured window. Progress goes to the earlier
lines of the standard output; the LAST line is the contract's one JSON
object. `--trace 0` reports the cell's end-to-end metrics with the monitor
and the profiler off: the program as a user gets it. `--trace 1` reports
the per-layer metrics and a breakdown of the device trace.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result: there is no way to make it pass on a CPU.
"""
from __future__ import annotations

import os
import sys
import time

_T0 = time.perf_counter()        # set-up is counted from here
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse  # noqa: E402
import json  # noqa: E402


def _say(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.2f}s] {msg}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import harness
    bench = harness.load_benchmark()
    declared = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in declared:
        print(f"benchmark: {args.workload!r} is not a cell of BENCHMARK.json "
              f"(have {sorted(declared)})", file=sys.stderr)
        return 2
    chips = int(declared[args.workload]["chips"])
    cell = harness.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind}); nothing was run", file=sys.stderr)
        return 2

    cache_dir = harness.place_cache()
    _say(f"{args.workload} seed={args.seed} seconds={args.seconds} "
         f"trace={args.trace} on {len(devices)} x {devices[0].device_kind}; "
         f"jax cache at {cache_dir}")

    trace = bool(args.trace)
    watch = harness.CompileWatch()
    ctx = harness.Context("tpu", args.seed, args.seconds, trace, watch,
                          harness.Tracer(), _say)
    runner = harness.module("runners", cell["runner"])
    state = runner.build(cell, ctx)
    try:
        runner.warm(state)
        setup_watch = watch.snapshot()
        setup_s = time.perf_counter() - _T0
        _say(f"set-up done in {setup_s:.2f}s: {setup_watch}")
        result = runner.measure(state)
    finally:
        runner.close(state)
    compiled = ctx.compiled_in_window()
    checks = dict(result["checks"])
    checks["no_compile_in_window"] = not any(compiled.values())
    _say(f"checks: {checks}; compiled in the window: {compiled}")

    line = {"correct": all(checks.values()),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    summary = None
    if trace:
        summary = ctx.tracer.summary()
        if summary is None:
            raise RuntimeError("the traced slice holds no device operation: "
                               f"nothing to read under {ctx.tracer.out_dir}")
        evidence = dict(result["evidence"], setup=setup_watch, trace=summary)
        line["metrics"] = harness.per_layer_metrics(bench, args.workload,
                                                    evidence)
        if summary is not None:
            line["breakdown"] = {"device_ops": summary["device_ops"],
                                 "idle_gaps": summary["idle_gaps"]}
            _say(f"trace: {json.dumps(summary)}")
    else:
        metrics = dict(result["end_to_end"], setup_s=setup_s)
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]
                 if "workloads" not in m or args.workload in m["workloads"]}
        missing = sorted(set(units) - set(metrics))
        if missing:
            raise RuntimeError(f"runner {cell['runner']} did not report "
                               f"{missing} for {args.workload}")
        line["metrics"] = {n: {"value": float(metrics[n]), "unit": units[n]}
                           for n in units}
    line["device"] = harness.device_block(
        devices, chips, summary, result.get("program_temp_bytes", 0))
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
