"""The controls of `runners/nemotron_serve.py`'s limits: what the cell's
comparison reads when a part of the model is computed in the nearest
precision below the one the configuration states, through the same checks
(`ling_precision_control`'s two controls, on this model); each has to come
out not correct.

- `state_in_bfloat16`: the cell's engine and its two programs as they are
  served, driven by hand as the cell's check 4 drives them; after every
  prefill's slot write and every decode step the float32 Mamba-2 states
  in the pool are rounded to bfloat16 and widened again
  (`ling_precision_control.held_in_bfloat16`).
- `router_in_bfloat16`: the router computed in bfloat16, read by the
  router's own check.

    python3 benchmarks/nemotron_precision_control.py \
        --workload nemotron3_nano.serve_chat --seed 2147483777   # one chip

prints one `CONTROL {...}` line with the readings of the model as it is
and of both controls.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.ling_precision_control import (  # noqa: E402
    held_in_bfloat16, router_in_bfloat16,
)


def readings(cell: dict, seed: int, say=print) -> dict:
    from benchmarks.runners import nemotron_serve as runner

    sizes = cell["config_sizes"]
    lm = runner.build_model(sizes, seed)
    engine = runner.make_engine(lm, cell["engine"])
    try:
        out = {"seed": seed}
        for name, hold in (("as_it_is", None),
                           ("state_in_bfloat16", held_in_bfloat16)):
            found = runner.run_checks(lm, engine, cell, seed, say, hold=hold)
            out[name] = {"checks": found["checks"],
                         "routing": found["routing"],
                         "readings": found["readings"],
                         "correct": all(found["checks"].values())}
            del found
    finally:
        engine.stop(drain=False)
    with router_in_bfloat16():
        err = runner.router_error(lm, sizes, seed)
    out["router_in_bfloat16"] = {
        "router_error": err, "correct": bool(err <= runner.ROUTER_TOL)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU; nothing was run", file=sys.stderr)
        return 2
    import paddle_tpu as paddle
    from benchmarks import harness
    harness.place_cache()
    paddle.set_device("tpu")
    cell = harness.load_cell(args.workload)
    t0 = time.perf_counter()
    out = readings(cell, args.seed, say=lambda m: print(m, flush=True))
    out["seconds"] = round(time.perf_counter() - t0, 1)
    print("CONTROL " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
