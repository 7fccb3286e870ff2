"""The control of `runners/dots_serve.py`'s limit on the decode step's read
(`MLA_TOL`): what the cell's comparison reads when `mla_decode` keeps its
running maximum, sum and context in bfloat16 in place of float32: the
nearest precision below the one the configuration states (bfloat16 operands,
float32 statistics and accumulation). The program has no such path, so the
control patches the kernel's entry, builds the cell's engine over it and
sends it through the same checks; it has to come out not correct, by
`latent_read_keeps_its_precision` (the model's logits move by less than the
activations' own rounding: PERF.md, PR 35).

    chiprun -- python3 benchmarks/dots_precision_control.py \
        --workload dots_vlm1.serve_long_context --seed 2147483777

prints one `CONTROL {...}` line with the checks and the readings.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class statistics_in_bfloat16:
    """While entered, every program traced reads its latent pages through
    `mla_decode` with bfloat16 statistics and accumulation."""

    def __enter__(self):
        import jax.numpy as jnp

        from paddle_tpu.kernels import mla_decode as kernel
        self._kernel, self._was = kernel, kernel.mla_decode
        kernel.mla_decode = functools.partial(self._was, stats=jnp.bfloat16)
        return self

    def __exit__(self, *exc):
        self._kernel.mla_decode = self._was


def readings(cell: dict, seed: int, say=print) -> dict:
    from benchmarks.runners import dots_serve as runner

    lm = runner.build_model(cell["config_sizes"], seed)
    with statistics_in_bfloat16():
        engine = runner.make_engine(lm, cell["engine"])
        try:
            found = runner.run_checks(lm, engine, cell, seed, say)
        finally:
            engine.stop(drain=False)
    return {"seed": seed, "statistics_in_bfloat16": {
        "checks": found["checks"], "routing": found["routing"],
        "readings": found["readings"],
        "correct": all(found["checks"].values())}}


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
