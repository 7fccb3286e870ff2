"""The controls of `runners/ling_serve.py`'s limits: what the cell's
comparison reads when a part of the model is computed in the nearest
precision below the one the configuration states. The program has no such
path, so a control rounds what the program hands back, or patches the
model, and sends it through the same checks; each has to come out not
correct.

- `state`: the KDA state held in bfloat16. The cell's engine and its two
  programs as they are served, driven by hand as the cell's check 4 drives
  them; after every prefill's slot write and every decode step the float32
  recurrent states in the pool are rounded to bfloat16 and widened again
  (what a pool of that dtype would keep between steps).
- `router`: the router computed in bfloat16 (its input, its weight and the
  product rounded to bfloat16 before the sigmoid), read by the router's own
  check (`router_error`: every expert layer's scores on seeded rows against
  the reference's). In the model it cannot be seen: the activations' error
  on the way to a router is ten times a bfloat16 router's own (PERF.md,
  PR 33), which is why the router is held alone.

    chiprun -- python3 benchmarks/ling_precision_control.py \
        --workload ling3_flash_vl.serve_long_answer --seed 2147483777

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


def held_in_bfloat16(pool):
    """The pool with its float32 recurrent states ([slots, H, d, d])
    rounded to bfloat16 (8 exponent bits, 7 of mantissa) and widened
    again. An explicit rounding: a cast down and up is a pair a compiler
    may drop."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    return [Tensor(jax.lax.reduce_precision(t._value, 8, 7))
            if t._value.ndim == 4 and t._value.dtype == jnp.float32 else t
            for t in pool]


class router_in_bfloat16:
    """While entered, every `RoutedExperts` computes its scores from
    bfloat16 operands and rounds the product to bfloat16."""

    def __enter__(self):
        import jax.numpy as jnp

        from paddle_tpu.nn.layer import routed_experts as layer
        from paddle_tpu.ops._dispatch import run_op
        self._layer, self._was = layer, layer.RoutedExperts.choose

        def choose(self, m):
            def f(m, router, bias):
                logits = jnp.matmul(m.astype(jnp.bfloat16),
                                    router.astype(jnp.bfloat16))
                return layer.route(logits.astype(jnp.float32), bias,
                                   self.top_k, self.n_group, self.topk_group,
                                   self.scaling)
            return run_op(f, [m, self.router, self.router_bias],
                          "moe_route_bf16")
        layer.RoutedExperts.choose = choose
        return self

    def __exit__(self, *exc):
        self._layer.RoutedExperts.choose = self._was


def readings(cell: dict, seed: int, say=print) -> dict:
    from benchmarks.runners import ling_serve as runner

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
