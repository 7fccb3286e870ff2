"""The control of `runners/brumby_serve.py`'s limits: what the cell's
comparison reads when the recurrent state is held in the nearest precision
below the one the configuration states. The program has no such path (its
state is float32, as `configs/brumby_14b.json` assumes), so the control
wraps the model: after every prefill and every decode step the state the
model returns is rounded to bfloat16 and widened again, which is what a
pool of that dtype would keep between steps. The wrapped model goes through
the same `LLMEngine`, the same two programs at the cell's sizes and the
same comparison as the cell's check 3; it has to come out not correct.

    chiprun -- python3 benchmarks/state_precision_control.py \
        --workload brumby_14b.serve_long_prompt --seed 2147483777

prints one `CONTROL {...}` line (one process a seed: a model of 8.4 GB
leaves the chip only with its process).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def held_in_bfloat16(lm):
    """`lm` under the engine's cache contract with its state rounded to
    bfloat16 (8 exponent bits, 7 of mantissa) wherever it is handed back."""
    import jax

    from paddle_tpu import nn
    from paddle_tpu.ops._dispatch import run_op

    class StateHeldIn(nn.Layer):
        cache_tag = lm.cache_tag

        def __init__(self):
            super().__init__()
            self.lm = lm

        def init_cache(self, *args, **kw):
            return self.lm.init_cache(*args, **kw)

        def forward_cached(self, tokens, cache, positions, lengths=None):
            out, new = self.lm.forward_cached(tokens, cache, positions,
                                              lengths)
            # an explicit rounding: a cast down and up again is a pair the
            # compiler may drop
            return out, [run_op(lambda a: jax.lax.reduce_precision(a, 8, 7),
                                [c], "state_held_in") for c in new]

    return StateHeldIn()


def readings(cell: dict, seed: int, say=print) -> dict:
    """The cell's check 3 on the model as it is and on the control."""
    from paddle_tpu.serving import LLMConfig, LLMEngine

    from benchmarks.runners import brumby_serve as runner

    sizes, eng_cfg, chk = cell["config_sizes"], cell["engine"], cell["check"]
    steps = chk["decode_tokens"]
    lm = runner.build_model(sizes, seed)
    ids, n = runner.check_rows(cell, seed)
    ref = runner.reference_logits(lm, sizes, ids, n, steps)
    full = runner.full_logits(lm, ids, n, steps, chk["prompts"])
    out = {"seed": seed, "n": n.tolist(),
           "full_vs_ref": runner.rel_err(full, ref)}
    for name, model in (("float32", lm),
                        ("bfloat16", held_in_bfloat16(lm))):
        engine = LLMEngine(model, LLMConfig(
            num_slots=eng_cfg["num_slots"], max_len=eng_cfg["max_len"],
            prefill_buckets=tuple(eng_cfg["prefill_buckets"]),
            warmup_on_start=False))
        got = runner.engine_logits(engine, ids, n, steps)
        out[name] = {
            "vs_ref": runner.rel_err(got, ref),
            "growth": runner.error_growth(got, full, ref),
            "rms_ratio": runner.error_ratio(got, full, ref),
            "rms_ratio_by_step": [
                round(runner.error_ratio(got[:, i], full[:, i], ref[:, i]), 4)
                for i in range(steps + 1)],
            "rms_ratio_by_row": [
                round(runner.error_ratio(got[r], full[r], ref[r]), 3)
                for r in range(len(n))],
            "correct": bool(
                runner.rel_err(got, ref) <= runner.STATE_TOL
                and runner.error_growth(got, full, ref)
                <= runner.STATE_GROWTH_TOL)}
        say(f"{name}: {out[name]}")
        engine.stop(drain=False)
        del engine, got
        gc.collect()
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
    out = readings(cell, args.seed, say=lambda m: None)
    out["seconds"] = round(time.perf_counter() - t0, 1)
    print("CONTROL " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
