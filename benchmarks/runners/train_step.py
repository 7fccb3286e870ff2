"""Runner `train_step`: pretraining through `paddle.jit.TrainStep.__call__`,
one host batch per call, the way a user's loop calls it.

Cell file keys read here: `mix` (`batch`, `seq`, `ring`; see
`traffic/batch_ring.py`), `generator`, `train` (`lr`, `amp_dtype`,
`warm_steps`, `loss_window`). The configuration gives the sizes.

Set-up: the model's own init under `paddle.seed(seed)`, every embedding
table redrawn N(0, `initializer_range`) from the seed (`nn.Embedding`
initialises N(0, 1), which starts the MLM loss near 117 and makes agreement
with a reference meaningless), the plain reference's loss on the first
batch, the step compiled by its first call, `warm_steps` more calls.

The window: `step(*batch)` over the ring until the clock passes
`--seconds`, then ONE `block_until_ready`; the rate is over all steps
dispatched and all the time to that barrier. No step is fenced. The loop
only never runs more than `IN_FLIGHT` steps ahead of the device: before
dispatching step i it waits for the loss of step i - IN_FLIGHT, as a loop
that logs its loss a few steps late does. Without that a host that
dispatches in 2 ms would queue minutes of 27 ms steps and the drain would
outlast the window many times.

`correct`:
- the first step's loss equals the reference's on the same weights and batch
  within `LOSS_RTOL`;
- every loss of the window is finite;
- the mean loss of the window's last `loss_window` steps is below that of
  its first `loss_window` (the task is learnable: labels = inputs).
"""
from __future__ import annotations

import contextlib
import functools
import time

import numpy as np

from .. import flops, harness
from ..reference import blocks
from .common import named_arrays, redraw_embeddings

# Under amp the step computes the loss in bf16 and hands it back in bf16,
# whose spacing between 8 and 16 is 0.0625: the final rounding alone moves a
# loss of 11 by up to 0.031, 2.8e-3 relative (up to 2^-8 = 3.9e-3 just above
# a power of two). The roundings before it (bf16 matmuls, fp32 accumulation)
# average out over batch x seq positions and stay below that. 2^-7 = 7.8e-3
# is one whole bf16 step at the worst place: it holds bf16, fails any
# narrower type, and fails a dropped term of the objective (the NSP term
# alone is 0.69 of 11, 6e-2). The reference is float32 at "highest".
LOSS_RTOL = 2.0 ** -7
IN_FLIGHT = 4          # steps the host may be ahead of the device


def build(cell: dict, ctx) -> dict:
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import models

    sizes = cell["config_sizes"]
    train = cell["train"]
    dev = paddle.set_device(ctx.device).jax_device()
    paddle.seed(ctx.seed)
    base = models.ErnieModel(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        intermediate_size=sizes["intermediate_size"],
        max_position_embeddings=sizes["max_position_embeddings"],
        type_vocab_size=sizes["type_vocab_size"],
        hidden_dropout_prob=0.0)
    net = models.ErnieForPretraining(base)
    redraw_embeddings(net, ctx.seed, sizes["initializer_range"])

    ce = nn.CrossEntropyLoss()

    def loss_fn(logits, nsp_logits, ids, nsp):
        v = logits.shape[-1]
        return ce(logits.reshape([-1, v]), ids.reshape([-1])) \
            + ce(nsp_logits, nsp)

    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=train["lr"])
    step = paddle.jit.TrainStep(net, loss_fn, opt,
                                amp_dtype=train["amp_dtype"],
                                n_model_inputs=1)
    ring = harness.module("traffic", cell["generator"]).generate(
        cell["mix"], ctx.seed, sizes["vocab_size"])
    ctx.say(f"built {cell['config']}: "
            f"{sum(int(np.prod(p.shape)) for p in net.parameters())} "
            f"parameters on {dev}; ring of {len(ring)} batches "
            f"{ring[0][0].shape}")

    # the reference's loss on the first batch, before any step moves the
    # weights (the step donates them)
    ids0, nsp0 = ring[0]
    ref = jax.jit(functools.partial(
        blocks.ernie_pretrain_loss, n_layers=sizes["num_hidden_layers"],
        heads=sizes["num_attention_heads"]))
    ref_loss = float(ref(named_arrays(net), ids0, nsp0))
    return {"cell": cell, "ctx": ctx, "net": net, "step": step, "ring": ring,
            "ref_loss": ref_loss, "checks": {}, "next": 0}


def _call(state, i: int):
    ids, nsp = state["ring"][i % len(state["ring"])]
    return state["step"](ids, ids, nsp)


def warm(state) -> None:
    import jax
    ctx, train = state["ctx"], state["cell"]["train"]
    t0 = time.perf_counter()
    first = float(_call(state, 0))                # compiles, or loads
    ctx.say(f"first step in {time.perf_counter() - t0:.2f}s: loss {first!r}, "
            f"reference {state['ref_loss']!r}")
    rel = abs(first - state["ref_loss"]) / abs(state["ref_loss"])
    state["checks"]["first_loss_matches_reference"] = bool(rel <= LOSS_RTOL)
    ctx.say(f"|loss - reference| / reference = {rel:.3e} "
            f"(tolerance {LOSS_RTOL})")
    loss = None
    for i in range(1, 1 + int(train["warm_steps"])):
        loss = _call(state, i)
    if loss is not None:
        jax.block_until_ready(loss._value)
    state["next"] = 1 + int(train["warm_steps"])


def measure(state) -> dict:
    import jax
    ctx, cell = state["ctx"], state["cell"]
    mix, train = cell["mix"], cell["train"]
    seconds, i0 = ctx.seconds, state["next"]
    step, ring = state["step"], state["ring"]
    n_ring = len(ring)
    # host spans in the profiler's trace, in traced runs only
    span = jax.profiler.TraceAnnotation if ctx.trace \
        else contextlib.nullcontext
    ready, clock = jax.block_until_ready, time.perf_counter
    losses, waits, dispatches = [], [], []
    i = i0
    ctx.window_opens()
    t0 = clock()
    if ctx.trace:
        ctx.tracer.arm(t0 + seconds)
    while True:
        ta = clock()
        if ta - t0 >= seconds:
            break
        ids, nsp = ring[i % n_ring]
        with span("bench.train.wait"):
            if i - i0 >= IN_FLIGHT:
                ready(losses[-IN_FLIGHT]._value)
        tb = clock()
        with span("bench.train.dispatch"):
            losses.append(step(ids, ids, nsp))
        tc = clock()
        waits.append(tb - ta)
        dispatches.append(tc - tb)
        i += 1
    if ctx.trace:
        ctx.tracer.stop()      # the slice ends with the loop, before the drain
    ready(losses[-1]._value)
    window_s = clock() - t0
    ctx.window_closes()
    state["next"] = i

    # what XLA says the step program needs while it runs, on top of the live
    # arrays (after the window: this lowers the step once more)
    temp_bytes = int(step.memory_report(ids, ids, nsp).get("temp_bytes", 0))

    steps = i - i0
    values = np.asarray(jax.device_get([x._value for x in losses]),
                        dtype=np.float64)
    finite = np.isfinite(values)
    k = min(int(train["loss_window"]), steps // 2)
    head, tail = float(values[:k].mean()), float(values[-k:].mean())
    ctx.say(f"{steps} steps in {window_s:.3f}s; loss first {k} = {head:.4f}, "
            f"last {k} = {tail:.4f}")
    checks = dict(state["checks"])
    checks["losses_finite"] = bool(finite.all())
    checks["loss_falls"] = bool(tail < head)
    tokens = steps * int(mix["batch"]) * int(mix["seq"])
    per_token = flops.train_flops_per_token(cell["config_sizes"],
                                            int(mix["seq"]))
    ctx.say(f"host per step: wait {1e3 * np.mean(waits):.3f} ms, dispatch "
            f"{1e3 * np.mean(dispatches):.3f} ms; {per_token:.0f} FLOPs a "
            f"token, {tokens / window_s * per_token / 1e12:.2f} model "
            "TFLOP/s")
    return {
        "attempted": steps, "failed": int((~finite).sum()), "checks": checks,
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "program_temp_bytes": temp_bytes,
        "evidence": {"spans": {"bench.train.dispatch": dispatches,
                               "bench.train.wait": waits},
                     "monitor": None},
    }


def close(state) -> None:
    pass
