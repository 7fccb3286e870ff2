"""chip_smoke.py — the quickest proof that the main path still runs on the chip.

    python chip_smoke.py             one TPU chip: train, serve, kernel,
                                     latent, ssd (--phases names a part)
    python chip_smoke.py --chips 4   four chips: ONLY the sharded paths
                                     (dp2 x mp2 train step, sp=4 ring
                                     attention) and what they are compared with

One process; nothing here starts a child that touches JAX (a chip belongs to
one process at a time). Every phase drives the framework through the entry
points a user calls, at the full width of a model the repo ships, checks what
comes out by the repo's own means, and prints one JSON line. A phase that
raises, or whose check fails, ends the script with a non-zero code: nothing
is caught and carried past. The LAST line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The seconds on the phase lines are smoke timings (set-up + compile to the
first result, then a short warm part): they say the path runs, not how fast.
Benchmark numbers come from the benchmark, never from here.

On a machine without a TPU the script fails before any phase; there is no
option that lets it pass on a CPU. tests/test_chip_smoke.py runs each phase
function at a tiny size on the CPU to keep the control flow honest.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import json
import sys
import threading
import time
from typing import Callable, Sequence, Tuple

import numpy as np

NOTE = "smoke timings, not benchmark numbers"


class SmokeFailure(AssertionError):
    """A phase ran to its end and its check did not hold."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Lowerings:
    """Counts programs JAX lowers (every compile or persistent-cache fetch
    starts with one): flat across a span = the span compiled nothing."""

    _EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, _duration, **_kw):
        if event == self._EVENT:
            self.n += 1


def _trace_compiles() -> int:
    """The repo's own ledger of traced builds (core/executable.py)."""
    from paddle_tpu import monitor
    return int(monitor.snapshot()["counters"].get("trace_compile", 0))


def _report(phase: str, setup_s: float, steady_s: float, check: dict) -> dict:
    import jax
    from paddle_tpu import _native
    line = {"phase": phase, "setup_s": round(setup_s, 3),
            "steady_s": round(steady_s, 3), "timing": NOTE, "check": check,
            "jax_cache_dir": jax.config.jax_compilation_cache_dir,
            "native": bool(_native.available())}
    print(json.dumps(line), flush=True)
    return line


def _rel_err(got, want) -> float:
    """max|got - want| over max|want|, in float32."""
    got = np.asarray(got, dtype=np.float32)
    want = np.asarray(want, dtype=np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))


# ---- train ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainCfg:
    build: Callable          # () -> ErnieModel
    batch: int = 32
    seq: int = 128
    steps: int = 4           # per-step calls, and the length of each run()
    seed: int = 0


def _ernie_base():
    from paddle_tpu import models
    return models.ernie_base()


TRAIN_FULL = TrainCfg(build=_ernie_base)


def _pretrain_loss():
    import paddle_tpu.nn as nn
    ce = nn.CrossEntropyLoss()

    def loss_fn(logits, nsp_logits, ids, nsp):
        v = logits.shape[-1]
        return ce(logits.reshape([-1, v]), ids.reshape([-1])) \
            + ce(nsp_logits, nsp)
    return loss_fn


def _pretrain_batch(cfg, vocab):
    rng = np.random.default_rng(cfg.seed)
    ids = rng.integers(0, vocab, (cfg.batch, cfg.seq)).astype(np.int32)
    nsp = rng.integers(0, 2, (cfg.batch,)).astype(np.int32)
    return ids, nsp


def phase_train(cfg: TrainCfg, device: str, lowerings: Lowerings) -> dict:
    """ERNIE pretraining through `paddle.jit.TrainStep`: a few steps through
    `__call__`, then `run()` (the device-side loop) twice, all on one batch
    so the loss must fall."""
    import paddle_tpu as paddle
    from paddle_tpu import models

    t0 = time.perf_counter()
    dev = paddle.set_device(device).jax_device()
    paddle.set_flags({"FLAGS_monitor": True})
    paddle.seed(cfg.seed)
    base = cfg.build()
    net = models.ErnieForPretraining(base)
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=1e-4)
    step = paddle.jit.TrainStep(net, _pretrain_loss(), opt,
                                amp_dtype="bfloat16", n_model_inputs=1)
    vocab, hidden = base.embeddings.word_embeddings.weight.shape
    ids_np, nsp_np = _pretrain_batch(cfg, vocab)
    ids, nsp = paddle.to_tensor(ids_np), paddle.to_tensor(nsp_np)
    ids_n = paddle.to_tensor(np.stack([ids_np] * cfg.steps))
    nsp_n = paddle.to_tensor(np.stack([nsp_np] * cfg.steps))

    losses = [float(step(ids, ids, nsp))]                  # compiles __call__
    losses += [float(x) for x in step.run(ids_n, ids_n, nsp_n).numpy()]
    setup_s = time.perf_counter() - t0                     # ... and run()

    warm = (_trace_compiles(), lowerings.n)
    t1 = time.perf_counter()
    losses += [float(step(ids, ids, nsp)) for _ in range(cfg.steps - 1)]
    losses += [float(x) for x in step.run(ids_n, ids_n, nsp_n).numpy()]
    steady_s = time.perf_counter() - t1
    after = (_trace_compiles(), lowerings.n)

    _require(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    _require(losses[-1] < losses[0],
             f"train: loss did not fall on a repeated batch: {losses}")
    _require(after == warm, "train: a second call of a warm signature "
             f"compiled (trace_compile, lowerings) {warm} -> {after}")
    stray = [n for n, p in net.named_parameters()
             if p._value.devices() != {dev}]
    _require(not stray, f"train: parameters not on {dev}: {stray[:3]}")
    return _report("train", setup_s, steady_s, {
        "model": {"layers": len(base.layers), "hidden": int(hidden),
                  "heads": base.layers[0].attention.num_heads,
                  "vocab": int(vocab)},
        "batch": cfg.batch, "seq": cfg.seq, "optimizer_steps": len(losses),
        "loss_first": losses[0], "loss_last": losses[-1],
        "params_on": str(dev), "steady_compiles": 0})


# ---- serve ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeCfg:
    build: Callable          # () -> GPTModel
    num_slots: int = 8
    max_len: int = 256
    # one prefill executable each (LLMConfig.prefill_buckets): three, not
    # the default ladder's six, keeps warm-up inside the smoke's time
    prefill_buckets: Tuple[int, ...] = (16, 64, 256)
    max_new_tokens: int = 8
    prompt_lens: Tuple[int, ...] = (7, 45, 120, 200)
    seed: int = 0


def _gpt2_small():
    from paddle_tpu.models.gpt import gpt2_small
    return gpt2_small()


SERVE_FULL = ServeCfg(build=_gpt2_small)


def _generate_concurrently(host, port, prompts, max_new):
    """One client connection per request, all in flight together."""
    from paddle_tpu.inference.server import PredictorClient
    results = [None] * len(prompts)

    def one(i):
        cli = PredictorClient(host, port, timeout=600.0)
        try:
            results[i] = cli.generate(prompts[i], max_new_tokens=max_new)
        finally:
            cli.close()

    threads = [threading.Thread(target=one, args=(i,), name=f"smoke-req-{i}")
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900.0)
    _require(not any(t.is_alive() for t in threads),
             "serve: a request did not come back in 900 s")
    return results


def phase_serve(cfg: ServeCfg, device: str, lowerings: Lowerings) -> dict:
    """GPT through `LLMEngine` behind `PredictorServer`, the way the README
    starts it: a first wave of concurrent requests warms every program the
    traffic needs, the second wave must compile nothing and repeat it."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.server import STATUS_OK, PredictorServer
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.serving import LLMConfig, LLMEngine

    t0 = time.perf_counter()
    paddle.set_device(device)
    paddle.set_flags({"FLAGS_monitor": True})
    paddle.seed(cfg.seed)
    gpt = cfg.build()
    lm = GPTForCausalLM(gpt)
    eng = LLMEngine(lm, LLMConfig(num_slots=cfg.num_slots,
                                  max_len=cfg.max_len,
                                  prefill_buckets=cfg.prefill_buckets,
                                  max_new_tokens=cfg.max_new_tokens))
    vocab, hidden = gpt.embeddings.word_embeddings.weight.shape
    rng = np.random.default_rng(cfg.seed)
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32)
               for n in cfg.prompt_lens]
    srv = PredictorServer(lambda x: x, llm_engine=eng).start()
    try:
        first = _generate_concurrently(srv.host, srv.port, prompts,
                                       cfg.max_new_tokens)
        setup_s = time.perf_counter() - t0
        warm = (_trace_compiles(), lowerings.n)
        t1 = time.perf_counter()
        second = _generate_concurrently(srv.host, srv.port, prompts,
                                        cfg.max_new_tokens)
        steady_s = time.perf_counter() - t1
        after = (_trace_compiles(), lowerings.n)
        counters = eng.stats()["counters"]
    finally:
        srv.stop()

    for wave in (first, second):
        for n, (status, toks) in zip(cfg.prompt_lens, wave):
            _require(status == STATUS_OK and len(toks) == cfg.max_new_tokens,
                     f"serve: prompt of {n} tokens came back status={status} "
                     f"with {toks!r}, asked for {cfg.max_new_tokens} tokens")
    _require([t for _, t in first] == [t for _, t in second],
             "serve: greedy decoding did not repeat itself")
    _require(after == warm, "serve: steady state compiled "
             f"(trace_compile, lowerings) {warm} -> {after}")

    # top-1 agreement with the model's own full forward on each prompt
    margins = []
    with paddle.no_grad():
        for p, (_, toks) in zip(prompts, first):
            last = lm(paddle.to_tensor(p[None])).numpy()[0, -1]
            top2 = np.sort(last)[-2:]
            margins.append(float(top2[1] - top2[0]))
            _require(int(np.argmax(last)) == toks[0],
                     f"serve: first token {toks[0]} of the {p.size}-token "
                     f"prompt is not the full forward's arg-max "
                     f"{int(np.argmax(last))} (top-2 margin {margins[-1]})")
    return _report("serve", setup_s, steady_s, {
        "model": {"layers": len(gpt.layers), "hidden": int(hidden),
                  "heads": gpt.layers[0].attention.num_heads,
                  "vocab": int(vocab)},
        "prompt_lens": list(cfg.prompt_lens),
        "tokens_per_request": cfg.max_new_tokens, "waves": 2,
        "first_token_top1_agrees": len(prompts),
        "top2_margins": [round(m, 4) for m in margins],
        "steady_compiles": 0,
        "evictions_error": counters.get("evictions.error", 0)})


# ---- kernel -----------------------------------------------------------------

# (batch, seq, heads, head_dim, causal): b*h, s, d of the issue's geometries
KERNEL_FULL = ((8, 1024, 12, 64, False), (1, 8192, 12, 64, True),
               (1, 8192, 12, 128, True))
# bf16: the tolerance tests/test_flash_attention.py holds the kernel to
BF16_TOL = 3e-2
SCAN_FULL = dict(hidden=768, heads=12, ffn=3072, layers=2, batch=8, seq=1024)
# the decode step's read in `gpt2_large.serve_decode`: 12 slots of a
# [1026, 20 x 64] float32 page a side, a decode block of 2 query rows, fills
# from a free slot (0) over block edges to the page's last position
DECODE_FULL = dict(slots=12, page=1026, heads=20, head_dim=64, rows=2,
                   positions=(0, 1, 63, 127, 128, 200, 383, 511, 640, 1000,
                              1023, 0))

# the expert pass of a decode step in `ling3_flash_vl.serve_long_answer`:
# 48 rows x 8 routes over the 128 experts held of 512, hidden 2560, expert
# width 768, bfloat16; every other row carries no sequence (`live`)
ROUTED_FULL = dict(rows=48, top_k=8, held=128, total=512, hidden=2560,
                   width=768)

# the decode step's read in `dots_vlm1.serve_long_context`: 16 slots of a
# [13312, 640] bfloat16 latent page, 128 heads, lengths from a free slot
# over block edges to the page's last row; and its prompt form at the
# smallest bucket (the row-block form it is compared with holds 0.8 GB of
# scores a block there; at 12288 it does not compile in reasonable time)
LATENT_FULL = dict(slots=16, page=13312, heads=128, latent=512, rope=64,
                   nope=128, v_dim=128, prompt=3072,
                   positions=(0, 1, 511, 512, 513, 1535, 3071, 4607, 6143,
                              8191, 8192, 9215, 12287, 13311, 2000, 0))
# the Nemotron cell's widths: 256 slots' states, the 4096 bucket's prompt,
# its pool of grouped K/V pages; float32 forms agree to their summation order
SSD_FULL = dict(slots=256, heads=64, head_dim=64, groups=8, state=128,
                prompt=4096, length=3000, page=5120, q_heads=32, kv_heads=2,
                kv_dim=128)
SSD_TOL = 1e-4


def _fa():
    """The kernel MODULE (the package re-exports a function of its name)."""
    import importlib
    return importlib.import_module("paddle_tpu.kernels.flash_attention")


def _reference_attention_bshd(q, k, v, causal):
    """`_reference_bhsd` on [B,S,H,D], two heads at a time: the whole
    s8192 score matrix of twelve heads would not fit beside its grads."""
    import jax.numpy as jnp
    fa = _fa()
    b, s, h, d = q.shape
    flat = [jnp.swapaxes(x, 1, 2).reshape(b * h, s, d) for x in (q, k, v)]
    outs = [fa._reference_bhsd(*(x[i:i + 2] for x in flat), causal)
            for i in range(0, b * h, 2)]
    return jnp.swapaxes(jnp.concatenate(outs).reshape(b, h, s, d), 1, 2)


def _fwd_bwd(attn, w):
    """jit of (out, dq, dk, dv) for loss = sum(attn(q, k, v) * w)."""
    import jax
    import jax.numpy as jnp

    def run(q, k, v):
        def loss(q, k, v):
            out = attn(q, k, v)
            return (out.astype(jnp.float32) * w).sum(), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)
    return jax.jit(run)


def _run_twice(step, *args):
    """Compile the jitted `step` for `args` and call the executable twice:
    (compiled text, its tpu_custom_call count, result, seconds to the first
    result, seconds of the second call)."""
    import jax
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    got = jax.block_until_ready(compiled(*args))
    setup_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    steady_s = time.perf_counter() - t1
    text = compiled.as_text()
    return (text, text.count('custom_call_target="tpu_custom_call"'), got,
            setup_s, steady_s)


def _check_against(phase, got, want, names, tol):
    errs = {}
    for n, g, w in zip(names, got, want):
        _require(bool(np.all(np.isfinite(np.asarray(g, np.float32)))),
                 f"{phase}: {n} is not finite")
        errs[n] = round(_rel_err(g, w), 5)
        _require(errs[n] <= tol, f"{phase}: {n} is {errs[n]} from the "
                 f"reference, tolerance {tol}")
    return errs


def _sdpa(causal):
    """`nn.functional.scaled_dot_product_attention` on raw arrays: the
    framework's own dispatch decides what runs."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.tensor import Tensor

    def attn(q, k, v):
        return F.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(v), is_causal=causal)._value
    return attn


def _attention_geometry(b, s, h, d, causal, seed, min_kernels, tol):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    q, k, v, w = (jnp.asarray(rng.uniform(-0.5, 0.5, (b, s, h, d)),
                              jnp.bfloat16) for _ in range(4))
    w = w.astype(jnp.float32)
    _, n_kernels, got, setup_s, steady_s = _run_twice(
        _fwd_bwd(_sdpa(causal), w), q, k, v)

    plan = _fa().dispatch_plan(s, d, jnp.bfloat16)
    _require(plan[2] == "pallas", f"kernel: {(b * h, s, d)} is dispatched "
             f"to the reference, not the kernel: {plan}")
    _require(n_kernels >= min_kernels,
             f"kernel: {(b * h, s, d)} compiled with {n_kernels} "
             f"tpu_custom_call, forward and backward need {min_kernels}: the "
             "kernel was interpreted or gave way to the XLA path")
    want = _fwd_bwd(
        lambda q, k, v: _reference_attention_bshd(q, k, v, causal), w)(q, k, v)
    errs = _check_against(f"kernel {(b * h, s, d)}", got, want,
                          ("out", "dq", "dk", "dv"), tol)
    return {"bh_s_d": [b * h, s, d], "causal": causal,
            "block_q": plan[0], "block_k": plan[1], "forward": plan[2],
            "backward": plan[3], "tpu_custom_calls": n_kernels,
            "rel_err": errs}, setup_s, steady_s


@contextlib.contextmanager
def _reference_in_place_of_the_kernel():
    """Swap the scan layer's kernel call for the XLA reference, so the
    same layer code yields what the kernel is compared with."""
    fa = _fa()
    kernel = fa.flash_attention_arrays
    fa.flash_attention_arrays = \
        lambda q, k, v, causal=False: _reference_attention_bshd(q, k, v, causal)
    try:
        yield
    finally:
        fa.flash_attention_arrays = kernel


def _scan_stack(hidden, heads, ffn, layers, batch, seq, seed, min_kernels,
                tol):
    """`ErnieScanStack` (the scanned, rematerialized ErnieLayer), forward
    and backward in bf16, against itself with the reference attention."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import functional_call, split_state
    from paddle_tpu.models.ernie import ErnieScanStack

    paddle.seed(seed)
    net = ErnieScanStack(hidden, heads, ffn, layers)
    trainable, _ = split_state(net)
    names = list(trainable)
    params = [trainable[n]._value.astype(jnp.bfloat16) for n in names]
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(-1, 1, (batch, seq, hidden)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-1, 1, (batch, seq, hidden)), jnp.float32)

    def run(params, x):
        def loss(params, x):
            out = functional_call(net, names, params, [], [], x)
            return (out.astype(jnp.float32) * w).sum(), out
        (_, out), (_, dx) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        return out, dx

    _, n_kernels, got, setup_s, steady_s = _run_twice(jax.jit(run), params, x)
    _require(n_kernels >= min_kernels,
             f"kernel: the ErnieScanStack step compiled with {n_kernels} "
             f"tpu_custom_call, needs {min_kernels}")
    with _reference_in_place_of_the_kernel():
        # a fresh function object: jit would hand `run` its cached trace,
        # the one with the kernel in it
        want = jax.jit(lambda p, x: run(p, x))(params, x)
    errs = _check_against("kernel scan-stack", got, want, ("out", "dx"), tol)
    return {"scan_stack": {"hidden": hidden, "heads": heads, "layers": layers,
                           "batch": batch, "seq": seq},
            "tpu_custom_calls": n_kernels, "rel_err": errs}, setup_s, steady_s


def _decode_step(slots, page, heads, head_dim, rows, positions, seed,
                 min_kernels, tol):
    """`ErnieSelfAttention.forward_cached` over float32 pages at a decode
    block's width: as the framework routes it (on a TPU, through
    `kernels.decode_attention`) against itself held to the dense einsums
    over the whole page. A serve cell's `correct` never compares a decode
    step's logits, so this is where the chip checks the ragged read."""
    import importlib

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.ernie import ErnieSelfAttention

    da = importlib.import_module("paddle_tpu.kernels.decode_attention")
    hidden = heads * head_dim
    paddle.seed(seed)
    attn = ErnieSelfAttention(hidden, heads, dropout=0.0, causal=True)
    attn.eval()
    rng = np.random.default_rng(seed)
    x, kc, vc = (jnp.asarray(rng.uniform(-1, 1, shape), jnp.float32)
                 for shape in ((slots, rows, hidden),
                               (slots, page, hidden), (slots, page, hidden)))
    pos = jnp.asarray(positions, jnp.int32)

    def step(x, kc, vc, pos):
        with paddle.no_grad():
            out, kc, vc, _, _ = attn.forward_cached(
                Tensor(x), Tensor(kc), Tensor(vc), Tensor(pos))
        return out._value, kc._value, vc._value

    _, n_kernels, got, setup_s, steady_s = _run_twice(
        jax.jit(step), x, kc, vc, pos)
    _require(n_kernels >= min_kernels,
             f"kernel: the decode step compiled with {n_kernels} "
             f"tpu_custom_call, needs {min_kernels}: its read went to the "
             "dense einsums")
    engages = da.engages
    da.engages = lambda *_: False
    try:
        want = jax.jit(lambda *a: step(*a))(x, kc, vc, pos)
    finally:
        da.engages = engages
    errs = _check_against("kernel decode-step", got, want,
                          ("out", "k_page", "v_page"), tol)
    return {"decode_step": {"slots": slots, "page": page, "heads": heads,
                            "head_dim": head_dim, "rows": rows,
                            "positions": list(positions)},
            "tpu_custom_calls": n_kernels, "rel_err": errs}, setup_s, steady_s


def _routed_step(rows, top_k, held, total, hidden, width, seed, min_kernels,
                 tol):
    """`nn.RoutedExperts`' expert pass (`experts_pass`: the grouped layout
    and two calls of the grouped matmul, on a TPU the Pallas kernel) with
    every other row routed nowhere, as the layer routes the rows a caller
    says carry no sequence: against `lax.ragged_dot` over the same layout;
    the live rows against the pass with every row live, to the bit (a
    tile's rows do not mix); and the tiles that hold rows, which are the
    experts read. Both serve cells' `correct` drives every slot live, so
    this is where the chip checks the masked pass."""
    import importlib

    import jax
    import jax.numpy as jnp

    gm = importlib.import_module("paddle_tpu.kernels.grouped_matmul")
    routed = importlib.import_module("paddle_tpu.nn.layer.routed_experts")
    rng = np.random.default_rng(seed)
    bf = lambda *shape: jnp.asarray(rng.uniform(-1, 1, shape), jnp.bfloat16)
    m = bf(rows, hidden)
    gate, up = (bf(held, hidden, width) * hidden ** -0.5 for _ in range(2))
    down = bf(held, width, hidden) * width ** -0.5
    experts = np.stack([rng.permutation(total)[:top_k] for _ in range(rows)])
    w = jnp.asarray(rng.uniform(0.1, 1, (rows, top_k)), jnp.float32)
    live = np.arange(rows) % 2 == 0
    every = np.where(experts < held, experts, held).astype(np.int32)
    local = {"all_live": jnp.asarray(every),
             "half_masked": jnp.asarray(np.where(live[:, None], every, held))}
    tile = gm.tile_rows_for(rows * top_k)
    active = {k: int(gm.layout(v.reshape(-1), held, tile)[2][0])
              for k, v in local.items()}
    _require(0 < active["half_masked"] * 10 <= active["all_live"] * 7,
             f"routed: masking half the rows left {active['half_masked']} "
             f"of {active['all_live']} tiles: dead rows still reach experts")

    # a fresh function object, here and below: jit hands a function it has
    # seen its cached trace, whatever `gm.grouped_matmul` is by then
    step = jax.jit(lambda *a: routed.experts_pass(*a))
    _, n_kernels, got, setup_s, steady_s = _run_twice(
        step, m, local["half_masked"], w, gate, up, down)
    _require(n_kernels >= 2 * min(min_kernels, 1),
             f"routed: the expert pass compiled with {n_kernels} "
             "tpu_custom_call, needs 2: it went to lax.ragged_dot")
    full = np.asarray(step(m, local["all_live"], w, gate, up, down),
                      np.float32)
    masked = np.asarray(got, np.float32)
    _require(bool(np.array_equal(masked[live], full[live])),
             "routed: a live row's numbers moved when other rows were masked")
    _require(not masked[~live].any(),
             "routed: a row that was routed nowhere has a sum")
    kernel = gm.grouped_matmul
    gm.grouped_matmul = lambda x, tile_group, active, tiles_of, ws, tile: \
        gm._ragged(x, tiles_of, ws, tile)
    try:
        want = jax.block_until_ready(jax.jit(
            lambda *a: routed.experts_pass(*a))(
                m, local["half_masked"], w, gate, up, down))
    finally:
        gm.grouped_matmul = kernel
    errs = _check_against("routed", (got,), (want,), ("out",), tol)
    timed = {}
    for name, routes in local.items():
        t0 = time.perf_counter()
        for _ in range(20):
            out = step(m, routes, w, gate, up, down)
        jax.block_until_ready(out)
        timed[name] = round((time.perf_counter() - t0) / 20 * 1e3, 4)
    return {"routed_step": {"rows": rows, "top_k": top_k, "held": held,
                            "total": total, "hidden": hidden, "width": width,
                            "tile_rows": tile, "live_rows": int(live.sum())},
            "tpu_custom_calls": n_kernels, "rel_err": errs,
            "active_tiles": active, "pass_ms_smoke": timed}, setup_s, steady_s


def phase_kernel(geometries=KERNEL_FULL, scan=SCAN_FULL, decode=DECODE_FULL,
                 routed=ROUTED_FULL, seed=0, min_kernels=2,
                 tol=BF16_TOL) -> dict:
    """The long-sequence attention the framework selects by itself: each
    geometry through `nn.functional` attention in bf16, and the scanned
    ErnieLayer, forward and backward against `_reference_bhsd`; then the
    serve path's decode step (float32 pages, forward only) against its
    dense read, and the routed expert pass with half its rows routed
    nowhere against `lax.ragged_dot`. `min_kernels` is 2 on the chip (a
    forward and a backward kernel in the compiled text; the decode step
    needs one, the expert pass its two); only the CPU rehearsal, which
    interprets, passes 0."""
    rows, setup_s, steady_s = [], 0.0, 0.0
    for i, (b, s, h, d, causal) in enumerate(geometries):
        row, su, st = _attention_geometry(b, s, h, d, causal, seed + i,
                                          min_kernels, tol)
        rows.append(row)
        setup_s, steady_s = setup_s + su, steady_s + st
    for row, su, st in (
            _scan_stack(**scan, seed=seed, min_kernels=min_kernels, tol=tol),
            _decode_step(**decode, seed=seed,
                         min_kernels=min(min_kernels, 1), tol=tol),
            _routed_step(**routed, seed=seed, min_kernels=min_kernels,
                         tol=tol)):
        rows.append(row)
        setup_s, steady_s = setup_s + su, steady_s + st
    return _report("kernel", setup_s, steady_s,
                   {"tolerance": tol, "dtype": "bfloat16", "paths": rows})


def _without_the_latent_kernels(fn, *args):
    """`fn` compiled with latent attention held to its `jax.numpy` forms
    (the dense read, the row-block prompt): what the kernels are compared
    with."""
    import importlib

    import jax
    md = importlib.import_module("paddle_tpu.kernels.mla_decode")
    engages = md.engages
    md.engages = lambda *_: False
    try:
        return jax.block_until_ready(jax.jit(lambda *a: fn(*a))(*args))
    finally:
        md.engages = engages


def phase_latent(slots, page, heads, latent, rope, nope, v_dim, prompt,
                 positions, seed=0, min_kernels=1, tol=BF16_TOL,
                 blocks=(128, 256, 512, 1024)) -> dict:
    """Latent attention as the framework routes it on a TPU, forward only,
    bfloat16: a decode step's read of the pool (`mla_decode`) against the
    dense read of the whole page, and a prompt (`mla_prefill`, the flash
    kernel at score width nope + rope and value width v) against the form
    that holds a block of scores. Also times the read at several block
    sizes (smoke timings: what `BLOCK_ROWS` was chosen from)."""
    import importlib

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.nn import functional as F

    md = importlib.import_module("paddle_tpu.kernels.mla_decode")
    rng = np.random.default_rng(seed)
    width = -(-(latent + rope) // 128) * 128
    bf = lambda *shape: jnp.asarray(rng.uniform(-1, 1, shape), jnp.bfloat16)
    pool = bf(slots, page, width).at[..., latent + rope:].set(0)
    w = bf(latent, heads * (nope + v_dim)) * (latent ** -0.5)
    scale = (nope + rope) ** -0.5
    pos = jnp.asarray(positions, jnp.int32)

    def step(qn, qr, pool, pos, w):
        with paddle.no_grad():
            return F.latent_attention_decode(qn, qr, pool, pos, w,
                                             scale=scale)._value

    def prefill(qn, qr, c, kr, w):
        with paddle.no_grad():
            return F.latent_attention_prompt(qn, qr, c, kr, w,
                                             scale=scale)._value

    rows, setup_s, steady_s = [], 0.0, 0.0
    for name, fn, args in (
            ("decode", step, (bf(slots, heads, nope), bf(slots, heads, rope),
                              pool, pos, w)),
            ("prompt", prefill, (bf(1, prompt, heads, nope),
                                 bf(1, prompt, heads, rope),
                                 bf(1, prompt, latent), bf(1, prompt, rope),
                                 w))):
        text, n_kernels, got, su, st = _run_twice(jax.jit(fn), *args)
        _require(n_kernels >= min_kernels,
                 f"latent {name}: compiled with {n_kernels} tpu_custom_call, "
                 f"needs {min_kernels}: it went to the jax.numpy form")
        t0 = time.perf_counter()
        want = _without_the_latent_kernels(fn, *args)
        dense_s = time.perf_counter() - t0
        errs = _check_against(f"latent {name}", (got,), (want,), ("out",),
                              tol)
        rows.append({name: [int(d) for d in args[2].shape], "heads": heads,
                     "tpu_custom_calls": n_kernels, "rel_err": errs,
                     "smoke_seconds": {"kernel": round(st, 5),
                                       "jnp_form_with_compile":
                                           round(dense_s, 3)}})
        setup_s, steady_s = setup_s + su, steady_s + st
    # the read alone at several block sizes, and the dense read, 20 calls
    q = bf(slots, heads, width)
    timed = {}
    from paddle_tpu.nn.functional.attention import _latent_read_dense
    forms = {f"block_{b}": jax.jit(functools.partial(
        md.mla_decode, scale=scale, block_rows=b)) for b in blocks}
    forms["dense"] = jax.jit(functools.partial(_latent_read_dense,
                                               scale=scale))
    for name, fn in forms.items():
        jax.block_until_ready(fn(q, pool, pos))
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(q, pool, pos)
        jax.block_until_ready(out)
        timed[name] = round((time.perf_counter() - t0) / 20 * 1e3, 4)
    live = int(np.sum(np.asarray(positions) + 1))
    return _report("latent", setup_s, steady_s,
                   {"tolerance": tol, "dtype": "bfloat16", "paths": rows,
                    "read_ms_smoke": timed, "live_rows": live,
                    "pool_rows": slots * page})


def _ssd_step_rate(ssd, args, interpret, groups, reps=20) -> dict:
    """The step kernel over donated states, called back to back as the
    decode program calls it: its bytes (one Mamba layer's,
    `benchmarks/nemotron_cost.ssd_step_bytes`), and on a TPU the smoke
    time of a call (the inputs' small preparation included), GB/s and the
    share of the device's HBM peak (`benchmarks/peaks.json`)."""
    import jax
    import jax.numpy as jnp
    from benchmarks import flops, nemotron_cost

    x, dt, a, b, c, s = args
    slots, heads, head_dim, state = s.shape
    cfg = {"mamba_num_heads": heads, "mamba_head_dim": head_dim,
           "n_groups": groups, "ssm_state_size": state,
           "hybrid_override_pattern": "M", "num_hidden_layers": 1}
    line = {"step_bytes": nemotron_cost.ssd_step_bytes(cfg, slots),
            "step_ms_smoke": None, "step_gb_per_s": None,
            "step_hbm_share": None}
    if interpret:                       # an interpreter's time is no rate
        return line
    step = jax.jit(functools.partial(ssd._step_pallas, interpret=False),
                   donate_argnums=5)
    s = jax.block_until_ready(step(x, dt, a, b, c, jnp.array(s))[1])
    t0 = time.perf_counter()
    for _ in range(reps):
        s = step(x, dt, a, b, c, s)[1]
    jax.block_until_ready(s)
    seconds = (time.perf_counter() - t0) / reps
    rate = line["step_bytes"] / seconds
    peak = flops.peak(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    line.update(step_ms_smoke=round(seconds * 1e3, 4),
                step_gb_per_s=round(rate / 1e9, 1),
                step_hbm_share=round(rate / peak, 4))
    return line


def phase_ssd(slots, heads, head_dim, groups, state, prompt, length, page,
              q_heads, kv_heads, kv_dim, seed=0, min_kernels=1,
              tol=SSD_TOL, read_tol=BF16_TOL) -> dict:
    """The state-space scan's two Pallas forms at the widths of the
    Nemotron cell, float32: the one-token step over every slot's state
    (`ssd_step`) against its `jax.numpy` form, and a prompt of `prompt`
    positions padded past `length` (`ssd_chunked`) against the chunked
    `jax.numpy` form; then a decode step's read of pages of grouped K/V
    heads (`decode_attention_gqa`, bfloat16) against the dense read. Smoke
    timings of the step and the read; the step's GB/s over donated states
    and its share of the HBM peak (`_ssd_step_rate`)."""
    import importlib

    import jax
    import jax.numpy as jnp

    ssd = importlib.import_module("paddle_tpu.kernels.ssd")
    da = importlib.import_module("paddle_tpu.kernels.decode_attention")
    from paddle_tpu.models import nemotron
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, heads), jnp.float32)

    def inputs(*lead):
        return (f(*lead, heads, head_dim),
                jax.nn.softplus(f(*lead, heads) - 4.6), a,
                f(*lead, groups, state), f(*lead, groups, state))

    step_in = inputs(slots) + (f(slots, heads, head_dim, state),)
    prompt_in = inputs(1, prompt) + (jnp.asarray([length], jnp.int32),)
    rows, setup_s, steady_s = [], 0.0, 0.0
    for name, kernel, form, args, names in (
            ("step", jax.jit(functools.partial(ssd._step_pallas,
                                               interpret=interpret)),
             jax.jit(ssd._step_jnp), step_in, ("y", "state")),
            ("chunked", jax.jit(functools.partial(ssd.ssd_chunked,
                                                  form="pallas")),
             jax.jit(functools.partial(ssd.ssd_chunked, form="jnp")),
             prompt_in, ("y", "state"))):
        text, n_kernels, got, su, st = _run_twice(kernel, *args)
        _require(n_kernels >= min_kernels,
                 f"ssd {name}: compiled with {n_kernels} tpu_custom_call, "
                 f"needs {min_kernels}")
        want = jax.block_until_ready(form(*args))
        if name == "chunked":       # rows past the length mean nothing
            got = (got[0][:, :length], got[1])
            want = (want[0][:, :length], want[1])
        rows.append({name: [int(d) for d in args[0].shape],
                     "tpu_custom_calls": n_kernels,
                     "rel_err": _check_against(f"ssd {name}", got, want,
                                               names, tol),
                     "smoke_seconds": round(st, 5)})
        setup_s, steady_s = setup_s + su, steady_s + st
    rows[0].update(_ssd_step_rate(ssd, step_in, interpret, groups))
    bf = lambda *shape: jnp.asarray(rng.uniform(-1, 1, shape), jnp.bfloat16)
    q = bf(slots, q_heads, kv_dim)
    k_page, v_page = (bf(slots, page, kv_heads * kv_dim) for _ in range(2))
    pos = jnp.asarray(rng.integers(0, page, slots), jnp.int32)
    scale = kv_dim ** -0.5
    read = jax.jit(lambda *a: nemotron._attend_step(*a, scale))
    text, n_kernels, got, su, st = _run_twice(read, q, k_page, v_page, pos)
    _require(n_kernels >= min_kernels,
             f"grouped read: compiled with {n_kernels} tpu_custom_call")
    # the dense read repeats every K/V head in float32: 16 slots a call
    engages = da.engages
    da.engages = lambda *_: False
    try:
        dense = jax.jit(lambda *a: nemotron._attend_step(*a, scale))
        want = jnp.concatenate([jax.block_until_ready(dense(
            q[i:i + 16], k_page[i:i + 16], v_page[i:i + 16], pos[i:i + 16]))
            for i in range(0, slots, 16)])
    finally:
        da.engages = engages
    rows.append({"read": [int(d) for d in k_page.shape], "heads": q_heads,
                 "tpu_custom_calls": n_kernels,
                 "rel_err": _check_against("grouped read", (got,), (want,),
                                           ("out",), read_tol),
                 "smoke_seconds": round(st, 5)})
    setup_s, steady_s = setup_s + su, steady_s + st
    return _report("ssd", setup_s, steady_s,
                   {"tolerance": tol, "read_tolerance": read_tol,
                    "paths": rows})


# ---- four chips: the sharded paths, and what they are compared with ---------

def _count_op(hlo_text: str, op: str) -> int:
    """Instructions of `op` in optimized HLO, sync or async (`-start`)."""
    return hlo_text.count(f" {op}(") + hlo_text.count(f" {op}-start(")


@dataclasses.dataclass(frozen=True)
class SpmdCfg:
    # ERNIE-base widths with Column/RowParallelLinear and a vocab-parallel
    # embedding; depth cut to 4 layers (the step's sharding does not
    # depend on depth), dropout off so both trajectories see one model
    model: dict = dataclasses.field(default_factory=lambda: dict(
        vocab_size=30522, hidden_size=768, num_hidden_layers=4,
        num_attention_heads=12, intermediate_size=3072,
        hidden_dropout_prob=0.0, use_mp=True))
    batch: int = 32
    seq: int = 128
    steps: int = 4
    seed: int = 0
    # bf16 autocast: the two programs order their reductions differently
    loss_rtol: float = 2e-2


def _mp_pretrainer(cfg: SpmdCfg):
    import paddle_tpu as paddle
    from paddle_tpu import models
    paddle.seed(cfg.seed)
    net = models.ErnieForPretraining(models.ErnieModel(**cfg.model),
                                     use_mp=True)
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=1e-4)
    return net, opt


def phase_spmd(cfg: SpmdCfg, devices: Sequence, platform: str) -> dict:
    """`fleet.init` dp2 x mp2 -> `SPMDTrainStep`, against the one-device
    `TrainStep` of the same model from the same seed on the same batches."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.parallel import SPMDTrainStep
    from paddle_tpu.parallel.topology import set_mesh

    _require(len(devices) >= 4, f"spmd: needs 4 devices, has {len(devices)}")
    rng = np.random.default_rng(cfg.seed)
    vocab = cfg.model["vocab_size"]
    batches = [(rng.integers(0, vocab, (cfg.batch, cfg.seq)).astype(np.int32),
                rng.integers(0, 2, (cfg.batch,)).astype(np.int32))
               for _ in range(cfg.steps)]

    def feed(ids, nsp):
        return [paddle.to_tensor(a) for a in (ids, ids, nsp)]

    # the comparison first, while no mesh is set: one device, plain TrainStep
    t0 = time.perf_counter()
    set_mesh(None)
    net1, opt1 = _mp_pretrainer(cfg)
    step1 = paddle.jit.TrainStep(net1, _pretrain_loss(), opt1,
                                 amp_dtype="bfloat16", n_model_inputs=1)
    one = [float(step1(*feed(*b))) for b in batches]
    del net1, opt1, step1
    compare_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    net4, opt4 = _mp_pretrainer(cfg)
    step4 = SPMDTrainStep(net4, _pretrain_loss(), opt4, mesh=hcg.get_mesh(),
                          amp_dtype="bfloat16", n_model_inputs=1)
    four = [float(step4(*feed(*batches[0])))]
    setup_s = time.perf_counter() - t1
    t2 = time.perf_counter()
    four += [float(step4(*feed(*b))) for b in batches[1:]]
    steady_s = time.perf_counter() - t2

    _require(all(np.isfinite(one + four)), f"spmd: non-finite {one} {four}")
    worst = max(abs(a - b) / abs(b) for a, b in zip(four, one))
    _require(worst <= cfg.loss_rtol,
             f"spmd: dp2 x mp2 losses {four} leave the one-device "
             f"trajectory {one} by {worst:.4f} > {cfg.loss_rtol}")

    # the parameters really live across four devices, in the expected pieces
    used, shards = set(), {}
    for name, p in net4.named_parameters():
        sh = p._value.sharding
        used |= set(sh.device_set)
        if p.dist_attr is not None and "mp" in p.dist_attr:
            want = tuple(n // 2 if a == "mp" else n
                         for n, a in zip(p.shape, p.dist_attr))
            got = tuple(sh.shard_shape(tuple(p.shape)))
            _require(got == want, f"spmd: {name} shard {got}, wanted {want}")
            shards[name] = list(got)
    _require(len(used) == 4 and {d.platform for d in used} == {platform},
             f"spmd: parameters cover {sorted(map(str, used))}, wanted four "
             f"distinct {platform} devices")
    _require(shards, "spmd: no parameter was sharded over mp")
    text = step4.compiled(*feed(*batches[0])).as_text()
    collectives = {op: _count_op(text, op)
                   for op in ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")}
    _require(collectives["all-reduce"] > 0,
             f"spmd: no all-reduce in the compiled step: {collectives}")
    some = sorted(shards)[:3]
    return _report("spmd_dp2_mp2", setup_s, steady_s, {
        "model": {k: cfg.model[k] for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "intermediate_size")},
        "batch": cfg.batch, "seq": cfg.seq,
        "mesh": {k: int(v) for k, v in hcg.get_mesh().shape.items()},
        "loss_one_device": one, "loss_dp2_mp2": four,
        "worst_rel_diff": round(worst, 5), "loss_rtol": cfg.loss_rtol,
        "devices_covered": sorted(str(d) for d in used),
        "mp_sharded_params": len(shards),
        "shard_shapes": {n: shards[n] for n in some},
        "collectives": collectives,
        "one_device_comparison_s": round(compare_s, 3)})


def phase_ring(devices: Sequence, geometry=(1, 8192, 12, 64), seed=0,
               min_kernels=2, tol=BF16_TOL) -> dict:
    """`create_mesh({"sp": 4})` -> `sequence_parallel_attention` (ring of
    Pallas flash blocks inside shard_map), forward and backward, against
    attention on one device from the same inputs."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.parallel.sp import sequence_parallel_attention
    from paddle_tpu.parallel.topology import set_mesh

    _require(len(devices) >= 4, f"ring: needs 4 devices, has {len(devices)}")
    rng = np.random.default_rng(seed)
    q, k, v, w = (jnp.asarray(rng.uniform(-0.5, 0.5, geometry), jnp.bfloat16)
                  for _ in range(4))
    w = w.astype(jnp.float32)

    # what it is compared with: the one-chip path, before any mesh exists
    set_mesh(None)
    t0 = time.perf_counter()
    want = jax.block_until_ready(_fwd_bwd(_sdpa(True), w)(q, k, v))
    compare_s = time.perf_counter() - t0

    mesh = create_mesh({"sp": 4}, devices=list(devices)[:4])

    def ring(q, k, v):
        return sequence_parallel_attention(
            Tensor(q), Tensor(k), Tensor(v), impl="ring", causal=True,
            mesh=mesh)._value

    text, n_kernels, got, setup_s, steady_s = _run_twice(
        _fwd_bwd(ring, w), q, k, v)
    _require(n_kernels >= min_kernels,
             f"ring: {n_kernels} tpu_custom_call in the compiled ring, needs "
             f"{min_kernels}: it fell to the dense ring or to one device")
    n_permute = _count_op(text, "collective-permute")
    _require(n_permute > 0, "ring: no collective-permute in the compiled "
             "text: the sequence was not split over the sp axis")
    _require(len(got[0].sharding.device_set) == 4,
             f"ring: output lives on {got[0].sharding.device_set}")
    errs = _check_against("ring", got, want, ("out", "dq", "dk", "dv"), tol)
    return _report("ring_sp4", setup_s, steady_s, {
        "b_s_h_d": list(geometry), "causal": True, "mesh": {"sp": 4},
        "tpu_custom_calls": n_kernels, "collective_permutes": n_permute,
        "tolerance": tol, "rel_err": errs,
        "one_device_comparison_s": round(compare_s, 3)})


# ---- entry ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, serve, kernel, latent, ssd on one chip "
                         "(default); 4: only the sharded paths and their "
                         "comparison")
    ap.add_argument("--phases", default="train,serve,kernel,latent,ssd",
                    help="the one-chip phases to run, comma-separated")
    args = ap.parse_args(argv)
    phases = args.phases.split(",")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform} "
              f"({devices[0].device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s); nothing was run", file=sys.stderr)
        return 2

    from paddle_tpu.core.compile_cache import place_jax_cache
    place_jax_cache()
    if args.chips == 4:
        phase_spmd(SpmdCfg(), devices, "tpu")
        gc.collect()
        phase_ring(devices)
    else:
        lowerings = Lowerings()
        if "train" in phases:
            phase_train(TRAIN_FULL, "tpu", lowerings)
            gc.collect()
        if "serve" in phases:
            phase_serve(SERVE_FULL, "tpu", lowerings)
            gc.collect()
        if "kernel" in phases:
            phase_kernel()
            gc.collect()
        if "latent" in phases:
            phase_latent(**LATENT_FULL)
            gc.collect()
        if "ssd" in phases:
            phase_ssd(**SSD_FULL)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
