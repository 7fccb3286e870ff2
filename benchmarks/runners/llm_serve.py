"""Runner `llm_serve`: `GPTForCausalLM` in `LLMEngine` behind
`PredictorServer`, clients over the wire with `PredictorClient.generate(
on_token=...)`, one connection per request, as `chip_smoke.phase_serve`
starts it. Load comes from this process: one dispatcher (the main thread)
and one short-lived thread per request in flight.

Cell file keys read here: `generator` and `mix` (see
`traffic/open_loop.py`), `engine` (`num_slots`, `max_len`,
`prefill_buckets`, `queue_depth`), `check` (`prompts`, `pad_to`). Engine
settings not named are the engine's defaults (fp32 weights, fp32 pool,
`decode_block=2`, `quant="off"`); `warmup_on_start` is False and a warm wave
over the wire compiles exactly what the cell's lengths reach: one prefill
per bucket a prompt length maps to, the decode step and the slot write.

End-to-end numbers are taken at the client: the median time to the first
streamed token from when the request was DUE (so a stalled generator or
server delays nothing out of sight; a request that never got a token enters
at the cap), and the median and 99th percentile over all gaps between
consecutive streamed tokens. The 95th percentiles and the tokens per second
received in the window go to the progress line only: with some tens of
requests in a window they move by more than any bound (PERF.md).

`correct`:
- every stream that finished has exactly the tokens asked (no EOS is set,
  so `max_new_tokens` is exact);
- on `check.prompts` seeded prompts, outside the window, the model's full
  forward agrees with the plain reference's last-position logits within
  `LOGIT_TOL`, and the engine's first token over the wire is the
  reference's arg-max wherever the reference's top-2 margin exceeds twice
  that tolerance (random weights leave margins that rounding can flip).
"""
from __future__ import annotations

import functools
import threading
import time

import numpy as np

from .. import harness
from ..stats import pct
from ..reference import blocks
from .common import named_arrays, redraw_embeddings

# max|model - reference| over max|reference|, last-position logits. The
# engine serves fp32 weights at the TPU's default matmul precision, which
# multiplies in bf16 (2^-9 relative per product) and accumulates in fp32;
# through 36 post-LN blocks (each LayerNorm re-scales, so errors add rather
# than compound) that comes to some 1e-3..1e-2 of the logits' range — the
# kernels of PR 22 sat at 4e-3..1e-2 against an fp32 reference (PERF.md).
# 3e-2 is the repo's bf16 tolerance (tests/test_flash_attention.py); an int8
# weight path lands above it.
LOGIT_TOL = 3e-2


def build(cell: dict, ctx) -> dict:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.inference.server import PredictorServer
    from paddle_tpu.models.gpt import GPTForCausalLM, GPTModel
    from paddle_tpu.serving import LLMConfig, LLMEngine

    sizes, eng_cfg, chk = cell["config_sizes"], cell["engine"], cell["check"]
    paddle.set_device(ctx.device)
    if ctx.trace:
        paddle.set_flags({"FLAGS_monitor": True})
    paddle.seed(ctx.seed)
    gpt = GPTModel(vocab_size=sizes["vocab_size"],
                   hidden_size=sizes["hidden_size"],
                   num_layers=sizes["num_hidden_layers"],
                   num_heads=sizes["num_attention_heads"],
                   intermediate_size=sizes["intermediate_size"],
                   max_seq_len=sizes["max_position_embeddings"], dropout=0.0)
    lm = GPTForCausalLM(gpt)
    lm.eval()
    redraw_embeddings(lm, ctx.seed, sizes["initializer_range"])
    ctx.say(f"built {cell['config']}: "
            f"{sum(int(np.prod(p.shape)) for p in lm.parameters())} "
            "parameters")

    # reference check, part 1 (before the pool takes its memory): the
    # model's full forward against the plain reference on seeded prompts,
    # right-padded to one length (causal: padding changes nothing to its
    # left), one program each
    mix = cell["mix"]
    rng = np.random.default_rng(ctx.seed + 1)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    lens = rng.integers(lo, min(hi, chk["pad_to"]) + 1, chk["prompts"])
    prompts = [rng.integers(0, sizes["vocab_size"], int(n)).astype(np.int32)
               for n in lens]
    padded = np.zeros((len(prompts), chk["pad_to"]), np.int32)
    for r, p in enumerate(prompts):
        padded[r, :p.size] = p
    rows, last = np.arange(len(prompts)), lens - 1
    ref = jax.jit(functools.partial(
        blocks.gpt_logits, n_layers=sizes["num_hidden_layers"],
        heads=sizes["num_attention_heads"]))
    ref_last = np.asarray(ref(named_arrays(lm), padded))[rows, last]
    full = paddle.jit.to_static(lm)
    with paddle.no_grad():
        got_last = np.asarray(full(paddle.to_tensor(padded)).numpy()
                              )[rows, last]
    scale = float(np.max(np.abs(ref_last)))
    err = float(np.max(np.abs(got_last - ref_last))) / scale
    top2 = np.sort(ref_last, axis=-1)[:, -2:]
    margins = (top2[:, 1] - top2[:, 0]) / scale
    ctx.say(f"full forward vs reference, last-position logits: rel err "
            f"{err:.3e} (tolerance {LOGIT_TOL}); top-2 margins / range "
            f"{[round(float(m), 4) for m in margins]}")
    checks = {"logits_match_reference": bool(err <= LOGIT_TOL)}
    del full, ref

    engine = LLMEngine(lm, LLMConfig(
        num_slots=eng_cfg["num_slots"], max_len=eng_cfg["max_len"],
        prefill_buckets=tuple(eng_cfg["prefill_buckets"]),
        queue_depth=eng_cfg["queue_depth"], warmup_on_start=False))
    server = PredictorServer(lambda x: x, llm_engine=engine).start()
    ctx.say(f"engine up: {engine.stats()['slots']} slots, pool "
            f"{engine.kv_pool_bytes() / 1e9:.3f} GB, buckets "
            f"{engine.buckets}, serving on {server.host}:{server.port}")
    return {"cell": cell, "ctx": ctx, "lm": lm, "engine": engine,
            "server": server, "checks": checks, "prompts": prompts,
            "ref_argmax": np.argmax(ref_last, axis=-1),
            "margins": margins}


def _generate(state, prompt, max_new, on_token=None, timeout=600.0):
    from paddle_tpu.inference.server import PredictorClient
    srv = state["server"]
    cli = PredictorClient(srv.host, srv.port, timeout=timeout)
    try:
        return cli.generate(prompt, max_new_tokens=max_new, on_token=on_token)
    finally:
        cli.close()


def warm(state) -> None:
    """The warm wave, then part 2 of the reference check over the wire."""
    from paddle_tpu.inference.server import STATUS_OK
    ctx, cell, engine = state["ctx"], state["cell"], state["engine"]
    spec = cell["mix"]["prompt_tokens"]
    reach = sorted({next(b for b in engine.buckets if b >= n)
                    for n in (spec["min"], spec["max"])}
                   | {b for b in engine.buckets
                      if spec["min"] <= b <= spec["max"]})
    rng = np.random.default_rng(state["ctx"].seed + 2)
    vocab = cell["config_sizes"]["vocab_size"]
    for b in reach:
        t0 = time.perf_counter()
        n = min(b, spec["max"])
        status, toks = _generate(state, rng.integers(0, vocab, n
                                                     ).astype(np.int32), 3)
        if status != STATUS_OK or len(toks) != 3:
            raise RuntimeError(f"warm wave: bucket {b} came back "
                               f"status={status} with {toks!r}")
        ctx.say(f"warm: bucket {b} ({n} tokens) in "
                f"{time.perf_counter() - t0:.2f}s")
    agree = []
    for p, want, margin in zip(state["prompts"], state["ref_argmax"],
                               state["margins"]):
        status, toks = _generate(state, p, 2)
        ok = status == STATUS_OK and len(toks) == 2
        agree.append(bool(ok and (toks[0] == want
                                  or margin <= 2 * LOGIT_TOL)))
    ctx.say(f"engine first token vs reference arg-max: {agree}")
    state["checks"]["first_token_is_reference_argmax"] = all(agree)


class _Flight:
    """One request in flight: sent at its due time from its own thread."""

    def __init__(self, state, req, t_zero: float):
        self.req = req
        self.due = t_zero + req.due_s
        self.sent = 0.0
        self.token_times = []
        self.status = None
        self.tokens = None
        self.error = None
        self.thread = threading.Thread(target=self._run, args=(state,),
                                       daemon=True, name="bench-client")

    def _on_token(self, _idx, _tok):
        self.token_times.append(time.perf_counter())

    def _run(self, state):
        self.sent = time.perf_counter()
        try:
            self.status, self.tokens = _generate(
                state, self.req.prompt, self.req.max_new, self._on_token,
                timeout=120.0)
        except Exception as e:  # a failed request is counted, not raised
            self.error = f"{type(e).__name__}: {e}"


def offer(state, seconds: float, rate_rps=None, tracer=None) -> dict:
    """Send one run's requests on their schedule and collect what came
    back. Returns the flights, the window's edges on `perf_counter`, and
    the monitor's snapshots at both edges (None with the monitor off)."""
    from paddle_tpu import monitor
    ctx, cell = state["ctx"], state["cell"]
    mix = dict(cell["mix"])
    if rate_rps is not None:
        mix["rate_rps"] = rate_rps
    reqs = harness.module("traffic", cell["generator"]).generate(
        mix, seconds, ctx.seed, cell["config_sizes"]["vocab_size"])
    t_zero = time.perf_counter() + float(mix["ramp_s"]) + 0.05
    t_end = t_zero + seconds
    flights = [_Flight(state, r, t_zero) for r in reqs]
    snaps = {}
    if tracer is not None:
        tracer.arm(t_end)

    def snap(name):
        if monitor.enabled():
            snaps[name] = monitor.snapshot()

    opened = False
    ctx.window_opens()
    for f in flights:
        if not opened and f.req.due_s >= 0:
            time.sleep(max(0.0, t_zero - time.perf_counter()))
            snap("before")
            opened = True
        time.sleep(max(0.0, f.due - time.perf_counter()))
        f.thread.start()
    time.sleep(max(0.0, t_end - time.perf_counter()))
    snap("after")
    if tracer is not None:
        tracer.stop()
    give_up = t_end + float(mix["drain_s"])
    for f in flights:
        f.thread.join(timeout=max(0.0, give_up - time.perf_counter()))
    ctx.window_closes()
    return {"flights": flights, "t_zero": t_zero, "t_end": t_end,
            "give_up": give_up, "monitor": (snaps.get("before"),
                                            snaps.get("after"))}


def client_numbers(run: dict, seconds: float) -> dict:
    """Client-side numbers of one `offer`."""
    from paddle_tpu.inference.server import STATUS_OK
    t_zero, t_end = run["t_zero"], run["t_end"]
    cap_ms = (run["give_up"] - t_zero) * 1000.0
    ttft, gaps, late, failed, wrong_len = [], [], [], 0, 0
    measured = [f for f in run["flights"] if f.req.measured]
    for f in measured:
        times = list(f.token_times)
        late.append((f.sent - f.due) * 1000.0 if f.sent else cap_ms)
        ttft.append((times[0] - f.due) * 1000.0 if times else cap_ms)
        gaps += [(b - a) * 1000.0 for a, b in zip(times, times[1:])]
        done = (not f.thread.is_alive() and f.error is None
                and f.status == STATUS_OK)
        if done and len(f.tokens) != f.req.max_new:
            wrong_len += 1
        if not done:
            failed += 1
    in_window = sum(1 for f in run["flights"] for t in list(f.token_times)
                    if t_zero <= t < t_end)
    return {"attempted": len(measured), "failed": failed,
            "wrong_len": wrong_len,
            "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
            "itl_p50_ms": pct(gaps, 50), "itl_p99_ms": pct(gaps, 99),
            "itl_p90_p95_ms": [pct(gaps, 90), pct(gaps, 95)],
            "serve_tokens_per_s": in_window / seconds,
            "ttft_ms": ttft, "late_ms": late}


def measure(state) -> dict:
    ctx = state["ctx"]
    run = offer(state, ctx.seconds, tracer=ctx.tracer)
    out = client_numbers(run, ctx.seconds)
    stats = state["engine"].stats()
    ctx.say(f"{out['attempted']} requests due in the window, {out['failed']} "
            f"failed; ttft p50/p95 {out['ttft_p50_ms']:.1f}/"
            f"{out['ttft_p95_ms']:.1f} ms, itl p50/p99 "
            f"{out['itl_p50_ms']:.2f}/{out['itl_p99_ms']:.2f} ms (p90, p95 "
            f"{out['itl_p90_p95_ms']}), "
            f"{out['serve_tokens_per_s']:.1f} tokens/s; send lateness p95 "
            f"{pct(out['late_ms'], 95):.2f} ms; engine counters "
            f"{stats['counters']}")
    checks = dict(state["checks"])
    checks["streams_have_the_tokens_asked"] = out["wrong_len"] == 0
    return {
        "attempted": out["attempted"], "failed": out["failed"],
        "checks": checks,
        "end_to_end": {k: out[k] for k in ("ttft_p50_ms", "itl_p50_ms",
                                           "itl_p99_ms")},
        "evidence": {"spans": {"bench.serve.send_late_ms": out["late_ms"]},
                     "monitor": run["monitor"]},
    }


def close(state) -> None:
    state["server"].stop(drain=False)
