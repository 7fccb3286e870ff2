"""Runner `lfm2_serve`: `Lfm2MoeForCausalLM` (gated short-convolution
mixers with two bfloat16 rows a slot, grouped-query attention with K and V
pages, a dense FFN in the leading blocks and 64 routed experts, all held on
this chip, after them; bfloat16 weights) in `LLMEngine` behind
`PredictorServer`. The load, the client-side numbers, the measured window
and the shutdown are `runners/llm_serve.py`'s; the by-hand drive of the
engine's programs, the full forward, the model's cached path, the routing
tally, the router's balance rule, the warm wave and check 5 are
`runners/ling_serve.py`'s; the check's rows in groups of widths are
`runners/dots_serve.py`'s: all imported, as `runners/nemotron_serve.py`
imports them. What is this runner's own is the model it builds, the
reference it calls and what decides `correct` (checks 1 to 4).

Cell file keys read here: those of `llm_serve` (`generator`, `mix`,
`engine`) and `check`: `rows` (groups of `count` rows with a prompt length
drawn in `min`..`max`, compared at a width of `pad_to` positions; the rows
fill every slot), `full_rows` (rows of the full forward: the first
group's first row and the first rows of the last group), `prompts` (the
last rows, through the cached path; they fit the last group's width),
`decode_tokens`.

`correct` (beside `llm_serve`'s "every finished stream has exactly the
tokens asked" and the harness's "nothing compiled in the window"), all
against `reference/lfm2.py`: float32, `highest` precision, the convolution
a loop over its taps, attention dense and causal with each K/V head
repeated, the experts a dense loop over all 64 with a mask. The check's
rows are seeded, ONE A SLOT of the engine (every one of its slots), and
their lengths reach every prefill bucket: a prompt of n tokens, then the
`check.decode_tokens` tokens that THE ENGINE THAT SERVES THE WINDOW makes
of it, greedy, driven by hand before its scheduler starts; logits are
compared at position n - 1 and at each that follows. Routing is a discrete
choice that bfloat16 activations flip (`ling_serve` says why at length),
so the choice is held to the reference's wherever the reference is
decided, and the sums of every path to the reference's ON THAT PATH'S OWN
CHOICES.

1. `logits_match_reference`: the model's full forward of `full_rows`
   rows, every compared position;
2. `routing_matches_reference`, `routing_is_decided_often`,
   `routing_agreement_holds`, `router_keeps_its_precision`: the full
   forward's choices by `ling_serve`'s decided-margin rule (one group: the
   group margin plays no part), the router ALONE within `ROUTER_TOL`;
3. `cached_path_matches_reference`: the last `check.prompts` rows through
   the model's cached path in programs of the check's own;
4. `engine_matches_reference`, `engine_state_keeps_its_precision`: every
   row through THE SERVING ENGINE's `jit_llm_prefill` at its bucket and
   the slot write, then the executions of its `jit_llm_decode` over the
   whole pool, all slots live, teacher-forced on the tokens the engine
   made: logits and choices off the programs' own outputs, and the FIRST
   convolution layer's two kept rows read out of the pool against the
   reference's v at the same positions (`STATE_TOL`). The controls are in
   `benchmarks/lfm2_precision_control.py`;
5. over the wire (`ling_serve.warm`): every row at once; every streamed
   token is the one check 4's programs made, and those are the
   reference's arg-max wherever its top-2 margin exceeds twice the
   tolerance.

An error is max|model - reference| over the vocabulary at one row and
position, over max|reference| of all compared logits.
"""
from __future__ import annotations

import time

import numpy as np

# the program's model: a commit without it fails here, before anything runs
from paddle_tpu.models.lfm2 import layer_kinds

from .. import lfm2_cost
from ..reference import lfm2 as reference
from .brumby_serve import peak_gb
from .dots_serve import check_rows
from .ling_serve import (  # noqa: F401  (`warm`: the runner's interface)
    LOGIT_TOL, ROUTE_AGREE_MIN, ROUTE_DECIDED_MIN, ROUTE_SCORE_TOL,
    ROUTER_ROWS, ROUTER_TOL, _balanced_bias, _Tally, cached_logits,
    engine_rows, experts_reached, full_logits, make_engine, warm,
)
from .llm_serve import (  # noqa: F401  (the runner's interface)
    client_numbers, close, measure, offer,
)

# `LOGIT_TOL` is `ling_serve`'s, 5e-2: bfloat16 weights and activations with
# float32 sums through 10 pre-norm blocks of two residual additions each
# (Ling's seven blocks read 2.4e-2 to 3.4e-2, Nemotron's nine 1.5e-2 to
# 2.9e-2 on one v5e chip: PERF.md); a choice that is not the reference's
# reads 0.2 to 0.4.
# The FIRST convolution layer's two kept rows (v = B * x at a row's last
# two positions) in the pool after the decode steps against the
# reference's v there, rms over rms over every slot (check 4). Its input
# is the normed embedding, so nothing upstream blurs it and no choice
# reaches it. The configuration keeps the rows in bfloat16, so what they
# show is that rounding (2^-9 a value) on top of the projection's: a few
# 1e-3. Rows held one precision lower, an 8-bit float (the control,
# `benchmarks/lfm2_precision_control.py`), lose 2^-4 a value: a few 1e-2.
# The limit's two readings are in PERF.md.
STATE_TOL = 1e-2
# The rows and tokens a row that `balance_router_bias` reads, in chunks of
# `BALANCE_CHUNK` rows.
BALANCE_ROWS = 16
BALANCE_CHUNK = 4
BALANCE_TOKENS = 512
# Positions the reference takes in one call (rows of one width).
REFERENCE_TOKENS = 4096


def kinds(sizes: dict):
    return layer_kinds(sizes["layer_types"], lfm2_cost.layers_held(sizes),
                       sizes["num_dense_layers"])


def rope_theta(sizes: dict) -> float:
    return float(sizes["rope_parameters"]["rope_theta"])


def build_model(sizes: dict, seed: int):
    """The configuration as the program builds it: parameters created in
    the configuration's dtype, weights from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.models.lfm2 import Lfm2MoeForCausalLM, Lfm2MoeModel
    paddle.seed(seed)
    lm = Lfm2MoeForCausalLM(Lfm2MoeModel(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers_published"],
        layers=lfm2_cost.layers_held(sizes),
        layer_types=sizes["layer_types"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        conv_L_cache=sizes["conv_L_cache"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_experts=sizes["num_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        num_dense_layers=sizes["num_dense_layers"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        held=(0, sizes["num_experts"]), norm_eps=sizes["norm_eps"],
        rope_theta=rope_theta(sizes),
        router_bias_std=sizes["router_bias_std"],
        initializer_range=sizes["initializer_range"],
        dtype=sizes.get("torch_dtype", "float32")))
    lm.eval()
    balance_router_bias(lm, sizes, seed)
    return lm


def balance_router_bias(lm, sizes: dict, seed: int) -> None:
    """Every router's bias as the learned expert bias leaves it in a
    trained model (`ling_serve._balanced_bias`'s rule): one pass over
    `BALANCE_ROWS` seeded rows, a block at a time, through the blocks' own
    parts; an expert block's router is balanced on what reaches it."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    rng = np.random.default_rng(seed + 3)
    ids = rng.integers(0, sizes["vocab_size"],
                       (BALANCE_ROWS, BALANCE_TOKENS)).astype(np.int32)
    with paddle.no_grad():
        xs = [lm.model.embed_tokens(paddle.to_tensor(
            ids[r:r + BALANCE_CHUNK]))
              for r in range(0, BALANCE_ROWS, BALANCE_CHUNK)]
        for layer in lm.model.layers:
            # a prompt from an empty cache, every row whole
            none = [None] if layer.kind == "conv" else [None, None]
            for i, x in enumerate(xs):
                u = layer.operator_norm(x)
                y = (layer.mixer.forward_cached(u, *none, None, False)
                     if layer.kind == "conv" else
                     layer.mixer.forward_cached(u, *none, None, None,
                                                False))[0]
                xs[i] = x + y
            ms = [layer.ffn_norm(x) for x in xs]
            ffn = layer.feed_forward
            if layer.ffn_kind == "moe":
                logits = jnp.concatenate([jnp.matmul(
                    m._value.astype(jnp.float32).reshape(-1, m.shape[-1]),
                    ffn.router._value, precision=jax.lax.Precision.HIGHEST)
                    for m in ms])
                ffn.router_bias.set_value(_balanced_bias(
                    logits, ffn.router_bias._value, ffn))
            xs = [x + ffn(m) for x, m in zip(xs, ms)]


def against_reference(lm, sizes: dict, ids, n, width, steps: int, logits,
                      routes, scores=None, rows=None) -> dict:
    """One path (`logits` [rows, steps + 1, V], `routes`: per expert block
    the chosen experts [rows, T, top_k]) against the reference ON THAT
    PATH'S CHOICES, rows of one width `REFERENCE_TOKENS` positions at a
    time. `scores` (per expert block [rows, T, experts], the path's)
    gives the routing readings; `rows` (the first convolution block's kept
    rows [rows, K - 1, hidden] as the path kept them after the last
    compared position) the state error, rms over rms. Returns `err`
    (largest |logits - reference| over the largest |reference| of all
    compared) and `row_err` [rows] on that scale, `argmax` and `margin`
    [rows, steps + 1] of the reference (its top-2 margin over that scale),
    `state_error`, and under "routing" the readings of
    `ling_serve._Tally`."""
    named = {k: p._value for k, p in lm.named_parameters()}
    at = n[:, None] - 1 + np.arange(steps + 1)[None, :]
    worst, scale = np.zeros(len(n)), 0.0
    argmax = np.zeros(at.shape, np.int64)
    margin = np.zeros(at.shape)
    sq = np.zeros(2)  # [error, reference]
    tally = _Tally(steps) if scores is not None else None
    for w in sorted(set(width.tolist())):
        mine = np.flatnonzero(width == w)
        batch = max(1, REFERENCE_TOKENS // w)
        for r0 in range(0, len(mine), batch):
            part = mine[r0:r0 + batch]
            out, routing, kept = reference.forward(
                named, ids[part, :w], at[part], kinds=kinds(sizes),
                heads=sizes["num_attention_heads"],
                kv_heads=sizes["num_key_value_heads"], first=0,
                top_k=sizes["num_experts_per_tok"],
                scaling=float(sizes["routed_scaling_factor"]),
                theta=rope_theta(sizes), eps=float(sizes["norm_eps"]),
                taps=sizes["conv_L_cache"],
                forced=[a[part, :w] for a in routes], rows_at=at[part, -1])
            out = np.asarray(out)
            worst[part] = np.abs(logits[part] - out).max(axis=(1, 2))
            scale = max(scale, float(np.abs(out).max()))
            top2 = np.sort(np.partition(out, -2, axis=-1)[..., -2:], -1)
            argmax[part] = np.argmax(out, axis=-1)
            margin[part] = top2[..., 1] - top2[..., 0]
            if rows is not None:
                want = np.asarray(kept[0], np.float64)
                sq += [np.sum(np.square(rows[part] - want)),
                       np.sum(np.square(want))]
            if tally is not None:
                for i, r in enumerate(part):
                    tally.add(int(n[r]), [a[r, :w] for a in routes],
                              [a[r, :w] for a in scores],
                              [{key: np.asarray(v)[i]
                                for key, v in layer.items()}
                               for layer in routing])
    found = {"err": float(worst.max() / scale), "row_err": worst / scale,
             "argmax": argmax, "margin": margin / scale}
    if rows is not None:
        found["state_error"] = float(np.sqrt(sq[0] / sq[1]))
    if tally is not None:
        found["routing"] = tally.readings()
    return found


def router_error(lm, sizes: dict, seed: int) -> float:
    """The router alone: see `ling_serve.ROUTER_TOL`."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..reference.ling import choose
    worst = 0.0
    key = jax.random.key(seed % (2 ** 32))
    for layer in lm.model.layers:
        if layer.ffn_kind != "moe":
            continue
        ffn = layer.feed_forward
        key, sub = jax.random.split(key)
        m = jax.random.normal(sub, (ROUTER_ROWS, sizes["hidden_size"]),
                              jnp.float32).astype(ffn.up_proj.dtype)
        with paddle.no_grad():
            scores = ffn.choose(paddle.to_tensor(m))[2]
        with jax.default_matmul_precision("highest"):
            biased = choose(
                m.astype(jnp.float32), ffn.router._value,
                ffn.router_bias._value, top_k=ffn.top_k,
                n_group=ffn.n_group, topk_group=ffn.topk_group,
                scaling=ffn.scaling)[-1]
        worst = max(worst, float(np.max(np.abs(
            np.asarray(scores.numpy()) - np.asarray(biased)))))
    return worst


def first_rows(engine, slot):
    """The first convolution block's kept rows as the pool holds them, the
    check's rows in their order: [rows, K - 1, hidden] float32."""
    pool = next(t for t, tag in zip(engine._pool, engine._tags)
                if tag == "state_pool")
    return np.asarray(pool.numpy(), np.float32)[slot]


def run_checks(lm, engine, cell: dict, seed: int, say, hold=None) -> dict:
    """Checks 1 to 4 on `engine`, whose scheduler is not running. `hold` (a
    control's: pool -> pool) is applied to the pool after every call of
    the engine's programs. Returns the checks, the readings, the rows as
    the engine completed them, the tokens it made and what the reference
    says of them (check 5 reads those)."""
    sizes, chk = cell["config_sizes"], cell["check"]
    steps, few = chk["decode_tokens"], chk["prompts"]
    ids, n, width = check_rows(cell, seed)
    t0 = time.perf_counter()
    served, made, routes, slot = engine_rows(engine, ids, n, steps,
                                             hold=hold)
    rows = first_rows(engine, slot)
    say(f"the serving engine's programs by hand, {len(n)} slots live, "
        f"{steps} decode executions in {time.perf_counter() - t0:.1f}s; "
        f"allocator peak {peak_gb():.2f} GB")
    t0 = time.perf_counter()
    eng = against_reference(lm, sizes, ids, n, width, steps, served, routes,
                            rows=rows)
    by_width = {int(w): round(float(eng["row_err"][width == w].max()), 5)
                for w in sorted(set(width.tolist()))}
    say(f"reference on the engine's choices: {len(n)} rows of "
        f"{sorted(set(width.tolist()))} positions in "
        f"{time.perf_counter() - t0:.1f}s; allocator peak {peak_gb():.2f} GB")
    # the full forward: the first row (the longest prompts) and the first
    # rows of the last group, one program a width
    last_group = len(n) - chk["rows"][-1]["count"]
    order = [np.array([0]),
             np.arange(last_group, last_group + chk["full_rows"] - 1)]
    t0 = time.perf_counter()
    parts = [full_logits(lm, ids[rows_, :width[rows_[0]]], n[rows_], steps,
                         len(rows_)) for rows_ in order]
    picked = np.concatenate(order)
    full = np.concatenate([logits for logits, _ in parts])

    def joined(layer, k):
        """Part k of an expert block's report, the parts' rows stacked and
        padded to the widest width."""
        got = [pairs[layer][k] for _, pairs in parts]
        wide = lambda a: [(0, 0), (0, ids.shape[1] - a.shape[1])] \
            + [(0, 0)] * (a.ndim - 2)
        return np.concatenate([np.pad(a, wide(a)) for a in got])
    layers = range(len(parts[0][1]))
    found = against_reference(
        lm, sizes, ids[picked], n[picked], width[picked], steps, full,
        [joined(l, 0) for l in layers], scores=[joined(l, 1) for l in layers])
    say(f"the full forward of rows {picked.tolist()} and the reference on "
        f"its choices in {time.perf_counter() - t0:.1f}s; allocator peak "
        f"{peak_gb():.2f} GB")
    routing = found["routing"]
    routing["router_error"] = router_error(lm, sizes, seed)
    reached = experts_reached(routes, n, steps, 0, sizes["num_experts"])
    last = np.arange(len(n) - few, len(n))
    pad_to = int(width[last].max())
    got, path_routes = cached_logits(lm, ids[last], n[last], pad_to, steps)
    path = against_reference(lm, sizes, ids[last], n[last], width[last],
                             steps, got, path_routes)
    say(f"prompt lengths {sorted(n.tolist())}, {steps + 1} positions a "
        f"prompt; against the reference on a path's own choices: full "
        f"forward {found['err']:.3e}, the serving engine's programs "
        f"{eng['err']:.3e} (by width: {by_width}), the model's cached path "
        f"in the check's own programs, rows {last.tolist()}, "
        f"{path['err']:.3e} (tolerance {LOGIT_TOL}); the first convolution "
        f"layer's rows in the pool {eng['state_error']:.3e} (limit "
        f"{STATE_TOL}); routing {routing} (every decided choice agrees, at "
        f"least {ROUTE_DECIDED_MIN} decided, at least {ROUTE_AGREE_MIN} "
        f"agree, score error under {ROUTE_SCORE_TOL}, the router alone "
        f"under {ROUTER_TOL}); held experts {len(n)} rows reach a layer: "
        f"counted {reached:.2f}, lfm2_cost expects "
        f"{lfm2_cost.experts_reached(sizes, len(n)):.2f}; allocator peak "
        f"{peak_gb():.2f} GB")
    checks = {
        "logits_match_reference": bool(found["err"] <= LOGIT_TOL),
        "routing_matches_reference": bool(
            routing["decided_agree"] == routing["decided"]
            and routing["score_error"] <= ROUTE_SCORE_TOL),
        "routing_is_decided_often": bool(
            routing["decided_share"] >= ROUTE_DECIDED_MIN),
        "routing_agreement_holds": bool(
            routing["agree_share"] >= ROUTE_AGREE_MIN),
        "router_keeps_its_precision": bool(
            routing["router_error"] <= ROUTER_TOL),
        "cached_path_matches_reference": bool(path["err"] <= LOGIT_TOL),
        "engine_matches_reference": bool(eng["err"] <= LOGIT_TOL),
        "engine_state_keeps_its_precision": bool(
            eng["state_error"] <= STATE_TOL)}
    return {"checks": checks, "ids": ids, "n": n, "made": made,
            "ref_argmax": eng["argmax"], "ref_margin": eng["margin"],
            "routing": routing,
            "readings": {"full": found["err"], "engine": eng["err"],
                         "cached_path": path["err"],
                         "state_error": eng["state_error"],
                         "experts_reached_a_step": reached}}


def build(cell: dict, ctx) -> dict:
    import paddle_tpu as paddle
    from paddle_tpu.inference.server import PredictorServer

    sizes = cell["config_sizes"]
    paddle.set_device(ctx.device)
    if ctx.trace:
        paddle.set_flags({"FLAGS_monitor": True})
    lm = build_model(sizes, ctx.seed)
    ctx.say(f"built {cell['config']}: "
            f"{sum(int(np.prod(p.shape)) for p in lm.parameters())} "
            f"parameters in {sizes.get('torch_dtype', 'float32')}, layers "
            f"{kinds(sizes)}; allocator peak {peak_gb():.2f} GB")
    engine = make_engine(lm, cell["engine"])
    found = run_checks(lm, engine, cell, ctx.seed, ctx.say)
    server = PredictorServer(lambda x: x, llm_engine=engine).start()
    ctx.say(f"engine up: {engine.stats()['slots']} slots, pool "
            f"{engine.kv_pool_bytes() / 1e9:.3f} GB (rows "
            f"{engine.kv_pool_bytes('state_pool') / 1e9:.4f}, pages "
            f"{engine.kv_pool_bytes('kv_pool') / 1e9:.3f}), buckets "
            f"{engine.buckets}, serving on {server.host}:{server.port}; "
            f"allocator peak {peak_gb():.2f} GB")
    return {"cell": cell, "ctx": ctx, "lm": lm, "engine": engine,
            "server": server, "checks": found["checks"], "found": found}
