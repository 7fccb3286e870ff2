"""Runner `nemotron_serve`: `NemotronHForCausalLM` (Mamba-2 layers with a
float32 state and a convolution's rows, a grouped-query attention layer
with K and V pages, relu^2 routed experts of which this chip holds a
stated share; bfloat16 weights) in `LLMEngine` behind `PredictorServer`.
The load, the client-side numbers, the measured window and the shutdown
are `runners/llm_serve.py`'s; the by-hand drive of the engine's programs,
the full forward, the model's cached path, the routing tally, the router's
balance rule, the warm wave and check 5 are `runners/ling_serve.py`'s; the
check's rows in groups of widths are `runners/dots_serve.py`'s: all
imported. What is this runner's own is the model it builds, the
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
against `reference/nemotron.py`: float32, `highest` precision, the scan as
the plain recurrence over positions, attention with each K/V head
repeated, the experts as a loop with a mask, given the same share of the
experts and of the vocabulary. The check's rows are seeded, ONE A SLOT of
the engine (every one of its slots), and their lengths reach every
prefill bucket: a prompt of n tokens, then the `check.decode_tokens`
tokens that THE ENGINE THAT SERVES THE WINDOW makes of it, greedy, driven
by hand before its scheduler starts; logits are compared at position
n - 1 and at each that follows. Routing is a discrete choice that
bfloat16 activations flip (`ling_serve` says why at length), so the
choice is held to the reference's wherever the reference is decided, and
the sums of every path to the reference's ON THAT PATH'S OWN CHOICES.

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
   the slot write, then 96 executions of its `jit_llm_decode` over the
   whole pool, all slots live, teacher-forced on the tokens the engine
   made: logits and choices off the programs' own outputs, and the FIRST
   Mamba layer's state read out of the pool, over the quarter of its heads
   that remember longest (`STATE_TOL`). The controls are in
   `benchmarks/nemotron_precision_control.py`;
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

from .. import dots_cost, nemotron_cost
from ..reference import nemotron as reference
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

# `LOGIT_TOL` is `ling_serve`'s, 5e-2: the same bfloat16 weights and
# activations with float32 sums through pre-norm blocks whose routed sum is
# scaled by 2.5 (seven blocks there read 2.4e-2 to 3.4e-2, five Dots blocks
# 1.4e-2 to 1.7e-2 on one v5e chip: PERF.md); a choice that is
# not the reference's reads 0.2 to 0.4.
# The FIRST Mamba layer's state in the pool after the 96 steps against the
# reference's, rms over rms over the quarter of its heads that remember
# longest (`long_heads`), every slot (check 4). Its input is the normed
# embedding, so nothing upstream blurs it and no choice reaches it. What a
# float32 state shows is the bfloat16 rounding of its projection's
# outputs, which comes with every token alike and which a head that sums
# many tokens averages down. A state HELD in bfloat16 (the control,
# `benchmarks/nemotron_precision_control.py`) is rounded whole after every
# call of the programs and keeps each loss as long as it remembers, so its
# error grows with the steps where a head remembers them. Over all 64
# heads the two read 2.13e-3 to 2.23e-3 and 5.76e-3 at 96 steps on one v5e
# chip: too close, since most heads forget within ~15 tokens. The limit's
# two readings over the long-remembering quarter are in PERF.md.
STATE_TOL = 3e-3
# The rows and tokens a row that `balance_router_bias` reads, in chunks of
# `BALANCE_CHUNK` rows.
BALANCE_ROWS = 16
BALANCE_CHUNK = 4
BALANCE_TOKENS = 512
# Rows of one width the reference takes in one call.
REFERENCE_BATCH = 16


def kinds(sizes: dict):
    from paddle_tpu.models.nemotron import layer_kinds
    return layer_kinds(sizes["hybrid_override_pattern"],
                       nemotron_cost.layers_held(sizes))


def build_model(sizes: dict, seed: int):
    """The configuration as the program builds it: parameters created in
    the configuration's dtype, weights from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron import NemotronHForCausalLM, NemotronHModel
    paddle.seed(seed)
    lm = NemotronHForCausalLM(NemotronHModel(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers_published"],
        layers=nemotron_cost.layers_held(sizes),
        hybrid_override_pattern=sizes["hybrid_override_pattern"],
        mamba_num_heads=sizes["mamba_num_heads"],
        mamba_head_dim=sizes["mamba_head_dim"], n_groups=sizes["n_groups"],
        ssm_state_size=sizes["ssm_state_size"],
        conv_kernel=sizes["conv_kernel"], chunk_size=sizes["chunk_size"],
        time_step_min=sizes["time_step_min"],
        time_step_max=sizes["time_step_max"],
        time_step_floor=sizes["time_step_floor"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=sizes[
            "moe_shared_expert_intermediate_size"],
        n_routed_experts=sizes["n_routed_experts_published"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        n_group=sizes["n_group"], topk_group=sizes["topk_group"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        held=(sizes.get("experts_held_first", 0), sizes["n_routed_experts"]),
        layer_norm_epsilon=sizes["layer_norm_epsilon"],
        router_bias_std=sizes["router_bias_std"],
        initializer_range=sizes["initializer_range"],
        dtype=sizes.get("torch_dtype", "float32")))
    lm.eval()
    balance_router_bias(lm, sizes, seed)
    return lm


def balance_router_bias(lm, sizes: dict, seed: int) -> None:
    """Every router's bias as auxiliary-loss-free balancing leaves it in a
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
        xs = [lm.backbone.embeddings(paddle.to_tensor(
            ids[r:r + BALANCE_CHUNK]))
              for r in range(0, BALANCE_ROWS, BALANCE_CHUNK)]
        for layer in lm.backbone.layers:
            us = [layer.norm(x) for x in xs]
            if layer.kind == "moe":
                mlp = layer.mixer
                logits = jnp.concatenate([jnp.matmul(
                    u._value.astype(jnp.float32).reshape(-1, u.shape[-1]),
                    mlp.router._value, precision=jax.lax.Precision.HIGHEST)
                    for u in us])
                mlp.router_bias.set_value(_balanced_bias(
                    logits, mlp.router_bias._value, mlp))
                xs = [x + mlp(u) for x, u in zip(xs, us)]
            else:
                # a prompt from an empty cache, every row whole
                none = [None, None]
                xs = [x + (layer.mixer.forward_cached(u, *none, None, False)
                           if layer.kind == "mamba" else
                           layer.mixer.forward_cached(u, *none, None, None,
                                                      False))[0]
                      for x, u in zip(xs, us)]


def against_reference(lm, sizes: dict, ids, n, width, steps: int, logits,
                      routes, scores=None, states=None) -> dict:
    """One path (`logits` [rows, steps + 1, V], `routes`: per expert block
    the chosen experts [rows, T, top_k]) against the reference ON THAT
    PATH'S CHOICES, rows of one width `REFERENCE_BATCH` at a time.
    `scores` (per expert block [rows, T, experts], the path's) gives the
    routing readings; `states` (the first Mamba block's [rows, H, P, N]
    as the path kept it after the last compared position) the state
    errors, rms over rms: `state_error` over the heads `long_heads`
    names, `state_error_all_heads` over all. Returns `err`
    (largest |logits - reference| over the largest |reference| of all
    compared), `argmax` and `margin` [rows, steps + 1] of the reference
    (its top-2 margin over that scale), the state errors, and under
    "routing" the readings of `ling_serve._Tally`."""
    named = {k: p._value for k, p in lm.named_parameters()}
    at = n[:, None] - 1 + np.arange(steps + 1)[None, :]
    worst, scale = np.zeros(len(n)), 0.0
    argmax = np.zeros(at.shape, np.int64)
    margin = np.zeros(at.shape)
    sq = np.zeros((2, 2))  # [long heads, all heads] x [error, reference]
    tally = _Tally(steps) if scores is not None else None
    for w in sorted(set(width.tolist())):
        mine = np.flatnonzero(width == w)
        for r0 in range(0, len(mine), REFERENCE_BATCH):
            rows = mine[r0:r0 + REFERENCE_BATCH]
            out, routing, kept = reference.forward(
                named, ids[rows, :w], at[rows], kinds=kinds(sizes),
                heads=sizes["num_attention_heads"],
                kv_heads=sizes["num_key_value_heads"],
                head_dim=sizes["head_dim"],
                mamba_heads=sizes["mamba_num_heads"],
                mamba_head_dim=sizes["mamba_head_dim"],
                groups=sizes["n_groups"], state=sizes["ssm_state_size"],
                first=sizes.get("experts_held_first", 0),
                top_k=sizes["num_experts_per_tok"], n_group=sizes["n_group"],
                topk_group=sizes["topk_group"],
                scaling=float(sizes["routed_scaling_factor"]),
                eps=float(sizes["layer_norm_epsilon"]),
                forced=[a[rows, :w] for a in routes],
                state_at=at[rows, -1])
            out = np.asarray(out)
            worst[rows] = np.abs(logits[rows] - out).max(axis=(1, 2))
            scale = max(scale, float(np.abs(out).max()))
            top2 = np.sort(out, axis=-1)[..., -2:]
            argmax[rows] = np.argmax(out, axis=-1)
            margin[rows] = top2[..., 1] - top2[..., 0]
            if states is not None:
                want = np.asarray(kept[0], np.float64)
                for i, h in enumerate((long_heads(lm), slice(None))):
                    sq[i] += [np.sum(np.square(states[rows][:, h]
                                               - want[:, h])),
                              np.sum(np.square(want[:, h]))]
            if tally is not None:
                for i, r in enumerate(rows):
                    tally.add(int(n[r]), [a[r, :w] for a in routes],
                              [a[r, :w] for a in scores],
                              [{key: np.asarray(v)[i]
                                for key, v in layer.items()}
                               for layer in routing])
    found = {"err": float(worst.max() / scale), "row_err": worst / scale,
             "argmax": argmax, "margin": margin / scale}
    if states is not None:
        found["state_error"] = float(np.sqrt(sq[0, 0] / sq[0, 1]))
        found["state_error_all_heads"] = float(np.sqrt(sq[1, 0] / sq[1, 1]))
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
    for layer in lm.backbone.layers:
        if layer.kind != "moe":
            continue
        mlp = layer.mixer
        key, sub = jax.random.split(key)
        m = jax.random.normal(sub, (ROUTER_ROWS, sizes["hidden_size"]),
                              jnp.float32).astype(mlp.up_proj.dtype)
        with paddle.no_grad():
            scores = mlp.choose(paddle.to_tensor(m))[2]
        with jax.default_matmul_precision("highest"):
            biased = choose(
                m.astype(jnp.float32), mlp.router._value,
                mlp.router_bias._value, top_k=mlp.top_k,
                n_group=mlp.n_group, topk_group=mlp.topk_group,
                scaling=mlp.scaling)[-1]
        worst = max(worst, float(np.max(np.abs(
            np.asarray(scores.numpy()) - np.asarray(biased)))))
    return worst


def long_heads(lm):
    """The quarter of the first Mamba block's heads that remember longest,
    by their seeded start: a head keeps a token ~1 / (Delta A) positions,
    Delta = softplus(dt_bias) (the projection's share of Delta left out),
    A = exp(A_log). See `STATE_TOL`."""
    import jax

    mixer = next(layer.mixer for layer in lm.backbone.layers
                 if layer.kind == "mamba")
    rate = np.asarray(jax.nn.softplus(mixer.dt_bias._value)) * np.exp(
        np.asarray(mixer.A_log._value))
    return np.sort(np.argsort(rate)[:len(rate) // 4])


def first_state(engine, slot):
    """The first Mamba block's float32 state as the pool holds it, the
    check's rows in their order: [rows, H, P, N]."""
    pool = next(t for t in engine._pool if len(t.shape) == 4)
    return np.asarray(pool.numpy())[slot]


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
    states = first_state(engine, slot)
    say(f"the serving engine's programs by hand, {len(n)} slots live, "
        f"{steps} decode executions in {time.perf_counter() - t0:.1f}s; "
        f"allocator peak {peak_gb():.2f} GB")
    t0 = time.perf_counter()
    eng = against_reference(lm, sizes, ids, n, width, steps, served, routes,
                            states=states)
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
    parts = [full_logits(lm, ids[rows, :width[rows[0]]], n[rows], steps,
                         len(rows)) for rows in order]
    rows = np.concatenate(order)
    full = np.concatenate([logits for logits, _ in parts])

    def joined(layer, k):
        """Part k of an expert layer's report, the parts' rows stacked and
        padded to the widest width."""
        got = [pairs[layer][k] for _, pairs in parts]
        wide = lambda a: [(0, 0), (0, ids.shape[1] - a.shape[1])] \
            + [(0, 0)] * (a.ndim - 2)
        return np.concatenate([np.pad(a, wide(a)) for a in got])
    layers = range(len(parts[0][1]))
    found = against_reference(
        lm, sizes, ids[rows], n[rows], width[rows], steps, full,
        [joined(l, 0) for l in layers], scores=[joined(l, 1) for l in layers])
    say(f"the full forward of rows {rows.tolist()} and the reference on its "
        f"choices in {time.perf_counter() - t0:.1f}s; allocator peak "
        f"{peak_gb():.2f} GB")
    routing = found["routing"]
    routing["router_error"] = router_error(lm, sizes, seed)
    first = sizes.get("experts_held_first", 0)
    reached = experts_reached(routes, n, steps, first,
                              sizes["n_routed_experts"])
    last = np.arange(len(n) - few, len(n))
    pad_to = int(width[last].max())
    got, path_routes = cached_logits(lm, ids[last], n[last], pad_to, steps)
    path = against_reference(lm, sizes, ids[last], n[last], width[last],
                             steps, got, path_routes)
    say(f"prompt lengths {sorted(n.tolist())}, {steps + 1} positions a "
        f"prompt; against the reference on a path's own choices: full "
        f"forward {found['err']:.3e}, the serving engine's programs "
        f"{eng['err']:.3e} (by width: {by_width}), the model's cached path "
        f"in the check's own programs, rows "
        f"{last.tolist()}, {path['err']:.3e} (tolerance {LOGIT_TOL}); the "
        f"first Mamba layer's state in the pool {eng['state_error']:.3e} "
        f"over its longest-remembering quarter of heads (limit "
        f"{STATE_TOL}), {eng['state_error_all_heads']:.3e} over all; "
        f"routing {routing} (every decided choice "
        f"agrees, at least {ROUTE_DECIDED_MIN} decided, at least "
        f"{ROUTE_AGREE_MIN} agree, score error under {ROUTE_SCORE_TOL}, the "
        f"router alone under {ROUTER_TOL}); held experts {len(n)} rows reach "
        f"a layer: counted {reached:.2f}, dots_cost expects "
        f"{dots_cost.experts_reached(sizes, len(n)):.2f}; allocator peak "
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
                         "state_error_all_heads": eng["state_error_all_heads"],
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
            f"{engine.kv_pool_bytes() / 1e9:.3f} GB (state "
            f"{engine.kv_pool_bytes('state_pool') / 1e9:.3f}, pages "
            f"{engine.kv_pool_bytes('kv_pool') / 1e9:.3f}), buckets "
            f"{engine.buckets}, serving on {server.host}:{server.port}; "
            f"allocator peak {peak_gb():.2f} GB")
    return {"cell": cell, "ctx": ctx, "lm": lm, "engine": engine,
            "server": server, "checks": found["checks"], "found": found}
