"""Runner `dots_serve`: `DotsForCausalLM` (latent attention in every layer:
the low-rank query, YaRN positions, a latent page a layer; routed experts of
which this chip holds a stated share; bfloat16 weights) in `LLMEngine`
behind `PredictorServer`. The load, the client-side numbers, the measured
window and the shutdown are `runners/llm_serve.py`'s, imported; the by-hand
drive of the engine's programs, the routing tally and the router's balance
are `runners/ling_serve.py`'s, imported: one scheduler, one wire, one
generator, one expert layer serve both routed models. What is this runner's
own is the model it builds, the rows it checks and what decides `correct`
(checks 1 to 4; the warm wave and check 5 over the wire are `ling_serve.warm`
on what check 4 hands it).

Cell file keys read here: those of `llm_serve` (`generator`, `mix`,
`engine`) and `check`: `rows` (groups of `count` rows with a prompt length
drawn in `min`..`max`, compared at a width of `pad_to` positions),
`full_rows`, `prompts`, `pad_to`, `decode_tokens`.

`correct` (beside `llm_serve`'s "every finished stream has exactly the
tokens asked" and the harness's "nothing compiled in the window"), all
against `reference/dots.py`: float32, `highest` precision, latent attention
EXPANDED (never absorbed), YaRN in its plain form, the experts as a loop
with a mask, given the same share of the experts and of the vocabulary. The
check's rows are seeded, ONE A SLOT of the engine, and their lengths reach
EVERY prefill bucket, the longest included (`check.rows`): a prompt of n
tokens, then the `check.decode_tokens` tokens that THE ENGINE THAT SERVES
THE WINDOW makes of it, greedy, driven by hand before its scheduler starts;
logits are compared at position n - 1 and at each of the 16 that follow.
The longest row decodes at positions past 8,192: what is compared there is
the kernel's long read and YaRN beyond the original 4,096 positions.

Routing is a discrete choice and bfloat16 activations flip it (PERF.md,
PR 33; `runners/ling_serve.py` says why at length), so THE CHOICE is held
to the reference's wherever the reference is decided, and THE SUMS of every
path to the reference's ON THAT PATH'S OWN CHOICES (`forced`).

1. `logits_match_reference`: the model's full forward of the first
   `check.full_rows` rows (one of every group), every compared position;
2. `routing_matches_reference`, `routing_is_decided_often`,
   `routing_agreement_holds`, `router_keeps_its_precision`: the full
   forward's choice at every real position of those rows and every expert
   layer against the reference's own choice on the same state, by
   `ling_serve`'s decided-margin rule; the router ALONE within
   `ROUTER_TOL`;
3. `cached_path_matches_reference`: the LAST `check.prompts` rows (short
   ones) through the model's cached path in programs of the check's own.
   The diagnostic that tells a fault of the model from one of the engine;
4. `engine_matches_reference`: every row through THE SERVING ENGINE's
   `jit_llm_prefill` at its bucket and the slot write into a slot of its
   own, then 16 executions of its `jit_llm_decode` over the whole pool, all
   slots live, each slot fed the token the step before made: logits and
   choices off the programs' own outputs, largest error on any slot (the
   rows past 8,192 positions are also reported alone); and
   `latent_read_keeps_its_precision`: the decode step's read ALONE, as the
   router is held alone: `F.latent_attention_decode` (the path the decode
   program takes: `mla_decode` on a TPU) with the first layer's own
   up-projection and seeded queries over THE SERVING POOL's first-layer
   pages as the 16 steps left them, every slot at its own length, against
   `reference.latent_step` (float32, expanded) on the same rows
   (`MLA_TOL`). The control that has to fail is in
   `benchmarks/dots_precision_control.py`;
5. `streamed_tokens_are_the_engines_own`,
   `streamed_tokens_are_reference_argmax`: over the wire, every row
   submitted AT ONCE: each stream's 16 tokens are the 16 that check 4's
   programs made of that prompt (no tolerance), and those are the
   reference's arg-max wherever its top-2 margin exceeds twice the
   tolerance.

An error is max|model - reference| over the vocabulary at one row and
position, over max|reference| of all compared logits.
"""
from __future__ import annotations

import time

import numpy as np

from .. import dots_cost
from ..reference import dots as reference
from .brumby_serve import peak_gb
from .ling_serve import (  # noqa: F401  (`warm`: the runner's interface)
    LOGIT_TOL, ROUTE_AGREE_MIN, ROUTE_DECIDED_MIN, ROUTE_SCORE_TOL,
    ROUTER_ROWS, ROUTER_TOL, _balanced_bias, _Tally, engine_rows,
    experts_reached, make_engine, warm,
)
from .llm_serve import (  # noqa: F401  (the runner's interface)
    client_numbers, close, measure, offer,
)

# `LOGIT_TOL` (a path against the reference on that path's choices, largest
# error over every row and position) is `ling_serve`'s, 5e-2, and so is the
# `warm` that reads it (the warm wave, then check 5 over the wire): the same
# bfloat16 weights and activations with float32 sums through pre-norm
# blocks whose routed sum is scaled by 2.5. Five blocks here read 1.4e-2 to
# 1.7e-2 on the chip (PERF.md, PR 35: a third of the limit; Ling's seven
# read 2.4e-2 to 3.4e-2); a choice that is not the reference's reads 0.2 to
# 0.4 (PERF.md, PR 33).
# The decode step's read alone (check 4), max|program - reference| over
# max|reference| of a slot's [heads, v] output, largest over the slots.
# With float32 statistics what is left is the bfloat16 rounding of the
# absorbed query and of the probabilities, averaged over a slot's
# thousands of rows: the chip reads 3.4e-3 to 4.9e-3 (PERF.md, PR 35). A
# running maximum, sum or context held in bfloat16 loses 2^-9 at every
# merge of a block (18 merges of 512 rows at 9,216 positions): the control
# reads 2.2e-2, and the model's logits move by less than the activations'
# own rounding (1.5e-2 either way: the logits cannot see it, the read alone
# can). The limit lies between, 1.8 x and 2.5 x from the two readings.
MLA_TOL = 9e-3
LONG_FROM = 8192
# The rows and tokens a row that `balance_router_bias` reads (a chunk of
# rows a call).
BALANCE_ROWS = 16
BALANCE_CHUNK = 4
BALANCE_TOKENS = 512


def dense_layers(sizes: dict):
    return [l < sizes["first_k_dense_replace"]
            for l in dots_cost.layers_held(sizes)]


def build_model(sizes: dict, seed: int):
    """The configuration as the program builds it: parameters created in
    the configuration's dtype, weights from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.models.dots import DotsForCausalLM, DotsModel
    paddle.seed(seed)
    lm = DotsForCausalLM(DotsModel(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers_published"],
        layers=dots_cost.layers_held(sizes),
        num_attention_heads=sizes["num_attention_heads"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        n_routed_experts=sizes["n_routed_experts_published"],
        n_shared_experts=sizes["n_shared_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        n_group=sizes["n_group"], topk_group=sizes["topk_group"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        held=(sizes.get("experts_held_first", 0), sizes["n_routed_experts"]),
        first_k_dense_replace=sizes["first_k_dense_replace"],
        q_lora_rank=sizes["q_lora_rank"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"], rope_theta=sizes["rope_theta"],
        rope_scaling=sizes["rope_scaling"],
        rms_norm_eps=sizes["rms_norm_eps"],
        router_bias_std=sizes["router_bias_std"],
        initializer_range=sizes["initializer_range"],
        dtype=sizes.get("torch_dtype", "float32")))
    lm.eval()
    balance_router_bias(lm, sizes, seed)
    return lm


def balance_router_bias(lm, sizes: dict, seed: int) -> None:
    """Set every router's bias as auxiliary-loss-free balancing leaves it in
    a trained model (`ling_serve.balance_router_bias`, whose rule this is:
    random weights pick a few experts on every token, and `itl_p50_ms`
    followed the seed by 3.3%): one pass over `BALANCE_ROWS` seeded rows, a
    layer at a time, through the layer's own parts."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    rng = np.random.default_rng(seed + 3)
    ids = rng.integers(0, sizes["vocab_size"],
                       (BALANCE_ROWS, BALANCE_TOKENS)).astype(np.int32)
    with paddle.no_grad():
        xs = [lm.dots.embed_tokens(paddle.to_tensor(ids[r:r + BALANCE_CHUNK]))
              for r in range(0, BALANCE_ROWS, BALANCE_CHUNK)]
        for layer in lm.dots.layers:
            # a prompt from an empty page, every row whole, positions from 0
            xs = [x + layer.mixer.forward_cached(
                layer.input_norm(x), None, None, None, False)[0] for x in xs]
            ms = [layer.post_norm(x) for x in xs]
            if layer.ffn_kind == "moe":
                mlp = layer.mlp
                logits = jnp.concatenate([jnp.matmul(
                    m._value.astype(jnp.float32).reshape(-1, m.shape[-1]),
                    mlp.router._value, precision=jax.lax.Precision.HIGHEST)
                    for m in ms])
                mlp.router_bias.set_value(_balanced_bias(
                    logits, mlp.router_bias._value, mlp))
            xs = [x + layer.mlp(m) for x, m in zip(xs, ms)]


def check_rows(cell: dict, seed: int):
    """The seeded rows of the check, one a slot of the engine, in the order
    of `check.rows`' groups: n prompt tokens and `decode_tokens` that
    follow. Returns (ids [rows, the widest pad_to], n [rows], width [rows]:
    the positions a row is compared over, its group's `pad_to`, which is
    one of a few fixed widths so that the reference and the full forward
    compile a few shapes and not one a row)."""
    chk, sizes = cell["check"], cell["config_sizes"]
    steps = chk["decode_tokens"]
    rng = np.random.default_rng(seed + 1)
    n, width = [], []
    for group in chk["rows"]:
        if group["max"] + steps > group["pad_to"]:
            raise ValueError(f"check.rows {group}: max + decode_tokens "
                             "exceeds pad_to")
        n += rng.integers(group["min"], group["max"] + 1,
                          group["count"]).tolist()
        width += [group["pad_to"]] * group["count"]
    if len(n) != cell["engine"]["num_slots"]:
        raise ValueError(f"check.rows holds {len(n)} rows; the engine has "
                         f"{cell['engine']['num_slots']} slots")
    ids = np.zeros((len(n), max(width)), np.int32)
    for r, length in enumerate(n):
        ids[r, :length + steps] = rng.integers(0, sizes["vocab_size"],
                                               length + steps)
    return ids, np.asarray(n, np.int32), np.asarray(width, np.int32)


def full_logits(lm, ids, n, width, steps: int):
    """The model's full forward of the given rows, a row a call over its
    own width. Returns (logits [rows, steps + 1, V] at positions n - 1 ..
    n + steps - 1, and per expert layer the chosen experts [rows, the
    widest, top_k] and the scores s' of all the experts [rows, the widest,
    routed], zeros past a row's width). One program a width."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    class Full(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lm = lm

        def forward(self, tokens, at):
            choices = []
            logits = self.lm(tokens, at, choices)
            return (logits, *(a for pair in choices for a in pair))

    at = (n[:, None] - 1 + np.arange(steps + 1)[None, :]).astype(np.int32)
    full = paddle.jit.to_static(Full(), name="dots_check_full")
    logits, flat = [], None
    with paddle.no_grad():
        for r in range(len(n)):
            out, *rest = (np.asarray(a.numpy()) for a in full(
                paddle.to_tensor(ids[r:r + 1, :width[r]]),
                paddle.to_tensor(at[r:r + 1])))
            logits.append(out)
            if flat is None:
                flat = [np.zeros((len(n), ids.shape[1]) + a.shape[2:],
                                 a.dtype) for a in rest]
            for mine, a in zip(flat, rest):
                mine[r, :width[r]] = a[0]
    return np.concatenate(logits), [(flat[i], flat[i + 1])
                                    for i in range(0, len(flat), 2)]


def cached_logits(lm, ids, n, pad_to: int, steps: int):
    """The model's own cached path on the given rows: the prompt form over
    [rows, pad_to] with lengths n, then `steps` one-token steps, each fed
    the row's next given token. Returns (logits [rows, steps + 1, V], and
    per expert layer the chosen experts over positions 0 .. n + steps - 1,
    [rows, width of ids, top_k], zeros past a row's end). Two programs."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    own = len(lm.dots.layers)

    class Prompt(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lm = lm

        def forward(self, tokens, lengths):
            rows = tokens.shape[0]
            logits, out = self.lm.forward_cached(
                tokens, self.lm.init_cache(rows, pad_to + steps),
                paddle.zeros([rows], dtype="int32"), lengths)
            return (logits, *out)

    class Step(nn.Layer):
        def __init__(self):
            super().__init__()
            self.lm = lm

        def forward(self, tokens, positions, *cache):
            logits, out = self.lm.forward_cached(
                paddle.unsqueeze(tokens, 1), list(cache), positions)
            return (logits, *out)

    prompt = paddle.jit.to_static(Prompt(), name="dots_check_prompt")
    step = paddle.jit.to_static(Step(), name="dots_check_step")
    rows = np.arange(len(n))
    with paddle.no_grad():
        logits, *rest = prompt(paddle.to_tensor(ids[:, :pad_to]),
                               paddle.to_tensor(n))
        routes = [np.zeros((len(n), ids.shape[1], a.shape[2]), np.int32)
                  for a in rest[own:]]
        for a, got in zip(routes, rest[own:]):
            got = np.asarray(got.numpy())
            for r in rows:
                a[r, :n[r]] = got[r, :n[r]]
        out = [np.asarray(logits.numpy())]
        for i in range(steps):
            logits, *rest = step(paddle.to_tensor(ids[rows, n + i]),
                                 paddle.to_tensor(n + i), *rest[:own])
            for a, got in zip(routes, rest[own:]):
                a[rows, n + i] = np.asarray(got.numpy())[:, 0]
            out.append(np.asarray(logits.numpy()))
    return np.stack(out, axis=1), routes


def against_reference(lm, sizes: dict, ids, n, width, steps: int, logits,
                      routes, scores=None) -> dict:
    """One path (`logits` [rows, steps + 1, V], `routes`: per expert layer
    the chosen experts [rows, T, top_k]) against the reference ON THAT
    PATH'S CHOICES, a row at a time over the row's own width (beside the
    weights and the pool the reference may hold one mixer, a quarter of the
    dense feed-forward or one expert in float32 and one row's activations,
    no more). `scores` (per expert layer [rows, T, routed], the path's)
    gives the routing readings. Returns `err` (largest |logits - reference|
    over the largest |reference| of all compared) and `row_err` [rows] on
    the same scale, `argmax` and `margin` [rows, steps + 1] of the
    reference (its top-2 margin over that scale), and under "routing" the
    readings of `ling_serve._Tally`."""
    named = {k: p._value for k, p in lm.named_parameters()}
    at = n[:, None] - 1 + np.arange(steps + 1)[None, :]
    worst, scale, tops = np.zeros(len(n)), 0.0, []
    tally = _Tally(steps) if scores is not None else None
    for r in range(len(n)):
        row, w = slice(r, r + 1), int(width[r])
        out, routing = reference.forward(
            named, ids[row, :w], at[row], dense_layers=dense_layers(sizes),
            heads=sizes["num_attention_heads"],
            first=sizes.get("experts_held_first", 0),
            top_k=sizes["num_experts_per_tok"], n_group=sizes["n_group"],
            topk_group=sizes["topk_group"],
            scaling=float(sizes["routed_scaling_factor"]),
            nope=sizes["qk_nope_head_dim"], rope_dim=sizes["qk_rope_head_dim"],
            theta=float(sizes["rope_theta"]),
            rope_scaling=sizes["rope_scaling"],
            eps=float(sizes["rms_norm_eps"]),
            forced=[a[row, :w] for a in routes])
        out = np.asarray(out)[0]
        worst[r] = float(np.max(np.abs(logits[r] - out)))
        scale = max(scale, float(np.max(np.abs(out))))
        top2 = np.sort(out, axis=-1)[..., -2:]
        tops.append((np.argmax(out, axis=-1), top2[..., 1] - top2[..., 0]))
        if tally is not None:
            tally.add(int(n[r]), [a[r, :w] for a in routes],
                      [a[r, :w] for a in scores],
                      [{key: np.asarray(v)[0] for key, v in layer.items()}
                       for layer in routing])
    found = {"err": float(worst.max() / scale), "row_err": worst / scale,
             "argmax": np.stack([a for a, _ in tops]),
             "margin": np.stack([m for _, m in tops]) / scale}
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
    for layer in lm.dots.layers:
        if layer.ffn_kind != "moe":
            continue
        key, sub = jax.random.split(key)
        m = jax.random.normal(sub, (ROUTER_ROWS, sizes["hidden_size"]),
                              jnp.float32).astype(layer.mlp.gate_proj.dtype)
        with paddle.no_grad():
            scores = layer.mlp.choose(paddle.to_tensor(m))[2]
        with jax.default_matmul_precision("highest"):
            biased = choose(
                m.astype(jnp.float32), layer.mlp.router._value,
                layer.mlp.router_bias._value, top_k=layer.mlp.top_k,
                n_group=layer.mlp.n_group, topk_group=layer.mlp.topk_group,
                scaling=layer.mlp.scaling)[-1]
        worst = max(worst, float(np.max(np.abs(
            np.asarray(scores.numpy()) - np.asarray(biased)))))
    return worst


def latent_read_error(lm, engine, positions, widths, seed: int):
    """The decode step's read alone: see `MLA_TOL`. `positions` [slots]:
    the last position written in every slot of the pool; `widths` [slots]:
    the rows of a slot's page handed to the reference (its row's compared
    width: a few fixed values, so the reference compiles a few shapes).
    Returns the error a slot [slots]."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.nn import functional as F
    mixer = lm.dots.layers[0].mixer
    slots, heads = len(positions), mixer.num_heads
    kq, kr = jax.random.split(jax.random.key(seed % (2 ** 32)))
    dtype = mixer.kv_up.weight._value.dtype
    qn = jax.random.normal(kq, (slots, heads, mixer.nope),
                           jnp.float32).astype(dtype)
    qr = jax.random.normal(kr, (slots, heads, mixer.rope),
                           jnp.float32).astype(dtype)
    page = engine._pool[0]
    with paddle.no_grad():
        got = np.asarray(F.latent_attention_decode(
            qn, qr, page, jnp.asarray(positions, jnp.int32),
            mixer.kv_up.weight, scale=mixer.scale
        ).numpy().astype(np.float32))
    errs = np.zeros(slots)
    for s in range(slots):
        want = np.asarray(reference.latent_step(
            qn[s], qr[s], page._value[s, :int(widths[s])],
            int(positions[s]) + 1, mixer.kv_up.weight._value,
            latent=mixer.latent, scale=mixer.scale))
        errs[s] = np.max(np.abs(got[s] - want)) / np.max(np.abs(want))
    return errs


def run_checks(lm, engine, cell: dict, seed: int, say) -> dict:
    """Checks 1 to 4 on `engine`, whose scheduler is not running. Returns
    the checks, the readings, the rows as the engine completed them, the
    tokens it made and what the reference says of them (check 5 reads
    those)."""
    sizes, chk = cell["config_sizes"], cell["check"]
    steps, few, wide = chk["decode_tokens"], chk["prompts"], chk["full_rows"]
    ids, n, width = check_rows(cell, seed)
    t0 = time.perf_counter()
    served, made, routes, slot = engine_rows(engine, ids, n, steps)
    say(f"the serving engine's programs by hand, {len(n)} slots live, "
        f"{steps} decode executions in {time.perf_counter() - t0:.1f}s; "
        f"allocator peak {peak_gb():.2f} GB")
    written, rows = np.zeros(len(n), np.int32), np.zeros(len(n), np.int32)
    written[slot], rows[slot] = n + steps - 1, width
    read_err = latent_read_error(lm, engine, written, rows, seed)
    t0 = time.perf_counter()
    eng = against_reference(lm, sizes, ids, n, width, steps, served, routes)
    say(f"reference on the engine's choices: {len(n)} rows of "
        f"{sorted(set(width.tolist()))} positions in "
        f"{time.perf_counter() - t0:.1f}s; allocator peak {peak_gb():.2f} GB")
    order = _one_of_every_group(chk["rows"], wide)
    t0 = time.perf_counter()
    full, pairs = full_logits(lm, ids[order], n[order], width[order], steps)
    found = against_reference(
        lm, sizes, ids[order], n[order], width[order], steps, full,
        [experts for experts, _ in pairs],
        scores=[all_scores for _, all_scores in pairs])
    say(f"the full forward of rows {order.tolist()} and the reference on its "
        f"choices in {time.perf_counter() - t0:.1f}s; allocator peak "
        f"{peak_gb():.2f} GB")
    routing = found["routing"]
    routing["router_error"] = router_error(lm, sizes, seed)
    first = sizes.get("experts_held_first", 0)
    reached = experts_reached(routes, n, steps, first,
                              sizes["n_routed_experts"])
    last = np.arange(len(n) - few, len(n))
    got, path_routes = cached_logits(lm, ids[last], n[last], chk["pad_to"],
                                     steps)
    path = against_reference(lm, sizes, ids[last], n[last], width[last],
                             steps, got, path_routes)
    long_rows = n + steps > LONG_FROM
    long_err = float(eng["row_err"][long_rows].max()) if long_rows.any() \
        else float("nan")
    say(f"prompt lengths {n.tolist()}, {steps + 1} positions a prompt; "
        f"against the reference on a path's own choices: full forward "
        f"{found['err']:.3e}, the serving engine's programs {eng['err']:.3e} "
        f"(a row: {[round(float(e), 4) for e in eng['row_err']]}; the rows "
        f"past {LONG_FROM} positions {long_err:.3e}), the decode step's "
        f"read alone over the pool's first-layer pages, largest of "
        f"{len(n)} slots {read_err.max():.3e}, at the longest "
        f"{read_err[np.argmax(written)]:.3e} (limit {MLA_TOL}), the "
        f"model's cached path in the check's own programs, rows "
        f"{last.tolist()}, {path['err']:.3e} (tolerance {LOGIT_TOL}); "
        f"routing {routing} (every decided choice agrees, at least "
        f"{ROUTE_DECIDED_MIN} decided, at least {ROUTE_AGREE_MIN} agree, "
        f"score error under {ROUTE_SCORE_TOL}, the router alone under "
        f"{ROUTER_TOL}); held experts {len(n)} rows reach a layer: counted "
        f"{reached:.2f}, dots_cost expects "
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
        "latent_read_keeps_its_precision": bool(read_err.max() <= MLA_TOL)}
    return {"checks": checks, "ids": ids, "n": n, "made": made,
            "ref_argmax": eng["argmax"], "ref_margin": eng["margin"],
            "routing": routing,
            "readings": {"full": found["err"], "engine": eng["err"],
                         "engine_long_rows": long_err,
                         "latent_read": float(read_err.max()),
                         "cached_path": path["err"],
                         "experts_reached_a_step": reached}}


def _one_of_every_group(groups, count: int):
    """The first rows of the groups in turn until `count` are named: the
    full forward sees one row of every width before a second of any."""
    starts = np.cumsum([0] + [g["count"] for g in groups[:-1]])
    rows = [s + i for i in range(max(g["count"] for g in groups))
            for s, g in zip(starts, groups) if i < g["count"]]
    return np.asarray(rows[:count])


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
            f"{dots_cost.layers_held(sizes)}; allocator peak "
            f"{peak_gb():.2f} GB")
    engine = make_engine(lm, cell["engine"])
    found = run_checks(lm, engine, cell, ctx.seed, ctx.say)
    server = PredictorServer(lambda x: x, llm_engine=engine).start()
    ctx.say(f"engine up: {engine.stats()['slots']} slots, pool "
            f"{engine.kv_pool_bytes() / 1e9:.3f} GB of pages, buckets "
            f"{engine.buckets}, serving on {server.host}:{server.port}; "
            f"allocator peak {peak_gb():.2f} GB")
    return {"cell": cell, "ctx": ctx, "lm": lm, "engine": engine,
            "server": server, "checks": found["checks"], "found": found}
