"""Runner `ling_serve`: `LingForCausalLM` (KDA layers with a float32
recurrent state and a convolution's rows, a latent-attention layer with a
page, routed experts of which this chip holds a stated share; bfloat16
weights) in `LLMEngine` behind `PredictorServer`. The load, the client-side
numbers, the measured window and the shutdown are `runners/llm_serve.py`'s,
imported: one scheduler, one wire, one generator serve every model. What is
this runner's own is the model it builds and what decides `correct`.

Cell file keys read here: those of `llm_serve` (`generator`, `mix`,
`engine`, `check.prompts`, `check.pad_to`) and `check.decode_tokens`.

`correct` (beside `llm_serve`'s "every finished stream has exactly the
tokens asked" and the harness's "nothing compiled in the window"), all
against `reference/ling.py`: float32, `highest` precision, KDA as the plain
recurrence, latent attention expanded, the experts as a loop with a mask,
given the same share of the experts and of the vocabulary. The check's rows
are seeded, ONE A SLOT of the engine: a prompt of n tokens, then the
`check.decode_tokens` tokens that THE ENGINE THAT SERVES THE WINDOW makes of
it, greedy, driven by hand before its scheduler starts; logits are compared
at position n - 1 and at each of the 16 that follow. The same 16 tokens
then have to come over the wire.

**Routing is a discrete choice, and bfloat16 activations flip it.** Of 256
eligible experts the 8th and the 9th score lie about 0.01 apart and the
program's scores differ from the reference's by some 3e-3 (the activations'
rounding, not the router's), so a fifth of the choices fall the other way;
the expert that comes in has other weights, that token's logits move by
tens of percent, and the next expert layers see another token. Two PROGRAM
paths differ from each other as often as either does from the reference
(measured on the chip: PERF.md, PR 33). Neither side is wrong there. So the
comparison is split: THE CHOICE is held to the reference's wherever the
reference is decided (2, below), and THE SUMS of every path are held to the
reference's ON THAT PATH'S OWN CHOICES: the reference takes a path's chosen
experts as given (`forced`: the weights still come from its own scores)
and reports its own choice beside them. Every cached-path program of the
model reports the experts it chose after its cache
(`LingForCausalLM.forward_cached`), the engine's two programs included, so
the engine that is timed is the engine that is checked.

1. `logits_match_reference`: the model's full forward, every row, every
   compared position, largest error;
2. `routing_matches_reference`, `routing_is_decided_often`,
   `routing_agreement_holds`, `router_keeps_its_precision`: the full
   forward's choice at every real position of every row and expert layer
   against the reference's own choice on the same state. The full forward
   returns the scores s' of ALL the experts, so the score error is known A
   POSITION: e = max over the experts |s' program - s' reference|. A flip
   needs two scores to move by the margin between them, so wherever the
   reference's margin (its 8th s' over its 9th) exceeds 2 e and its group
   margin (its 4th group over its 5th; a group's score sums two scores)
   exceeds 4 e, the chosen SET has to equal the reference's: every such
   choice is held to it, such choices have to be a stated share of all,
   and so has the share of all choices that agree. What reaches a router
   in the model carries the activations' error, which is larger than a
   bfloat16 router's own, so the router is also held to its precision
   ALONE: every expert layer's `choose` on seeded rows at the published
   width against the reference's scores on the SAME rows. Also counts the
   held experts that the rows of a decode step reach, beside `ling_cost`'s
   expectation;
3. `cached_path_matches_reference`: the first `check.prompts` rows through
   the model's cached path in programs of the check's own (the prompt form
   to length n, padding masked, then 16 one-token steps, teacher-forced).
   The diagnostic that tells a fault of the model from one of the engine;
4. `engine_matches_reference`, `engine_state_keeps_its_precision`: every
   row through THE SERVING ENGINE's `jit_llm_prefill` at its bucket and
   the slot write into a slot of its own, then 16 executions of its
   `jit_llm_decode` over the whole pool, all slots live, each slot fed the
   token the step before made (`engine_rows`): the logits and the choices
   read off the programs' own outputs, largest error on any slot. What a
   state held below float32 does is lose a little at every token, so the
   first KDA layer's state is read out of the pool after the last step and
   held to the reference's (`STATE_TOL`). The controls are in
   `benchmarks/ling_precision_control.py`;
5. `streamed_tokens_are_the_engines_own`,
   `streamed_tokens_are_reference_argmax`: over the wire, every row
   submitted AT ONCE: each stream's 16 tokens are the 16 that check 4's
   programs made of that prompt (the same two programs on the same rows:
   every one, no tolerance), and those are the reference's arg-max at
   every position where its top-2 margin exceeds twice the tolerance.

An error is max|model - reference| over the vocabulary at one row and
position, over max|reference| of all compared logits.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import ling_cost
from ..reference import ling as reference
from .brumby_serve import check_rows, peak_gb  # shared with the other state model
from .llm_serve import (  # noqa: F401  (the runner's interface)
    _generate, client_numbers, close, measure, offer,
)

# A path against the reference on that path's choices, largest error over
# every row and position. Weights and activations are bfloat16 (2^-9
# relative a rounding), sums float32; each of 7 pre-norm blocks rounds its
# projections, the mixer's output, the expert activations and the routed
# sum onto the residual stream: some 60 independent roundings at 2e-3 come
# to ~1.5e-2 of a logit's own size and ~1e-2 of the largest logit, as in
# `brumby_serve` for one row. It is the LARGEST of 48 x 17 positions, and
# the routed sum is scaled by 2.5: the chip reads 2.4e-2 to 3.4e-2 (PERF.md,
# PR 33). A choice that is not the reference's reads 0.2 to 0.4 (the same
# runs, against the reference on its own choices); a lost shared expert or
# gate more. The limit lies between, nearer the readings.
LOGIT_TOL = 5e-2
# The routing check (2). A reference choice is DECIDED at a position when
# its expert margin exceeds this many score errors OF THAT POSITION (the
# largest |s' program - s' reference| over all the experts there), and its
# group margin twice as many (a group's score is the sum of two scores).
# Two scores that each move by at most e cannot swap across a gap over
# 2 e, so a decided choice that differs is a fault of the choice itself
# (the groups, the bias, the top-k), whatever the activations' error.
ROUTE_MARGIN_ERRORS = 2.0
# What keeps the rule above from holding vacuously: the share of all (row,
# position, layer) choices that are decided, the share whose set equals the
# reference's own, and the largest score error of ANY expert anywhere (of
# some 48 million scores). On the chip (PERF.md, PR 33): decided 1.4e-3 to
# 2.1e-3 of ~95k choices (the margin has to exceed twice the largest error
# of 512, the group margin four times), every one agreeing; agreeing share
# 0.785 to 0.811 (24 seeds); largest error 5.2e-2 and 6.2e-2. A router fed
# other inputs than the reference's (the control of `benchmarks/tests`: its
# rows shifted by a position) errs by tenths at every position, decides
# nothing and agrees nowhere. Limits between, a third of the way at most;
# they are not precision limits: the router's is `ROUTER_TOL`.
ROUTE_DECIDED_MIN = 5e-4
ROUTE_AGREE_MIN = 0.6
ROUTE_SCORE_TOL = 0.15
# Every expert layer's `choose` on `ROUTER_ROWS` seeded rows against the
# reference's on the same rows: the largest |s' program - s' reference| of
# any expert. Float32 at `highest` precision differs from the reference by
# summation order alone (chip: 0 to 1e-6); a product of bfloat16 operands
# rounded to bfloat16 reads 1e-3 to 4e-3 (the control). The limit is the
# geometric middle.
ROUTER_ROWS = 256
ROUTER_TOL = 1e-4
# The FIRST KDA layer's recurrent state in the pool after the 16 steps
# against the reference's, rms over rms, every slot (check 4). Its input is
# the normed embedding, so nothing upstream blurs it and no choice reaches
# it: what is left is the bfloat16 rounding of its projections, and
# whatever the state loses where it is held. On the chip the state in
# float32 reads 4.0e-3 to 4.2e-3 (21 seeds) and held in bfloat16 (the
# control) 7.6e-3 (PERF.md, PR 33); the limit is their geometric middle,
# ten spreads of the float32 reading above it. (The growth of the logits'
# error over the steps, `brumby_serve`'s measure, read 0.99 against 1.05:
# the delta rule corrects what it holds, and that is too little room to
# decide on. Deeper layers' states read 1e-2 to 4e-2 either way: the
# upstream activations' error covers the state's own.)
STATE_TOL = 5.6e-3
# The rows and tokens a row that `balance_router_bias` reads, in chunks of
# `BALANCE_CHUNK` rows, and how often it corrects an expert's share: on 16k
# tokens an expert's load is a count of 256, and fresh tokens then spread
# the load by a fifth of its mean (1.5 before), the eight groups within 5%
# (CPU, published widths).
BALANCE_ROWS = 32
BALANCE_CHUNK = 8
BALANCE_TOKENS = 512
BALANCE_ROUNDS = 2


def kinds(sizes: dict):
    from paddle_tpu.models.ling import layer_kinds
    return layer_kinds(ling_cost.layers_held(sizes), sizes["layer_group_size"],
                       sizes["first_k_dense_replace"])


def build_model(sizes: dict, seed: int):
    """The configuration as the program builds it: parameters created in
    the configuration's dtype, weights from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.models.ling import LingForCausalLM, LingModel
    paddle.seed(seed)
    lm = LingForCausalLM(LingModel(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes.get("num_hidden_layers_published",
                                    sizes["num_hidden_layers"]),
        layers=ling_cost.layers_held(sizes),
        num_attention_heads=sizes["num_attention_heads"],
        head_dim=sizes["head_dim"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_experts=sizes.get("num_experts_published", sizes["num_experts"]),
        num_experts_per_tok=sizes["num_experts_per_tok"],
        n_group=sizes["n_group"], topk_group=sizes["topk_group"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        moe_shared_expert_intermediate_size=sizes[
            "moe_shared_expert_intermediate_size"],
        held=(sizes.get("experts_held_first", 0), sizes["num_experts"]),
        first_k_dense_replace=sizes["first_k_dense_replace"],
        layer_group_size=sizes["layer_group_size"],
        kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"], rope_theta=sizes["rope_theta"],
        rms_norm_eps=sizes["rms_norm_eps"],
        short_conv_kernel_size=sizes["short_conv_kernel_size"],
        kda_lower_bound=sizes["kda_lower_bound"],
        kda_decay_bias=sizes["kda_decay_bias"],
        router_bias_std=sizes["router_bias_std"],
        initializer_range=sizes["initializer_range"],
        dtype=sizes.get("torch_dtype", "float32")))
    lm.eval()
    balance_router_bias(lm, sizes, seed)
    return lm


def _balanced_bias(logits, bias, mlp):
    """The bias under which the router picks every expert about equally
    often on these tokens. An expert's bias brings the score it exceeds on
    its SHARE of the tokens to the experts' mean of that score; the share
    starts at top_k / num_experts and is corrected `BALANCE_ROUNDS` times
    by what the choice (groups and all) really gave it."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.layer.routed_experts import route
    tokens, experts = logits.shape
    ranked = jnp.sort(jax.nn.sigmoid(logits), axis=0)          # ascending
    share = jnp.full((experts,), mlp.top_k / experts)
    for _ in range(BALANCE_ROUNDS + 1):
        at = jnp.clip(((1.0 - share) * tokens).astype(jnp.int32), 0,
                      tokens - 1)
        level = ranked[at, jnp.arange(experts)]
        new = bias + jnp.mean(level) - level
        chosen = route(logits, new, mlp.top_k, mlp.n_group, mlp.topk_group,
                       mlp.scaling)[0]
        load = jnp.zeros((experts,)).at[chosen.reshape(-1)].add(1.0)
        fair = tokens * mlp.top_k / experts
        share = jnp.clip(share * fair / jnp.maximum(load, 0.25 * fair),
                         0.25 * mlp.top_k / experts, 0.25)
    return new


def balance_router_bias(lm, sizes: dict, seed: int) -> None:
    """Set every router's bias as auxiliary-loss-free balancing leaves it
    in a trained model: every expert picked about equally often. Random
    weights do not spread their rows evenly (hidden states share a large
    common part, so some experts' scores stand above the rest on every
    token: with b ~ N(0, 0.01) alone a step's 48 rows reached 49 to 56 of
    the 128 held experts a layer, by the seed, where an even router reaches
    68, and `itl_p50_ms` followed it: PERF.md, PR 33). One pass over
    `BALANCE_ROWS` seeded rows, a layer at a time, through the layer's own
    parts (norm, mixer, norm, feed-forward: `LingLayer.forward_cached`'s
    two lines, stopped between them where the router reads); the layers
    after it see the balanced layer's output. The N(0, 0.01) the bias was
    drawn with stays under it."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    rng = np.random.default_rng(seed + 3)
    ids = rng.integers(0, sizes["vocab_size"],
                       (BALANCE_ROWS, BALANCE_TOKENS)).astype(np.int32)
    with paddle.no_grad():
        xs = [lm.ling.embed_tokens(paddle.to_tensor(ids[r:r + BALANCE_CHUNK]))
              for r in range(0, BALANCE_ROWS, BALANCE_CHUNK)]
        for layer in lm.ling.layers:
            # a prompt from an empty cache, every row whole: no state and
            # no rows (KDA), no page and positions from 0 (MLA), no lengths
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


def full_logits(lm, ids, n, steps: int, batch: int):
    """The model's full forward of the check rows (prompt and the tokens
    that follow, padded), `batch` rows a call. Returns (logits [rows,
    steps + 1, V] at positions n - 1 .. n + steps - 1, and per expert
    layer the chosen experts [rows, T, top_k] and the scores s' of all the
    experts [rows, T, num_experts]). One program."""
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
    full = paddle.jit.to_static(Full(), name="ling_check_full")
    outs = []
    with paddle.no_grad():
        for r in range(0, len(n), batch):
            outs.append([np.asarray(a.numpy()) for a in full(
                paddle.to_tensor(ids[r:r + batch]),
                paddle.to_tensor(at[r:r + batch]))])
    logits, *flat = (np.concatenate(parts) for parts in zip(*outs))
    return logits, [(flat[i], flat[i + 1]) for i in range(0, len(flat), 2)]


def cached_logits(lm, ids, n, pad_to: int, steps: int):
    """The model's own cached path on the check rows: the prompt form over
    [rows, pad_to] with lengths n, then `steps` one-token steps, each fed
    the row's next given token. Returns (logits [rows, steps + 1, V] at
    positions n - 1 .. n + steps - 1, and per expert layer the chosen
    experts over positions 0 .. n + steps - 1 of a row, [rows, pad_to +
    steps, top_k], zeros past a row's end). Two programs."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    own = len(lm.cache_tag)

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

    prompt = paddle.jit.to_static(Prompt(), name="ling_check_prompt")
    step = paddle.jit.to_static(Step(), name="ling_check_step")
    rows = np.arange(len(n))
    with paddle.no_grad():
        logits, *rest = prompt(paddle.to_tensor(ids[:, :pad_to]),
                               paddle.to_tensor(n))
        routes = [np.zeros((len(n), pad_to + steps, a.shape[2]), np.int32)
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


def engine_rows(engine, ids, n, steps: int, follow: bool = True, hold=None):
    """The check rows through the engine's own programs, as an admission
    and a decode step drive them (the scheduler is not running): row r
    through `jit_llm_prefill` at its bucket and the slot write into a slot
    of its own (rows and slots permuted against each other), then `steps`
    executions of `jit_llm_decode` over the whole pool, every slot live.
    With `follow` each slot is fed the token the call before made (the
    program's own greedy `outs[0]`), which is written into `ids` [rows,
    >= max n + steps] after the prompt; without it the tokens `ids` holds
    there. `hold` (a control's: pool -> pool) is applied to the pool after
    every call. Returns (the programs' logits [rows, steps + 1, V] at
    positions n - 1 .. n + steps - 1, the steps + 1 greedy tokens [rows,
    steps + 1], per expert layer the chosen experts [rows, width of ids,
    top_k] as the programs report them, zeros past a row's end, and the
    slot of every row)."""
    import paddle_tpu as paddle
    rows = np.arange(len(n))
    slots, own = engine.config.num_slots, len(engine._pool)
    slot = np.random.default_rng(len(n)).permutation(slots)[:len(n)]
    keep = (lambda: None) if hold is None else (
        lambda: setattr(engine, "_pool", hold(engine._pool)))
    logits, made, routes = [], [], None
    with paddle.no_grad():
        first, toks0 = [], []
        for r in rows:
            tok, _, last, chose = engine._prefill_slot(ids[r, :n[r]],
                                                       int(slot[r]))
            keep()
            first.append(np.asarray(last.numpy()))
            toks0.append(tok)
            if routes is None:
                routes = [np.zeros((len(n), ids.shape[1], a.shape[2]),
                                   np.int32) for a in chose]
            for a, got in zip(routes, chose):
                a[r, :n[r]] = np.asarray(got.numpy())[0, :n[r]]
            if follow:
                ids[r, n[r]] = tok
        logits.append(np.concatenate(first))
        made.append(np.asarray(toks0, np.int32))
        toks, pos = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        for i in range(steps):
            toks[slot], pos[slot] = ids[rows, n + i], n + i
            outs, donated = engine._decode_pool(toks, pos)
            if not donated:
                raise RuntimeError("the decode program did not take the "
                                   "pool donated")
            keep()
            for a, got in zip(routes, outs[2 + own:]):
                a[rows, n + i] = np.asarray(got.numpy())[slot, 0]
            made.append(np.asarray(outs[0].numpy())[slot])
            if follow and i + 1 < steps:
                ids[rows, n + i + 1] = made[-1]
            logits.append(np.asarray(outs[1].numpy())[slot])
    return np.stack(logits, axis=1), np.stack(made, axis=1), routes, slot


def pool_states(engine, slot):
    """The float32 recurrent states as the pool holds them, the check's
    rows in their order: [rows, H, d, d] a KDA layer."""
    return [np.asarray(t.numpy())[slot] for t in engine._pool
            if len(t.shape) == 4]


def against_reference(lm, sizes: dict, ids, n, steps: int, paths: dict,
                      scores=None, states=None) -> dict:
    """Every path of `paths` ({name: (logits [rows, steps + 1, V], per
    expert layer the chosen experts [rows, T, top_k])}) against the
    reference ON THAT PATH'S CHOICES, a row at a time and all paths of a
    row in ONE reference call (the batch: the same tokens, each path's
    choices forced; beside the weights and the pool the reference may hold
    one mixer or 8 experts in float32 and these rows' activations, no
    more). `scores` (per expert layer [rows, T, num_experts], the first
    path's) gives the routing readings; `states` ({path: per KDA layer
    [rows, H, d, d]}) the state errors. Returns per path `err` (largest
    |logits - reference| over the largest |reference| of all compared),
    `argmax` and `margin` [rows, steps + 1] of the reference (its top-2
    margin over that scale), `state_error` a KDA layer, and under
    "routing" the readings of `_Tally`."""
    named = {k: p._value for k, p in lm.named_parameters()}
    names = list(paths)
    at = n[:, None] - 1 + np.arange(steps + 1)[None, :]
    worst = {k: 0.0 for k in names}
    scale = {k: 0.0 for k in names}
    tops = {k: [] for k in names}
    sq = {k: None for k in states or {}}
    tally = _Tally(steps) if scores is not None else None
    for r in range(len(n)):
        row = slice(r, r + 1)
        out, routing, kept = reference.forward(
            named, np.repeat(ids[row], len(names), 0),
            np.repeat(at[row], len(names), 0), kinds=kinds(sizes),
            heads=sizes["num_attention_heads"],
            first=sizes.get("experts_held_first", 0),
            top_k=sizes["num_experts_per_tok"], n_group=sizes["n_group"],
            topk_group=sizes["topk_group"],
            scaling=float(sizes["routed_scaling_factor"]),
            nope=sizes["qk_nope_head_dim"], rope_dim=sizes["qk_rope_head_dim"],
            theta=float(sizes["rope_theta"]), eps=float(sizes["rms_norm_eps"]),
            lower=float(sizes["kda_lower_bound"]),
            forced=[np.concatenate([paths[k][1][layer][row] for k in names])
                    for layer in range(len(paths[names[0]][1]))],
            state_at=np.repeat(at[row, -1], len(names)))
        out = np.asarray(out)
        for i, k in enumerate(names):
            worst[k] = max(worst[k], float(np.max(np.abs(
                paths[k][0][r] - out[i]))))
            scale[k] = max(scale[k], float(np.max(np.abs(out[i]))))
            top2 = np.sort(out[i], axis=-1)[..., -2:]
            tops[k].append((np.argmax(out[i], axis=-1),
                            top2[..., 1] - top2[..., 0]))
            if k in sq:
                mine = [a[r] for a in states[k]]
                ref = [np.asarray(a)[i] for a in kept]
                part = np.array([[np.sum(np.square(a - b, dtype=np.float64)),
                                  np.sum(np.square(b, dtype=np.float64))]
                                 for a, b in zip(mine, ref)])
                sq[k] = part if sq[k] is None else sq[k] + part
        if tally is not None:
            tally.add(int(n[r]), [a[r] for a in paths[names[0]][1]],
                      [a[r] for a in scores],
                      [{key: np.asarray(v)[0] for key, v in layer.items()}
                       for layer in routing])
    found = {k: {"err": worst[k] / scale[k],
                 "argmax": np.stack([a for a, _ in tops[k]]),
                 "margin": np.stack([m for _, m in tops[k]]) / scale[k]}
             for k in names}
    for k, part in sq.items():
        found[k]["state_error"] = [float(np.sqrt(a / b)) for a, b in part]
    if tally is not None:
        found["routing"] = tally.readings()
    return found


class _Tally:
    """The full forward's choices against the reference's own over every
    real position of the rows, a row at a time: see `ROUTE_MARGIN_ERRORS`."""

    def __init__(self, steps: int):
        self.steps = steps
        self.score_err = 0.0
        self.same = self.decided = self.decided_same = self.total = 0

    def add(self, n: int, experts, scores, ref) -> None:
        end = n + self.steps
        for mine, got, want in zip(experts, scores, ref):
            err = np.max(np.abs(got[:end] - want["biased"][:end]), axis=-1)
            agree = np.all(np.sort(mine[:end], -1)
                           == np.sort(want["experts"][:end], -1), axis=-1)
            sure = ((want["margin"][:end] > ROUTE_MARGIN_ERRORS * err)
                    & (want["group_margin"][:end]
                       > 2 * ROUTE_MARGIN_ERRORS * err))
            self.score_err = max(self.score_err, float(err.max()))
            self.same += int(agree.sum())
            self.total += agree.size
            self.decided += int(sure.sum())
            self.decided_same += int((sure & agree).sum())

    def readings(self) -> dict:
        total = max(self.total, 1)
        return {"score_error": self.score_err, "choices": self.total,
                "agree_share": self.same / total,
                "decided_share": self.decided / total,
                "decided": self.decided, "decided_agree": self.decided_same}


def experts_reached(routes, n, steps: int, first: int, count: int) -> float:
    """The held experts that the rows of one decode step reach, a layer:
    the mean over the steps and the expert layers."""
    rows = np.arange(len(n))
    return float(np.mean([
        len({int(e) for e in experts[rows, n + i].ravel()
             if first <= e < first + count})
        for experts in routes for i in range(steps)]))


def router_error(lm, sizes: dict, seed: int) -> float:
    """The router alone: see `ROUTER_TOL`."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    worst = 0.0
    key = jax.random.key(seed % (2 ** 32))
    for layer in lm.ling.layers:
        if layer.ffn_kind != "moe":
            continue
        key, sub = jax.random.split(key)
        m = jax.random.normal(sub, (ROUTER_ROWS, sizes["hidden_size"]),
                              jnp.float32).astype(layer.mlp.gate_proj.dtype)
        with paddle.no_grad():
            scores = layer.mlp.choose(paddle.to_tensor(m))[2]
        with jax.default_matmul_precision("highest"):
            biased = reference.choose(
                m.astype(jnp.float32), layer.mlp.router._value,
                layer.mlp.router_bias._value, top_k=layer.mlp.top_k,
                n_group=layer.mlp.n_group, topk_group=layer.mlp.topk_group,
                scaling=layer.mlp.scaling)[-1]
        worst = max(worst, float(np.max(np.abs(
            np.asarray(scores.numpy()) - np.asarray(biased)))))
    return worst


def run_checks(lm, engine, cell: dict, seed: int, say, hold=None) -> dict:
    """Checks 1 to 4 on `engine`, whose scheduler is not running. Returns
    the checks, the readings, the rows as the engine completed them, the
    tokens it made and what the reference says of them (check 5 reads
    those)."""
    sizes, chk = cell["config_sizes"], cell["check"]
    steps, pad_to, few = chk["decode_tokens"], chk["pad_to"], chk["prompts"]
    ids, n = check_rows(cell, seed)
    t0 = time.perf_counter()
    served, made, routes, slot = engine_rows(engine, ids, n, steps,
                                             hold=hold)
    states = pool_states(engine, slot)
    say(f"the serving engine's programs by hand, {len(n)} slots live, "
        f"{steps} decode executions in {time.perf_counter() - t0:.1f}s; "
        f"allocator peak {peak_gb():.2f} GB")
    full, pairs = full_logits(lm, ids, n, steps, few)
    t0 = time.perf_counter()
    found = against_reference(
        lm, sizes, ids, n, steps,
        {"full": (full, [experts for experts, _ in pairs]),
         "engine": (served, routes)},
        scores=[all_scores for _, all_scores in pairs],
        states={"engine": states})
    say(f"reference: {len(n)} rows of {ids.shape[1]} positions, two paths a "
        f"row, in {time.perf_counter() - t0:.1f}s; allocator peak "
        f"{peak_gb():.2f} GB")
    routing = found["routing"]
    routing["router_error"] = router_error(lm, sizes, seed)
    first = sizes.get("experts_held_first", 0)
    reached = experts_reached(routes, n, steps, first, sizes["num_experts"])
    differ = float(np.mean([
        np.any(np.sort(mine[r, :n[r] + steps], -1)
               != np.sort(theirs[r, :n[r] + steps], -1), axis=-1).mean()
        for mine, (theirs, _) in zip(routes, pairs) for r in range(len(n))]))
    got, path_routes = cached_logits(lm, ids[:few], n[:few], pad_to, steps)
    path = against_reference(lm, sizes, ids[:few], n[:few], steps,
                             {"path": (got, path_routes)})["path"]
    eng = found["engine"]
    say(f"prompt lengths {n.tolist()}, {steps + 1} positions a prompt; "
        f"against the reference on a path's own choices: full forward "
        f"{found['full']['err']:.3e}, the serving engine's programs "
        f"{eng['err']:.3e} ({differ:.4f} of their choices differ from the "
        f"full forward's), the model's cached path in the check's own "
        f"programs, first {few} rows, {path['err']:.3e} (tolerance "
        f"{LOGIT_TOL}); routing {routing} (every decided choice agrees, at "
        f"least {ROUTE_DECIDED_MIN} decided, at least {ROUTE_AGREE_MIN} "
        f"agree, score error under {ROUTE_SCORE_TOL}, the router alone "
        f"under {ROUTER_TOL}); the KDA states in the pool against the "
        f"reference's, a layer, {[round(e, 5) for e in eng['state_error']]} "
        f"(the first under {STATE_TOL}); held experts {len(n)} rows reach a "
        f"layer: counted {reached:.2f}, ling_cost expects "
        f"{ling_cost.experts_reached(sizes, len(n)):.2f}; allocator peak "
        f"{peak_gb():.2f} GB")
    checks = {
        "logits_match_reference": bool(found["full"]["err"] <= LOGIT_TOL),
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
            eng["state_error"][0] <= STATE_TOL)}
    return {"checks": checks, "ids": ids, "n": n, "made": made,
            "ref_argmax": eng["argmax"], "ref_margin": eng["margin"],
            "routing": routing,
            "readings": {"full": found["full"]["err"], "engine": eng["err"],
                         "cached_path": path["err"],
                         "state_error": eng["state_error"],
                         "experts_reached_a_step": reached}}


def make_engine(lm, eng_cfg: dict):
    from paddle_tpu.serving import LLMConfig, LLMEngine
    return LLMEngine(lm, LLMConfig(
        num_slots=eng_cfg["num_slots"], max_len=eng_cfg["max_len"],
        prefill_buckets=tuple(eng_cfg["prefill_buckets"]),
        queue_depth=eng_cfg.get("queue_depth", 256), warmup_on_start=False))


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


def warm(state) -> None:
    """The warm wave over every bucket the mix reaches (as `llm_serve.warm`
    sends it), then check 5 over the wire."""
    from paddle_tpu.inference.server import STATUS_OK
    ctx, cell, engine = state["ctx"], state["cell"], state["engine"]
    sizes, chk = cell["config_sizes"], cell["check"]
    spec = cell["mix"]["prompt_tokens"]
    reach = sorted({next(b for b in engine.buckets if b >= m)
                    for m in (spec["min"], spec["max"])}
                   | {b for b in engine.buckets
                      if spec["min"] <= b <= spec["max"]})
    rng = np.random.default_rng(ctx.seed + 2)
    for b in reach:
        t0 = time.perf_counter()
        m = min(b, spec["max"])
        status, toks = _generate(state, rng.integers(
            0, sizes["vocab_size"], m).astype(np.int32), 3)
        if status != STATUS_OK or len(toks) != 3:
            raise RuntimeError(f"warm wave: bucket {b} came back "
                               f"status={status} with {toks!r}")
        ctx.say(f"warm: bucket {b} ({m} tokens) in "
                f"{time.perf_counter() - t0:.2f}s; allocator peak "
                f"{peak_gb():.2f} GB")

    steps, found = chk["decode_tokens"], state.pop("found")
    ids, n = found["ids"], found["n"]
    # every row at once: all slots live while each stream is produced
    with ThreadPoolExecutor(len(n)) as pool:
        came = list(pool.map(
            lambda r: _generate(state, ids[r, :n[r]], steps), range(len(n))))
    for r, (status, toks) in enumerate(came):
        if status != STATUS_OK or len(toks) != steps:
            raise RuntimeError(f"check 5: row {r} came back status={status} "
                               f"with {len(toks)} tokens")
    streamed = np.asarray([toks for _, toks in came])
    # token i was produced from the prefix that ends at position n - 1 + i
    own = streamed == found["made"][:, :steps]
    decided = found["ref_margin"][:, :steps] > 2 * LOGIT_TOL
    agree = found["ref_argmax"][:, :steps] == found["made"][:, :steps]
    ctx.say(f"streamed tokens, {len(n)} streams at once: {int(own.sum())} "
            f"of {own.size} are the tokens the engine's programs made by "
            f"hand (rows that differ: {np.flatnonzero(~own.all(1)).tolist()}"
            f"); of those tokens {int(agree.sum())} are the reference's "
            f"arg-max, {int(decided.sum())} have a top-2 margin over "
            f"{2 * LOGIT_TOL}, of which {int((agree & decided).sum())} "
            f"agree; allocator peak {peak_gb():.2f} GB")
    state["checks"]["streamed_tokens_are_the_engines_own"] = bool(own.all())
    state["checks"]["streamed_tokens_are_reference_argmax"] = bool(
        own.all() and np.all(agree | ~decided))
