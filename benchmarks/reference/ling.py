"""The plain reference of Ling-3.0-flash's language model (`bailing_hybrid`):
float32 `jax.numpy` at `highest` matmul precision, independent of
`paddle_tpu` (nothing of it is imported). d the hidden size, h a head,
u = RMSNorm(x); layer l is the PUBLISHED index:

    x'  = x  + Mixer_l(RMSNorm(x))        Mixer_l = MLA if (l + 1) % group == 0 else KDA
    x'' = x' + FFN_l(RMSNorm(x'))         FFN_l   = SwiGLU if l < first_k_dense else MoE
    logits = W_head RMSNorm(x_L)          untied; the vocabulary's slice held

KDA, as the plain RECURRENCE over positions (`lax.scan`; it shares neither
the chunked form nor the step kernel with the program):

    q_t, k_t, v_t = silu(conv(W_q u)_t), silu(conv(W_k u)_t), silu(conv(W_v u)_t)
    q_t^h = l2norm(q_t^h) / sqrt(dk);  k_t^h = l2norm(k_t^h)
    g_t^h = lower * sigmoid(exp(A^h) ((W_a u_t)^h + b_a^h));  beta_t^h = sigmoid((W_b u_t)^h)
    S_t^h = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}^h + beta_t k_t v_t^T
    o_t^h = (S_t^h)^T q_t^h;   y_t = W_o concat_h(sigmoid((W_g u_t)^h) RMSNorm(o_t^h))

MLA in the EXPANDED form, full keys and values a head (never the absorbed
one), rotary on the rope part only, interleaved pairs:

    [qn; qr] = (W_q u)^h;  [c; kr] = W_dkv u;  c = RMSNorm(c);  kr, qr rotated
    [kn_j^h; v_j^h] = (W_ukv c_j)^h
    a_tj = softmax_{j<=t}((qn_t^h . kn_j^h + qr_t^h . kr_j) / sqrt(nope + rope))

MoE as a LOOP over the experts held, in blocks, with a mask (never a sort
or a grouped matmul); the router over all `num_experts`:

    s = sigmoid(W_r m);  s' = s + b;  group score = its 2 largest s' summed
    keep `topk_group` groups; T = the `top_k` largest s' among their experts
    w_e = scaling * s_e / sum_{e' in T} s_e'
    FFN(m) = sum_{e in T, first <= e < first + count} w_e expert_e(m) + shared(m)

It fits beside a model that fills most of a chip: weights come in as the
model's own (bfloat16) arrays and are upcast a layer's mixer, or
`EXPERT_BLOCK` experts, at a time; scores in blocks of rows; the head on
the positions asked for only, in blocks of the vocabulary.

Weights: a flat `{parameter name: array}` dict under the names the built
model gives them; linear weights are [in, out].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 256
VOCAB_BLOCK = 16384
EXPERT_BLOCK = 8
_HIGHEST = functools.partial(jax.default_matmul_precision, "highest")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def rope(x, theta):
    """x [B, T, ..., d] at positions 0..T-1, interleaved pairs (2i, 2i+1)."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # [T, d/2]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def short_conv(x, w):
    """Causal depthwise convolution. x [B, T, C]; w [K, C], w[K - 1] on the
    current row; zeros before the sequence."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(w[j] * padded[:, j:j + t] for j in range(k))


def delta_rule(q, k, v, g, beta, state_at=None):
    """The recurrence, a position at a time. q, k, g [B, T, H, dk]; v
    [B, T, H, dv]; beta [B, T, H] -> o [B, T, H, dv]; with `state_at` [B]
    also the state S [B, H, dk, dv] as it stands after that position."""
    b, t, h, dk = k.shape
    zero = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    stop = jnp.full((b,), t - 1) if state_at is None else state_at

    def step(carry, x):
        s, kept = carry
        q, k, v, g, beta, at = x
        s = jnp.exp(g)[..., None] * s
        s = s + (beta[..., None] * k)[..., None] * (
            v - jnp.einsum("bhk,bhkv->bhv", k, s))[..., None, :]
        kept = jnp.where((at == stop)[:, None, None, None], s, kept)
        return (s, kept), jnp.einsum("bhk,bhkv->bhv", q, s)

    (_, kept), o = jax.lax.scan(
        step, (zero, zero),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
        + (jnp.arange(t),))
    o = jnp.moveaxis(o, 0, 1)
    return o if state_at is None else (o, kept)


def kda(u, w, state_at, *, heads, lower, eps):
    """Returns (y, the state after position `state_at`)."""
    b, t, _ = u.shape
    width = w["q_proj.weight"].shape[1]
    d = width // heads
    rows = jnp.concatenate([u @ w["q_proj.weight"], u @ w["k_proj.weight"],
                            u @ w["v_proj.weight"]], -1)
    y = jax.nn.silu(short_conv(rows, w["conv_weight"]))
    q, k, v = (y[..., i * width:(i + 1) * width].reshape(b, t, heads, d)
               for i in range(3))
    q, k = l2norm(q) / math.sqrt(d), l2norm(k)
    a = (u @ w["a_proj.weight"] + w["a_proj.bias"]).reshape(b, t, heads, d)
    g = lower * jax.nn.sigmoid(jnp.exp(w["a_log"])[:, None] * a)
    beta = jax.nn.sigmoid(u @ w["b_proj.weight"])
    o, state = delta_rule(q, k, v, g, beta, state_at)
    o = rms_norm(o, w["o_norm.weight"], eps)
    o = o * jax.nn.sigmoid(u @ w["g_proj.weight"])[..., None]
    return o.reshape(b, t, width) @ w["o_proj.weight"], state


def mla(u, w, *, heads, nope, rope_dim, theta, eps):
    b, t, _ = u.shape
    latent = w["kv_norm.weight"].shape[0]
    q = (u @ w["q_proj.weight"]).reshape(b, t, heads, nope + rope_dim)
    qn, qr = q[..., :nope], rope(q[..., nope:], theta)
    down = u @ w["kv_down.weight"]
    c = rms_norm(down[..., :latent], w["kv_norm.weight"], eps)
    kr = rope(down[..., latent:], theta)                      # [B, T, rope]
    kv = (c @ w["kv_up.weight"]).reshape(b, t, heads, -1)
    kn, v = kv[..., :nope], kv[..., nope:]
    cols = jnp.arange(t)
    out = []
    for r0 in range(0, t, ROW_BLOCK):
        r1 = min(r0 + ROW_BLOCK, t)
        s = (jnp.einsum("bthd,bjhd->bhtj", qn[:, r0:r1], kn)
             + jnp.einsum("bthd,bjd->bhtj", qr[:, r0:r1], kr)
             ) / math.sqrt(nope + rope_dim)
        keep = jnp.arange(r0, r1)[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhtj,bjhd->bthd", p, v))
    y = jnp.concatenate(out, axis=1).reshape(b, t, -1)
    return y @ w["o_proj.weight"]


def choose(m, router, bias, *, top_k, n_group, topk_group, scaling):
    """The router's choice for rows m [T, hidden]. Returns (experts
    [T, top_k] over all the experts, weights [T, top_k], the margin of the
    expert choice [T] (the top_k-th s' minus the next among the kept
    groups' experts) and of the group choice [T] (the last kept group's
    score minus the next; +inf where every group is kept), and s' [T, E]
    of every expert)."""
    t, e = m.shape[0], router.shape[1]
    s = jax.nn.sigmoid(m @ router)
    biased = every = s + bias
    group_margin = jnp.full((t,), jnp.inf)
    if n_group > 1:
        groups = biased.reshape(t, n_group, e // n_group)
        score = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)       # [T, G]
        ranked = jnp.sort(score, axis=-1)[:, ::-1]
        if topk_group < n_group:
            group_margin = ranked[:, topk_group - 1] - ranked[:, topk_group]
        kept = score >= ranked[:, topk_group - 1:topk_group]
        biased = jnp.where(jnp.repeat(kept, e // n_group, axis=1), biased,
                           -jnp.inf)
    order = jnp.argsort(-biased, axis=-1)
    experts = order[:, :top_k]
    ranked = jnp.take_along_axis(biased, order[:, :top_k + 1], axis=1)
    w = jnp.take_along_axis(s, experts, axis=1)
    return (experts, scaling * w / w.sum(-1, keepdims=True),
            ranked[:, top_k - 1] - ranked[:, top_k], group_margin, every)


@jax.jit
def _expert_block(m, dense_w, gate, up, down):
    """sum over this block's experts of dense_w[:, e] * expert_e(m)."""
    with _HIGHEST():
        gate, up, down = (a.astype(jnp.float32) for a in (gate, up, down))
        h = jax.nn.silu(jnp.einsum("td,edw->etw", m, gate)) \
            * jnp.einsum("td,edw->etw", m, up)
        return jnp.einsum("etd,te->td", jnp.einsum("etw,ewd->etd", h, down),
                          dense_w)


def moe(m, w, *, first, top_k, n_group, topk_group, scaling, forced=None):
    """m [T, hidden]; the experts held are first .. first + count - 1.
    Returns (y [T, hidden], experts [T, top_k], expert margin, group
    margin, s' [T, num_experts]): the reference's OWN choice and margins.
    With `forced` [T, top_k] the sum runs over those experts instead
    (weights from the reference's own s): a choice inside the margin may
    fall either way, and the sum of another expert is another number."""
    with _HIGHEST():
        m32 = m.astype(jnp.float32)
        experts, weights, margin, group_margin, biased = choose(
            m32, w["router"].astype(jnp.float32),
            w["router_bias"].astype(jnp.float32), top_k=top_k,
            n_group=n_group, topk_group=topk_group, scaling=scaling)
        count = w["gate_proj"].shape[0]
        used = experts
        if forced is not None:
            used = jnp.asarray(forced)
            s = jnp.take_along_axis(
                biased - w["router_bias"].astype(jnp.float32), used, axis=1)
            weights = scaling * s / s.sum(-1, keepdims=True)
        # [T, count]: a route's weight under its expert, zero elsewhere
        dense = jnp.zeros((m.shape[0], w["router"].shape[1]), jnp.float32).at[
            jnp.arange(m.shape[0])[:, None], used].add(weights)
        dense = dense[:, first:first + count]
        y = (jax.nn.silu(m32 @ w["shared_gate.weight"].astype(jnp.float32))
             * (m32 @ w["shared_up.weight"].astype(jnp.float32))
             ) @ w["shared_down.weight"].astype(jnp.float32)
    for e0 in range(0, count, EXPERT_BLOCK):
        e1 = min(e0 + EXPERT_BLOCK, count)
        y = y + _expert_block(m32, dense[:, e0:e1], w["gate_proj"][e0:e1],
                              w["up_proj"][e0:e1], w["down_proj"][e0:e1])
    return y, experts, margin, group_margin, biased


@functools.partial(jax.jit, static_argnames=("kind", "heads", "lower", "nope",
                                             "rope_dim", "theta", "eps"))
def _mixer(x, w_norm, w, state_at, *, kind, heads, lower, nope, rope_dim,
           theta, eps):
    """(x + mixer(norm(x)), a KDA layer's state after `state_at` or None)."""
    with _HIGHEST():
        w = {k: a.astype(jnp.float32) for k, a in w.items()}
        u = rms_norm(x, w_norm.astype(jnp.float32), eps)
        if kind == "kda":
            y, state = kda(u, w, state_at, heads=heads, lower=lower, eps=eps)
            return x + y, state
        return x + mla(u, w, heads=heads, nope=nope, rope_dim=rope_dim,
                       theta=theta, eps=eps), None


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, w_norm, w, *, eps):
    with _HIGHEST():
        w = {k: a.astype(jnp.float32) for k, a in w.items()}
        m = rms_norm(x, w_norm.astype(jnp.float32), eps)
        return x + (jax.nn.silu(m @ w["gate_proj.weight"])
                    * (m @ w["up_proj.weight"])) @ w["down_proj.weight"]


@jax.jit
def _head_block(h, w):
    with _HIGHEST():
        return h @ w.astype(jnp.float32)


def _sub(named, prefix):
    return {k[len(prefix):]: a for k, a in named.items()
            if k.startswith(prefix)}


def forward(named: dict, ids, at, *, kinds, heads: int, first: int,
            top_k: int, n_group: int, topk_group: int, scaling: float,
            nope: int, rope_dim: int, theta: float, eps: float,
            lower: float, prefix: str = "ling", forced=None, state_at=None):
    """Full forward of tokens `ids` [B, T] through the layers held, whose
    kinds are `kinds` [(mixer, ffn)]. Returns (logits [B, P, V] at the
    positions `at` [B, P] only, and per expert layer a dict of `experts`
    [B, T, top_k], `margin` [B, T], `group_margin` [B, T] and `biased`
    [B, T, num_experts], the scores s' the choice was made by). `forced`:
    per expert layer the experts [B, T, top_k] to sum over in place of the
    reference's own (see `moe`); what is returned per layer is still the
    reference's own choice, on the state the forced sums left. With
    `state_at` [B] a third result: every KDA layer's recurrent state
    [B, H, dk, dv] as it stands after that position."""
    ids = jnp.asarray(ids)
    b, t = ids.shape
    x = jnp.asarray(named[prefix + ".embed_tokens.weight"]
                    )[ids].astype(jnp.float32)
    routing, states = [], []
    stop = jnp.full((b,), t - 1) if state_at is None else jnp.asarray(state_at)
    for i, (mixer, ffn) in enumerate(kinds):
        w = _sub(named, f"{prefix}.layers.{i}.")
        x, state = _mixer(x, w["input_norm.weight"], _sub(w, "mixer."), stop,
                          kind=mixer, heads=heads, lower=float(lower),
                          nope=nope, rope_dim=rope_dim, theta=float(theta),
                          eps=float(eps))
        if state is not None:
            states.append(state)
        if ffn == "dense":
            x = _dense_ffn(x, w["post_norm.weight"], _sub(w, "mlp."),
                           eps=float(eps))
            continue
        with _HIGHEST():
            m = rms_norm(x, w["post_norm.weight"].astype(jnp.float32), eps)
        y, experts, margin, group_margin, biased = moe(
            m.reshape(b * t, -1), _sub(w, "mlp."), first=first, top_k=top_k,
            n_group=n_group, topk_group=topk_group, scaling=scaling,
            forced=None if forced is None else jnp.asarray(
                forced[len(routing)]).reshape(b * t, top_k))
        x = x + y.reshape(b, t, -1)
        routing.append({"experts": experts.reshape(b, t, top_k),
                        "margin": margin.reshape(b, t),
                        "group_margin": group_margin.reshape(b, t),
                        "biased": biased.reshape(b, t, -1)})
    h = jnp.take_along_axis(x, jnp.asarray(at)[..., None], axis=1)
    with _HIGHEST():
        h = rms_norm(h, jnp.asarray(named[prefix + ".norm.weight"],
                                    jnp.float32), eps)
    head = named["lm_head.weight"]
    logits = jnp.concatenate(
        [_head_block(h, head[:, v0:v0 + VOCAB_BLOCK])
         for v0 in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
    return (logits, routing) if state_at is None else (logits, routing,
                                                       states)
