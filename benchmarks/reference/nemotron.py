"""The plain reference of Nemotron-H (`nemotron_h`), as Nemotron 3 Nano
has it: float32 `jax.numpy` at `highest` matmul precision, independent of
`paddle_tpu` (nothing of it is imported). Every block is one part, u =
RMSNorm(x); layer l is the PUBLISHED index and its kind is
`hybrid_override_pattern[l]`:

    x' = x + Part_l(RMSNorm(x))             M: Mamba-2, E: MoE, *: attention
    logits = W_head RMSNorm(x_L)            untied; the vocabulary's slice held

Mamba-2, the scan as the plain RECURRENCE over positions (`lax.scan`; it
shares neither the chunked form nor the step kernel with the program):

    [z | xBC | dt] = W_in u;  xBC = silu(conv4(xBC) + b_conv);  x, B, C = xBC
    Delta = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t^h = exp(Delta_t^h A^h) S_{t-1}^h + Delta_t^h x_t^h (B_t^g)^T
    (g = h // (H / G), the group head h reads)
    y_t^h = S_t^h C_t^g + D^h x_t^h
    out = W_out (w * RMSNorm over each of G groups (y * silu(z)))

Attention, grouped query heads over fewer K/V heads (each K/V head
repeated for its group), causal, 1 / sqrt(head_dim), no rotary position.

MoE as a LOOP over the experts held, in blocks, with a mask (never a sort
or a grouped matmul); the router (`reference.ling.choose`: sigmoid scores,
a bias for the choice, top k, weights from the unbiased scores normalised
and scaled) over all the routed experts:

    FFN(m) = sum_{e in T, first <= e < first + count} w_e W_d,e relu(W_u,e m)^2
             + W_d,sh relu(W_u,sh m)^2

Departure, also the program's: the gated norm's output is not rounded
to the input's dtype before its weight (float32 throughout here).

Weights come in as the model's own (bfloat16) arrays and are upcast a
block, or `EXPERT_BLOCK` experts, at a time; attention's scores in blocks
of rows; the head on the positions asked for only, in blocks of the
vocabulary. Weights: a flat `{parameter name: array}` dict under the names
the built model gives them; linear weights are [in, out].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .ling import _HIGHEST, _head_block, _sub, choose, rms_norm, short_conv

ROW_BLOCK = 256
VOCAB_BLOCK = 16384
EXPERT_BLOCK = 8


def scan(x, delta, a, b, c, state_at):
    """The recurrence, a position at a time. x [B, T, H, P]; delta [B, T,
    H]; a [H]; b, c [B, T, G, N]. Returns (y [B, T, H, P] without the D
    term, the state [B, H, P, N] as it stands after position `state_at`
    [B])."""
    bsz, t, h, p = x.shape
    r = h // b.shape[2]
    zero = jnp.zeros((bsz, h, p, b.shape[3]), jnp.float32)

    def step(carry, part):
        s, kept = carry
        x, delta, b, c, at = part
        b, c = jnp.repeat(b, r, axis=1), jnp.repeat(c, r, axis=1)
        s = (jnp.exp(delta * a)[..., None, None] * s
             + (delta[..., None] * x)[..., None] * b[:, :, None, :])
        kept = jnp.where((at == state_at)[:, None, None, None], s, kept)
        return (s, kept), jnp.einsum("bhpn,bhn->bhp", s, c)

    (_, kept), y = jax.lax.scan(
        step, (zero, zero),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c))
        + (jnp.arange(t),))
    return jnp.moveaxis(y, 0, 1), kept


def mamba(u, w, state_at, *, heads, head_dim, groups, state, eps):
    """Returns (out, the state after position `state_at`)."""
    bsz, t, _ = u.shape
    inner, gn = heads * head_dim, groups * state
    proj = u @ w["in_proj.weight"]
    z, xbc = proj[..., :inner], proj[..., inner:2 * inner + 2 * gn]
    dt = proj[..., 2 * inner + 2 * gn:]
    xbc = jax.nn.silu(short_conv(xbc, w["conv_weight"]) + w["conv_bias"])
    x = xbc[..., :inner].reshape(bsz, t, heads, head_dim)
    b = xbc[..., inner:inner + gn].reshape(bsz, t, groups, state)
    c = xbc[..., inner + gn:].reshape(bsz, t, groups, state)
    delta = jax.nn.softplus(dt + w["dt_bias"])
    y, kept = scan(x, delta, -jnp.exp(w["A_log"]), b, c, state_at)
    y = (y + w["D"][:, None] * x).reshape(bsz, t, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(bsz, t, groups, -1), 1.0, eps).reshape(bsz, t,
                                                                  inner)
    return (y * w["norm_weight"]) @ w["out_proj.weight"], kept


def attention(u, w, *, heads, kv_heads, head_dim):
    bsz, t, _ = u.shape
    q = (u @ w["q_proj.weight"]).reshape(bsz, t, heads, head_dim)
    k, v = (jnp.repeat((u @ w[name]).reshape(bsz, t, kv_heads, head_dim),
                       heads // kv_heads, axis=2)
            for name in ("k_proj.weight", "v_proj.weight"))
    cols = jnp.arange(t)
    out = []
    for r0 in range(0, t, ROW_BLOCK):
        r1 = min(r0 + ROW_BLOCK, t)
        s = jnp.einsum("bthd,bjhd->bhtj", q[:, r0:r1], k) / math.sqrt(head_dim)
        keep = jnp.arange(r0, r1)[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhtj,bjhd->bthd", p, v))
    return jnp.concatenate(out, axis=1).reshape(bsz, t, -1) \
        @ w["o_proj.weight"]


@functools.partial(jax.jit, static_argnames=("kind", "heads", "kv_heads",
                                             "head_dim", "mamba_heads",
                                             "mamba_head_dim", "groups",
                                             "state", "eps"))
def _mixer(x, w_norm, w, state_at, *, kind, heads, kv_heads, head_dim,
           mamba_heads, mamba_head_dim, groups, state, eps):
    """(x + part(norm(x)), a Mamba block's state after `state_at` or
    None)."""
    with _HIGHEST():
        w = {k: a.astype(jnp.float32) for k, a in w.items()}
        u = rms_norm(x, w_norm.astype(jnp.float32), eps)
        if kind == "mamba":
            y, kept = mamba(u, w, state_at, heads=mamba_heads,
                            head_dim=mamba_head_dim, groups=groups,
                            state=state, eps=eps)
            return x + y, kept
        return x + attention(u, w, heads=heads, kv_heads=kv_heads,
                             head_dim=head_dim), None


@jax.jit
def _expert_block(m, dense_w, up, down):
    """sum over this block's experts of dense_w[:, e] * expert_e(m)."""
    with _HIGHEST():
        up, down = up.astype(jnp.float32), down.astype(jnp.float32)
        h = jnp.square(jax.nn.relu(jnp.einsum("td,edw->etw", m, up)))
        return jnp.einsum("etd,te->td", jnp.einsum("etw,ewd->etd", h, down),
                          dense_w)


def moe(m, w, *, first, top_k, n_group, topk_group, scaling, forced=None):
    """m [T, hidden]; the experts held are first .. first + count - 1.
    Returns (y [T, hidden], experts [T, top_k], expert margin, group
    margin, s' [T, experts]): the reference's OWN choice and margins. With
    `forced` [T, top_k] the sum runs over those experts instead (weights
    from the reference's own s), as `reference.ling.moe`."""
    with _HIGHEST():
        m32 = m.astype(jnp.float32)
        experts, weights, margin, group_margin, biased = choose(
            m32, w["router"].astype(jnp.float32),
            w["router_bias"].astype(jnp.float32), top_k=top_k,
            n_group=n_group, topk_group=topk_group, scaling=scaling)
        count = w["up_proj"].shape[0]
        used = experts
        if forced is not None:
            used = jnp.asarray(forced)
            s = jnp.take_along_axis(
                biased - w["router_bias"].astype(jnp.float32), used, axis=1)
            weights = scaling * s / s.sum(-1, keepdims=True)
        dense = jnp.zeros((m.shape[0], w["router"].shape[1]), jnp.float32).at[
            jnp.arange(m.shape[0])[:, None], used].add(weights)
        dense = dense[:, first:first + count]
        y = jnp.square(jax.nn.relu(
            m32 @ w["shared_up.weight"].astype(jnp.float32))) \
            @ w["shared_down.weight"].astype(jnp.float32)
    for e0 in range(0, count, EXPERT_BLOCK):
        e1 = min(e0 + EXPERT_BLOCK, count)
        y = y + _expert_block(m32, dense[:, e0:e1], w["up_proj"][e0:e1],
                              w["down_proj"][e0:e1])
    return y, experts, margin, group_margin, biased


def forward(named: dict, ids, at, *, kinds, heads: int, kv_heads: int,
            head_dim: int, mamba_heads: int, mamba_head_dim: int,
            groups: int, state: int, first: int, top_k: int, n_group: int,
            topk_group: int, scaling: float, eps: float,
            prefix: str = "backbone", forced=None, state_at=None):
    """Full forward of tokens `ids` [B, T] through the blocks held, whose
    kinds are `kinds` ("mamba", "moe", "attention"). Returns (logits [B, P,
    V] at the positions `at` [B, P] only, and per expert block a dict of
    `experts` [B, T, top_k], `margin` [B, T], `group_margin` [B, T] and
    `biased` [B, T, experts]). `forced`: per expert block the experts
    [B, T, top_k] to sum over in place of the reference's own (see `moe`).
    With `state_at` [B] a third result: every Mamba block's state [B, H,
    P, N] as it stands after that position."""
    ids = jnp.asarray(ids)
    b, t = ids.shape
    x = jnp.asarray(named[prefix + ".embeddings.weight"]
                    )[ids].astype(jnp.float32)
    routing, states = [], []
    stop = jnp.full((b,), t - 1) if state_at is None else jnp.asarray(state_at)
    for i, kind in enumerate(kinds):
        w = _sub(named, f"{prefix}.layers.{i}.")
        if kind != "moe":
            x, kept = _mixer(
                x, w["norm.weight"], _sub(w, "mixer."), stop, kind=kind,
                heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                mamba_heads=mamba_heads, mamba_head_dim=mamba_head_dim,
                groups=groups, state=state, eps=float(eps))
            if kept is not None:
                states.append(kept)
            continue
        with _HIGHEST():
            m = rms_norm(x, w["norm.weight"].astype(jnp.float32), eps)
        y, experts, margin, group_margin, biased = moe(
            m.reshape(b * t, -1), _sub(w, "mixer."), first=first,
            top_k=top_k, n_group=n_group, topk_group=topk_group,
            scaling=scaling, forced=None if forced is None else jnp.asarray(
                forced[len(routing)]).reshape(b * t, top_k))
        x = x + y.reshape(b, t, -1)
        routing.append({"experts": experts.reshape(b, t, top_k),
                        "margin": margin.reshape(b, t),
                        "group_margin": group_margin.reshape(b, t),
                        "biased": biased.reshape(b, t, -1)})
    h = jnp.take_along_axis(x, jnp.asarray(at)[..., None], axis=1)
    with _HIGHEST():
        h = rms_norm(h, jnp.asarray(named[prefix + ".norm_f.weight"],
                                    jnp.float32), eps)
    head = named["lm_head.weight"]
    logits = jnp.concatenate(
        [_head_block(h, head[:, v0:v0 + VOCAB_BLOCK])
         for v0 in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1)
    return (logits, routing) if state_at is None else (logits, routing,
                                                       states)
