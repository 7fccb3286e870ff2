"""A dropless routed expert layer that is told which experts it holds.

DeepSeek-V3's router (bias-corrected sigmoid scores, a choice limited to
the best groups) over SwiGLU or relu^2 experts, plus one shared expert:

    s = sigmoid(W_r m)              s' = s + b       (b: for the choice only)
    group score = the sum of a group's 2 largest s'; keep `topk_group` groups
    T = the `top_k` largest s' among the kept groups' experts
    w_e = scaling * s_e / sum_{e' in T} s_{e'}       (weights from s, not s')
    y = sum_{e in T} w_e W_d,e(silu(W_g,e m) * (W_u,e m)) + shared(m)

The router always has the deployment's width, `num_experts`. `held =
(first, count)` names the experts whose weights live here (the share of
one chip under expert parallelism; the default is all of them): the sum
then runs over `e in T` with `first <= e < first + count`, routes that
land elsewhere are left out, and the shared expert is whole. The parts of
all the shares, the shared expert counted once, add up to the uncut layer.

The rows a held expert takes are sorted by expert and go through ONE
grouped matmul a projection (`kernels/grouped_matmul.py`, `moe_experts`
in a device trace): no capacity, no dropped token, the same code for a
prompt's thousands of rows and a decode step's few. `parallel/moe.py` is
the capacity-bucket layer (GShard) and stays what it is.

A caller that knows which rows carry a token anyone will read says so
(`live`): the others (a free slot's row in a decode step, a prompt
bucket's padding) are routed NOWHERE, as a route to another rank is, so
the step reads the experts its live rows reach and a dead row's output is
the shared expert's alone. The router still reports every row's choice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import monitor as _monitor
from ...kernels import grouped_matmul as _gm
from ...ops._dispatch import run_op
from .. import functional as F
from .. import initializer as I
from .common import Linear
from .layers import Layer

__all__ = ["RoutedExperts"]


def route(logits, bias, top_k, n_group, topk_group, scaling):
    """The choice. logits [T, E] float32, bias [E] -> (experts [T, top_k]
    int32, weights [T, top_k] float32, the biased scores s' [T, E] the
    choice was made by)."""
    t, e = logits.shape
    s = jax.nn.sigmoid(logits)
    scores = biased = s + bias
    if n_group > 1:
        groups = biased.reshape(t, n_group, e // n_group)
        score = jax.lax.top_k(groups, 2)[0].sum(-1)
        kept = jax.lax.top_k(score, topk_group)[1]                # [T, kept]
        mask = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        biased = jnp.where(jnp.repeat(mask, e // n_group, axis=1), biased,
                           -jnp.inf)
    experts = jax.lax.top_k(biased, top_k)[1]
    w = jnp.take_along_axis(s, experts, axis=1)
    return (experts.astype(jnp.int32),
            scaling * w / (w.sum(-1, keepdims=True) + 1e-20), scores)


def experts_pass(m, local, w, gate, up, down, reach=False):
    """sum over a row's routes of w * expert(m). m [T, hidden]; local
    [T, top_k] int32, the route's index among the experts held, or their
    count for a route that lands elsewhere; w [T, top_k] float32; gate
    (None: relu^2 experts), up [count, hidden, width]; down [count, width,
    hidden]. Returns [T, hidden] float32; with `reach` also [2] int32 off
    the layout's group sizes: the held experts some route reached, and the
    routes that landed on held experts."""
    t, k = local.shape
    count = up.shape[0]
    tile = _gm.tile_rows_for(t * k)
    place, tile_group, active, tiles_of = _gm.layout(local.reshape(-1),
                                                     count, tile)
    total = _gm.padded_rows(t * k, count, tile)
    # the token of every row of the grouped layout (row 0 where none is)
    token = jnp.zeros((total,), jnp.int32).at[place].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    x = m[token]
    h = _gm.grouped_matmul(x, tile_group, active, tiles_of, (gate, up), tile)
    y = _gm.grouped_matmul(h, tile_group, active, tiles_of, (down,), tile)
    here = (local < count)[..., None]
    mine = y[jnp.minimum(place, total - 1)].reshape(t, k, -1)
    # a route that lands elsewhere reads a row nobody wrote: select, never
    # multiply by zero
    out = jnp.where(here, mine.astype(jnp.float32) * w[..., None], 0.0
                    ).sum(1)
    if not reach:
        return out
    return out, jnp.stack([(tiles_of > 0).sum(dtype=jnp.int32),
                           here.sum(dtype=jnp.int32)])


class RoutedExperts(Layer):
    def __init__(self, hidden, width, num_experts, top_k, n_group=1,
                 topk_group=1, scaling=1.0, held=None, shared_width=0,
                 weight_attr=None, bias_attr=None, activation="swiglu"):
        super().__init__()
        first, count = held if held is not None else (0, num_experts)
        if not (0 <= first and first + count <= num_experts and count > 0):
            raise ValueError(f"RoutedExperts: held {held} lies outside "
                             f"{num_experts} experts")
        if num_experts % n_group or topk_group > n_group:
            raise ValueError(f"RoutedExperts: {n_group} groups (keep "
                             f"{topk_group}) do not divide {num_experts}")
        self.num_experts, self.top_k = num_experts, top_k
        self.n_group, self.topk_group = n_group, topk_group
        self.scaling, self.held = float(scaling), (first, count)
        # the router is float32 whatever the experts' dtype: a choice is
        # discrete, and a score rounded to bfloat16 ties
        self.router = self.create_parameter([hidden, num_experts],
                                            dtype="float32")
        self.router_bias = self.create_parameter(
            [num_experts], attr=bias_attr, dtype="float32", is_bias=True)
        stacked = lambda a, b: self.create_parameter(
            [count, a, b], attr=weight_attr,
            default_initializer=I.XavierNormal(fan_in=a, fan_out=b))
        # "relu2": experts of two projections, W_d relu(W_u m)^2, no gate
        gated = {"swiglu": True, "relu2": False}[activation]
        self.gate_proj = stacked(hidden, width) if gated else None
        self.up_proj = stacked(hidden, width)
        self.down_proj = stacked(width, hidden)
        linear = lambda a, b: Linear(a, b, weight_attr, bias_attr=False)
        if shared_width:
            self.shared_gate = linear(hidden, shared_width) if gated else None
            self.shared_up = linear(hidden, shared_width)
            self.shared_down = linear(shared_width, hidden)
        else:
            self.shared_gate = self.shared_up = None
        if _monitor._ENABLED:
            _monitor.gauge_set("moe.experts_held", count)
            _monitor.gauge_set("moe.experts_total", num_experts)

    def choose(self, m):
        """m [T, hidden] -> (experts [T, top_k] int32 over all
        `num_experts`, weights [T, top_k] float32, the biased scores s'
        [T, num_experts])."""
        def f(m, router, bias):
            logits = jnp.matmul(m.astype(jnp.float32), router,
                                precision=jax.lax.Precision.HIGHEST)
            return route(logits, bias, self.top_k, self.n_group,
                         self.topk_group, self.scaling)
        return run_op(f, [m, self.router, self.router_bias], "moe_route")

    def forward(self, x, return_choice=False, live=None,
                return_reached=False):
        """x [..., hidden] -> the held experts' part of the sum plus the
        shared expert, in x's dtype; with `return_choice` also the chosen
        experts [..., top_k] and their biased scores s' [..., num_experts];
        with `return_reached` last the pass's [2] int32 counts
        (`experts_pass`). `live` (bool, x's leading dimensions): the rows
        whose output anyone reads; the others reach no held expert, their
        choice is reported."""
        lead = list(x.shape[:-1])
        m = x.reshape([-1, x.shape[-1]])
        experts, w, scores = self.choose(m)
        first, count = self.held
        # relu^2 experts hold no gate: `experts_pass` is given None for it
        gate = [] if self.gate_proj is None else [self.gate_proj]

        def f(m, experts, w, gate, up, down, *live):
            local = experts - first
            local = jnp.where((local >= 0) & (local < count), local, count)
            if live:
                local = jnp.where(live[0].reshape(-1, 1), local, count)
            out = experts_pass(m, local, w, gate, up, down, return_reached)
            if return_reached:
                return out[0].astype(m.dtype), out[1]
            return out.astype(m.dtype)
        masked = [] if live is None else [live]
        if masked and _monitor._ENABLED:
            _monitor.count("moe.masked_traces")
        fn = f if gate else lambda m, e, w, *rest: f(m, e, w, None, *rest)
        y = run_op(fn, [m, experts, w, *gate, self.up_proj,
                       self.down_proj] + masked, "moe_experts")
        reached = []
        if return_reached:
            y, reached = y[0], [y[1]]
        if self.shared_gate is not None:
            y = y + self.shared_down(F.silu(self.shared_gate(m))
                                     * self.shared_up(m))
        elif self.shared_up is not None:
            y = y + self.shared_down(F.relu(self.shared_up(m)) ** 2)
        y = y.reshape(lead + [x.shape[-1]])
        if return_choice:
            return (y, experts.reshape(lead + [self.top_k]),
                    scores.reshape(lead + [self.num_experts]), *reached)
        return (y, *reached) if reached else y
