"""LFM2-MoE (`lfm2_moe`): gated short-convolution mixers beside grouped-query
attention, a dense feed-forward in the leading blocks and routed experts
after them.

LiquidAI's LFM2-24B-A2B: pre-RMSNorm blocks whose mixer is a gated short
convolution or attention, as the published `layer_types` says by index;
an untied head after a final RMSNorm. Block l (the published index), u
the normed input:

    h += Mixer_l(RMSNorm(h));   h += FFN_l(RMSNorm(h))

The gated short convolution (`Lfm2ShortConv`):

    [B | C | x] = W_in u;   v = B * x
    z_t = sum_{j < K} w_j * v_{t - K + 1 + j}        causal, depthwise, no bias
    out = W_out (C * z)

(`kda.short_conv_prompt` / `short_conv_step`); what a sequence keeps is
the last K - 1 rows of v. Attention (`Lfm2Attention`): q heads grouped
over fewer K/V heads, q and k each through an RMSNorm over the head, then
rotary (half-split), causal, 1 / sqrt(head_dim), no bias. The FFN of the
first `num_dense_layers` blocks is a SwiGLU; every later block's is
`nn.RoutedExperts`: sigmoid scores, a bias for the choice only, the top k
weighed by their unbiased scores normalised, SwiGLU experts, no shared
expert.

A model may hold a part of the depth (`layers`: the published indices it
holds) and a part of the experts (`held`): the share of one chip in a
stated deployment.

The cache contract `serving.LLMEngine` asks of a model, as
`models/nemotron.py`'s: `init_cache` -> a flat list, per convolution
block its rows `[B, K - 1, hidden]` (`state_pool`), per attention block a
K page and a V page `[B, max_len, kv_heads * head_dim]` (`kv_pool`), all
in the weights' dtype; `cache_tag` one tag an array;
`forward_cached(tokens, cache, positions, lengths=None)`: with `lengths` a
prompt from an EMPTY cache, else one token a row through `cache`; after
the cache's arrays what the call reports: every expert block's chosen
experts `[B, T, top_k]`, and after a STEP the int32 vector the engine
stamps under `step_counts` (`LLMEngine`): the held experts the step's
expert blocks reached, the most they could reach, and the routes that
landed on held experts, each summed over the expert blocks. A step's row
at position 0 carries no sequence and its output is unspecified, as is a
prompt's row past its length: the expert blocks route those rows nowhere
(`_decoder._live_rows`).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..framework.param_attr import ParamAttr
from ..kernels import kda as _kda
from ..nn import functional as F
from ..ops._dispatch import run_op
from ..ops.creation import arange
from ..ops.manipulation import reshape, unsqueeze
from ._decoder import (
    SwiGLU, _linear, _live_rows, _logits, _Normal, _parameters_in, _rows_at,
)
from .nemotron import _attend_prompt, _attend_step

KINDS = {"conv": "conv", "full_attention": "attention"}
# what a decode step reports after its routes, in this order
STEP_COUNTS = ("moe.decode.experts_reached", "moe.decode.experts_read_max",
               "moe.decode.routes_reached")


def layer_kinds(layer_types, layers, num_dense_layers):
    """(mixer, ffn) of the published layer indices `layers`: the mixer as
    `layer_types` names it, the FFN dense before `num_dense_layers` and
    routed after."""
    kinds = []
    for l in layers:
        if layer_types[l] not in KINDS:
            raise ValueError(f"layer {l}: kind {layer_types[l]!r} is not "
                             "built")
        kinds.append((KINDS[layer_types[l]],
                      "dense" if l < num_dense_layers else "moe"))
    return kinds


def _gate_in(p, dtype):
    """[B | C | x] -> (v = B * x in the weights' dtype, C)."""
    h = p.shape[-1] // 3
    f32 = lambda a: a.astype(jnp.float32)
    return (f32(p[..., :h]) * f32(p[..., 2 * h:])).astype(dtype), \
        p[..., h:2 * h]


def _gate_out(c, z, dtype):
    return (c.astype(jnp.float32) * z).astype(dtype)


class Lfm2ShortConv(nn.Layer):
    def __init__(self, hidden_size, kernel):
        super().__init__()
        self.hidden_size, self.kernel = hidden_size, kernel
        self.in_proj = _linear(hidden_size, 3 * hidden_size)
        # PyTorch's Conv1d start, U(+-1/sqrt(K)), has this deviation
        self.conv_weight = self.create_parameter(
            [kernel, hidden_size],
            attr=ParamAttr(initializer=_Normal(1.0 / math.sqrt(3.0 * kernel))))
        self.out_proj = _linear(hidden_size, hidden_size)

    def forward_cached(self, u, rows, lengths, step):
        """`step` false: a prompt [B, T, hidden] from empty rows (`rows` is
        not read; `lengths` [B] or None); true: one token [B, 1, hidden]
        through them. Returns (out, rows)."""
        p = self.in_proj(u)
        if not step:
            def prompt(p, w, *lengths):
                v, c = _gate_in(p, p.dtype)
                z, kept = _kda.short_conv_prompt(v, w, *lengths)
                return _gate_out(c, z, p.dtype), kept
            masked = [] if lengths is None else [lengths]
            y, rows = run_op(prompt, [p, self.conv_weight] + masked,
                             "lfm2_conv")
        else:
            if u.shape[1] != 1:
                raise ValueError("a step through convolution rows is one "
                                 f"token wide, got {u.shape[1]}")

            def one(p, w, rows):
                v, c = _gate_in(p[:, 0], rows.dtype)
                z, rows = _kda.short_conv_step(v, w, rows)
                return _gate_out(c, z, p.dtype)[:, None], rows
            y, rows = run_op(one, [p, self.conv_weight, rows],
                             "lfm2_conv_step")
        return self.out_proj(y), rows


class Lfm2Attention(nn.Layer):
    def __init__(self, hidden_size, num_heads, num_kv_heads, rope_theta,
                 eps):
        super().__init__()
        if num_heads % num_kv_heads or hidden_size % num_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             f"K/V heads at hidden {hidden_size}")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = d = hidden_size // num_heads
        self.rope_theta = rope_theta
        self.scale = 1.0 / math.sqrt(d)
        self.q_proj = _linear(hidden_size, num_heads * d)
        self.k_proj = _linear(hidden_size, num_kv_heads * d)
        self.v_proj = _linear(hidden_size, num_kv_heads * d)
        self.out_proj = _linear(num_heads * d, hidden_size)
        self.q_layernorm = nn.RMSNorm(d, eps)
        self.k_layernorm = nn.RMSNorm(d, eps)

    def forward_cached(self, u, k_page, v_page, positions, lengths, step):
        """`step` false: a prompt [B, T, hidden] from empty pages (the pages
        name the length only, or None); true: one token [B, 1, hidden]
        written at `positions` [B] and read back with the slot's prefix.
        Returns (out, k_page, v_page)."""
        b, t = u.shape[0], u.shape[1]
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        scale = self.scale
        start = positions if positions is not None else \
            Tensor(jnp.zeros((b,), jnp.int32))
        pos = unsqueeze(start, 1) + unsqueeze(arange(t, dtype="int32"), 0)
        q = self.q_layernorm(reshape(self.q_proj(u), [b, t, h, d]))
        k = self.k_layernorm(reshape(self.k_proj(u), [b, t, kv, d]))
        q = F.rotary_embedding(q, pos, self.rope_theta)
        k = reshape(F.rotary_embedding(k, pos, self.rope_theta),
                    [b, t, kv * d])
        v = self.v_proj(u)
        if not step:
            def f(q, k, v):
                grow = lambda a: jnp.repeat(a.reshape(b, t, kv, d), h // kv,
                                            axis=2)
                return _attend_prompt(q, grow(k), grow(v),
                                      scale).reshape(b, t, -1)
            y = run_op(f, [q, k, v], "gqa_prompt")
            if k_page is not None:
                # the slot's pages whole: what follows the prompt is zeros
                pad = lambda r, p: jnp.pad(r, ((0, 0), (0, p.shape[1] - t),
                                               (0, 0)))
                k_page = run_op(pad, [k, k_page], "gqa_page_fill")
                v_page = run_op(pad, [v, v_page], "gqa_page_fill")
            return self.out_proj(y), k_page, v_page
        if t != 1:
            raise ValueError("a decode step through K/V pages is one token "
                             f"wide, got {t}")
        k_page = F.latent_page_write(k_page, k[:, 0], positions)
        v_page = F.latent_page_write(v_page, v[:, 0], positions)
        y = run_op(lambda q, kp, vp, pos: _attend_step(
            q[:, 0], kp, vp, pos.astype(jnp.int32), scale),
            [q, k_page, v_page, positions], "gqa_step")
        return self.out_proj(reshape(y, [b, 1, h * d])), k_page, v_page


class Lfm2Block(nn.Layer):
    def __init__(self, kinds, cfg):
        super().__init__()
        self.kind, self.ffn_kind = kinds
        hidden, eps = cfg["hidden_size"], cfg["norm_eps"]
        self.operator_norm = nn.RMSNorm(hidden, eps)
        self.ffn_norm = nn.RMSNorm(hidden, eps)
        if self.kind == "conv":
            self.mixer = Lfm2ShortConv(hidden, cfg["conv_L_cache"])
        else:
            self.mixer = Lfm2Attention(
                hidden, cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["rope_theta"], eps)
        if self.ffn_kind == "dense":
            self.feed_forward = SwiGLU(hidden, cfg["intermediate_size"])
        else:
            self.feed_forward = nn.RoutedExperts(
                hidden, cfg["moe_intermediate_size"], cfg["num_experts"],
                cfg["num_experts_per_tok"], 1, 1,
                cfg["routed_scaling_factor"], held=cfg["held"],
                weight_attr=ParamAttr(initializer=_Normal()),
                bias_attr=ParamAttr(initializer=_Normal(
                    cfg["router_bias_std"])))

    def cache_arrays(self, batch, max_len, dtype):
        """The arrays this block keeps a sequence, slot on axis 0, and
        their tags."""
        m = self.mixer
        if self.kind == "conv":
            return [(jnp.zeros((batch, m.kernel - 1, m.hidden_size), dtype),
                     "state_pool")]
        page = (batch, max_len, m.num_kv_heads * m.head_dim)
        return [(jnp.zeros(page, dtype), "kv_pool"),
                (jnp.zeros(page, dtype), "kv_pool")]

    def forward_cached(self, x, cache, positions, lengths, step,
                       scores=None, live=None):
        """cache: this block's arrays. Returns (x, new arrays, the experts
        an expert block chose [B, T, top_k] or None, what a STEP's expert
        block reached [3] int32 or None: `STEP_COUNTS` of this block);
        `scores` (a list) gains an expert block's biased scores [B, T,
        experts]; `live` [B, T] bool or None: the rows an expert block
        routes."""
        u = self.operator_norm(x)
        if self.kind == "conv":
            y, *new = self.mixer.forward_cached(u, *cache, lengths, step)
        else:
            y, *new = self.mixer.forward_cached(u, *cache, positions,
                                                lengths, step)
        x = x + y
        m = self.ffn_norm(x)
        if self.ffn_kind == "dense":
            return x + self.feed_forward(m), new, None, None
        ffn = self.feed_forward
        y, experts, biased, *reached = ffn(m, return_choice=True, live=live,
                                           return_reached=step)
        if scores is not None:
            scores.append(biased)
        if not reached:
            return x + y, new, experts, None
        held = ffn.held[1]
        counts = run_op(lambda r: jnp.stack([r[0], jnp.int32(held), r[1]]),
                        reached, "moe_step_counts")
        return x + y, new, experts, counts


class Lfm2MoeModel(nn.Layer):
    def __init__(self, vocab_size=65536, hidden_size=2048,
                 num_hidden_layers=40, layers=None, layer_types=None,
                 num_attention_heads=32, num_key_value_heads=8,
                 conv_L_cache=3, intermediate_size=11776,
                 moe_intermediate_size=1536, num_experts=64,
                 num_experts_per_tok=4, num_dense_layers=2,
                 routed_scaling_factor=1.0, held=None, norm_eps=1e-5,
                 rope_theta=1e6, router_bias_std=0.01,
                 initializer_range=0.02, dtype="float32"):
        super().__init__()
        if layer_types is None:      # the published pattern
            layer_types = ["conv", "conv"] + [
                "conv" if i % 4 else "full_attention"
                for i in range(num_hidden_layers - 2)]
        if len(layer_types) != num_hidden_layers:
            raise ValueError(f"{len(layer_types)} layer types for "
                             f"{num_hidden_layers} layers")
        cfg = dict(locals())
        self.layer_ids = list(range(num_hidden_layers)) if layers is None \
            else [int(l) for l in layers]
        self.param_dtype = dtype
        with _parameters_in(dtype):
            self.embed_tokens = nn.Embedding(
                vocab_size, hidden_size, weight_attr=ParamAttr(
                    initializer=_Normal(initializer_range)))
            self.layers = nn.LayerList(
                [Lfm2Block(kinds, cfg) for kinds in layer_kinds(
                    layer_types, self.layer_ids, num_dense_layers)])
            self.embedding_norm = nn.RMSNorm(hidden_size, norm_eps)

    def cache_arrays(self, batch_size, max_len):
        out = []
        for layer in self.layers:
            out += layer.cache_arrays(batch_size, max_len, self.param_dtype)
        return out

    def forward(self, input_ids, choices=None):
        """The full forward (nothing kept); `choices` (a list) gains every
        expert block's [chosen experts [B, T, top_k], biased scores [B, T,
        experts]]."""
        scores = None if choices is None else []
        x, _, routes, _ = self.forward_cached(input_ids, None, None, None,
                                              scores)
        if choices is not None:
            choices += [list(pair) for pair in zip(routes, scores)]
        return x

    def forward_cached(self, input_ids, cache, positions, lengths=None,
                       scores=None):
        """`cache` None: the full forward. Returns (hidden states, the new
        cache, every expert block's chosen experts [B, T, top_k] int32, and
        for a step `STEP_COUNTS` summed over the expert blocks [3] int32,
        else None)."""
        step = cache is not None and lengths is None
        live = None if cache is None else _live_rows(
            positions, lengths, input_ids.shape[1])
        x = self.embed_tokens(input_ids)
        new, routes, counts, at = [], [], None, 0
        for layer in self.layers:
            n = 1 if layer.kind == "conv" else 2
            mine = [None] * n if cache is None else cache[at:at + n]
            at += n
            x, kept, experts, reached = layer.forward_cached(
                x, mine, positions, lengths, step, scores, live)
            new += kept
            if experts is not None:
                routes.append(experts)
            if reached is not None:
                counts = reached if counts is None else counts + reached
        return x, new, routes, counts        # the final norm is the head's


class Lfm2MoeForCausalLM(nn.Layer):
    # `serving.LLMEngine` stamps these counters off a decode step's report
    step_counts = STEP_COUNTS

    def __init__(self, model: Lfm2MoeModel):
        super().__init__()
        self.model = model
        hidden, vocab = (model.embed_tokens.embedding_dim,
                         model.embed_tokens.num_embeddings)
        with _parameters_in(model.param_dtype):
            self.lm_head = _linear(hidden, vocab)
        # `serving.LLMEngine` reads this: one tag an array of `init_cache`
        self.cache_tag = tuple(tag for _, tag in model.cache_arrays(1, 1))
        if not any(l.ffn_kind == "moe" for l in model.layers):
            self.step_counts = ()

    def forward(self, input_ids, at=None, choices=None):
        """Logits [B, T, vocab]; with `at` [B] or [B, P], those of the
        positions `at[b]` only. `choices` as `Lfm2MoeModel.forward`'s."""
        h = self.model(input_ids, choices)
        return _logits(self.model.embedding_norm, self.lm_head,
                       h if at is None else _rows_at(h, at))

    def init_cache(self, batch_size, max_len=None, dtype="float32"):
        """Every array in the weights' dtype: this model keeps no state
        that sums over a sequence (`dtype`, the engine's state dtype, is
        not read)."""
        return [Tensor(a) for a, _ in self.model.cache_arrays(
            batch_size, max_len or 1)]

    def forward_cached(self, input_ids, cache, positions, lengths=None):
        """Returns (logits, the new cache and AFTER it what the call
        reports: every expert block's chosen experts [B, T, top_k] int32,
        then for a step the `step_counts` [3] int32)."""
        h, new, routes, counts = self.model.forward_cached(
            input_ids, list(cache), positions, lengths)
        last = h[:, 0] if lengths is None else _rows_at(h, lengths - 1)
        return _logits(self.model.embedding_norm, self.lm_head, last), \
            new + routes + ([] if counts is None else [counts])
