"""Ling (`bailing_hybrid`): a decoder whose layers differ with depth.

inclusionAI's Ling-3.0-flash language model: pre-RMSNorm blocks whose
mixer is Kimi Delta Attention (`kernels/kda.py`) in all layers but every
`layer_group_size`-th, which is DeepSeek-V2's multi-head latent attention
(`F.latent_attention_*`), and whose feed-forward part is a dense SwiGLU in
the first `first_k_dense_replace` layers and a routed expert layer
(`nn.RoutedExperts`) after them. Layer l (the published index):

    x'  = x  + Mixer_l(RMSNorm(x))     Mixer_l = MLA if (l + 1) % group == 0 else KDA
    x'' = x' + FFN_l(RMSNorm(x'))      FFN_l   = SwiGLU if l < first_k_dense else MoE

KDA (u the normed input; heads of `head_dim` for keys and values):

    q, k, v = silu(conv4(W_q u)), silu(conv4(W_k u)), silu(conv4(W_v u))
    q^h = l2norm(q^h) / sqrt(d);   k^h = l2norm(k^h)
    g^h = lower_bound * sigmoid(exp(A^h) ((W_a u)^h + b_a^h))   a key channel
    beta^h = sigmoid((W_b u)^h)                                 a head
    S, o = the gated delta rule (kernels/kda.py)
    y = W_o concat_h(sigmoid((W_g u)^h) * RMSNorm_d(o^h))

MLA (`_decoder.LatentAttention`, shared with `models/dots.py`): q = W_q u
split a head into nope + rope; [c; kr] = W_dkv u, c normed, kr and the
query's rope part rotated (interleaved pairs); keys and values a head are
W_ukv c. What a sequence keeps is the row [c; kr] a position.

A model may hold a part of the depth (`layers`: the published indices it
holds, whose kinds follow from the index), a part of the experts (`held`)
and a slice of the vocabulary (`vocab_size` is the rows held): the share
of one chip in a stated deployment.

The cache contract `serving.LLMEngine` asks of a model: `init_cache` -> a
flat list, per KDA layer the state `S [B, H, d, d]` float32 and the
convolution's rows `[B, 3, 3 H d]`, per MLA layer the page `[B, max_len,
latent + rope, in whole 128s]`; `cache_tag` is a tuple, one tag an array
(`state_pool` for what does not grow, `kv_pool` for the page);
`forward_cached(tokens, cache, positions, lengths=None)` as
`models/brumby.py`'s: with `lengths` a prompt from an EMPTY cache, else one
token a row through `cache`. After the cache's arrays it returns what the
call reports, an expert layer each: the experts chosen `[B, T, top_k]`.

**A step's row at position 0 carries no sequence and its output is
unspecified** (a sequence's first step is at its prompt's length, at least
1; `serving.LLMEngine` marks free slots and left-out rows so), as is a
prompt's row past its length: `_live_rows` says which rows those are and
the expert layers route them nowhere (`nn.RoutedExperts(live=)`), so a
step reads the experts its live rows reach. Their choice is still reported.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..framework.param_attr import ParamAttr
from ..kernels import kda as _kda
from ..nn import initializer as I
from ..ops._dispatch import run_op
from ..ops.manipulation import concat, reshape, unsqueeze
from ._decoder import (
    LatentAttention, SwiGLU, _linear, _live_rows, _logits, _Normal,
    _parameters_in, _rows_at,
)


def layer_kinds(layers, layer_group_size, first_k_dense_replace):
    """[(mixer, ffn)] of the published layer indices `layers`."""
    return [("mla" if (l + 1) % layer_group_size == 0 else "kda",
             "dense" if l < first_k_dense_replace else "moe")
            for l in layers]


class LingKDA(nn.Layer):
    def __init__(self, hidden_size, num_heads, head_dim, conv_kernel,
                 lower_bound, decay_bias, rms_norm_eps):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.lower_bound = float(lower_bound)
        width = num_heads * head_dim
        self.q_proj = _linear(hidden_size, width)
        self.k_proj = _linear(hidden_size, width)
        self.v_proj = _linear(hidden_size, width)
        # one array for the three convolutions, as their rows are kept
        self.conv_weight = self.create_parameter(
            [conv_kernel, 3 * width],
            attr=ParamAttr(initializer=_Normal(0.5)))
        self.a_proj = _linear(
            hidden_size, width,
            bias_attr=ParamAttr(initializer=I.Constant(decay_bias)))
        self.a_log = self.create_parameter(
            [num_heads], attr=ParamAttr(initializer=_Normal(0.2)),
            dtype="float32")
        self.b_proj = _linear(hidden_size, num_heads)
        self.g_proj = _linear(hidden_size, num_heads)
        self.o_norm = nn.RMSNorm(head_dim, rms_norm_eps)
        self.o_proj = _linear(width, hidden_size)

    def _qkv_rows(self, u):
        """The convolutions' pre-activation rows [B, T, 3 H d]."""
        return concat([self.q_proj(u), self.k_proj(u), self.v_proj(u)],
                      axis=-1)

    def _gates(self, u):
        """(g [B, T, H, d] float32, beta [B, T, H], out gate [B, T, H])."""
        h, d, low = self.num_heads, self.head_dim, self.lower_bound

        def f(u, w_a, b_a, a_log, b, gate):
            # the decay's map in float32: g sits near 0, where a
            # projection rounded to bfloat16 would move it by percents
            a = jnp.matmul(u, w_a, preferred_element_type=jnp.float32) \
                + b_a.astype(jnp.float32)
            a = a.reshape(a.shape[:-1] + (h, d))
            g = low * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * a)
            return (g, jax.nn.sigmoid(b.astype(jnp.float32)),
                    jax.nn.sigmoid(gate.astype(jnp.float32)))
        return run_op(f, [u, self.a_proj.weight, self.a_proj.bias, self.a_log,
                          self.b_proj(u), self.g_proj(u)], "kda_gates")

    def _heads(self, y):
        """y [B, T, 3 H d] float32 after the convolution -> q, k, v
        [B, T, H, d] float32, q and k normalised, q scaled."""
        h, d = self.num_heads, self.head_dim

        def f(y):
            y = jax.nn.silu(y).reshape(y.shape[:-1] + (3, h, d))
            unit = lambda a: a * jax.lax.rsqrt(
                jnp.sum(a * a, -1, keepdims=True) + 1e-6)
            return (unit(y[..., 0, :, :]) / math.sqrt(d),
                    unit(y[..., 1, :, :]), y[..., 2, :, :])
        return run_op(f, [y], "kda_heads")

    def _out(self, o, gate, dtype):
        """o [B, T, H, d] float32, gate [B, T, H] -> [B, T, hidden]."""
        o = self.o_norm(o) * unsqueeze(gate, -1)
        return self.o_proj(reshape(o, o.shape[:2] + [-1]).astype(dtype))

    def forward_cached(self, u, state, rows, lengths, step):
        """`step` false: a prompt [B, T, hidden] from an empty state
        (`state` and `rows` are not read; `lengths` [B] or None); true: one
        token [B, 1, hidden] through them. Returns (out, state, rows)."""
        g, beta, gate = self._gates(u)
        x = self._qkv_rows(u)
        if not step:
            masked = [] if lengths is None else [lengths]
            y, rows = run_op(_kda.short_conv_prompt,
                             [x, self.conv_weight] + masked, "kda_conv")
            q, k, v = self._heads(y)
            o, state = run_op(_kda.kda_chunked, [q, k, v, g, beta] + masked,
                              "kda_chunked")
        else:
            if u.shape[1] != 1:
                raise ValueError("a step through a recurrent state is one "
                                 f"token wide, got {u.shape[1]}")
            y, rows = run_op(
                lambda x, w, r: _kda.short_conv_step(x[:, 0], w, r),
                [x, self.conv_weight, rows], "kda_conv_step")
            q, k, v = self._heads(unsqueeze(y, 1))

            def one(q, k, v, g, beta, s):
                o, s = _kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                     beta[:, 0], s)
                return o[:, None], s
            o, state = run_op(one, [q, k, v, g, beta, state], "kda_step")
        return self._out(o, gate, u.dtype), state, rows


class LingLayer(nn.Layer):
    def __init__(self, mixer, ffn, cfg):
        super().__init__()
        self.mixer_kind, self.ffn_kind = mixer, ffn
        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.input_norm = nn.RMSNorm(hidden, eps)
        if mixer == "kda":
            self.mixer = LingKDA(hidden, cfg["num_attention_heads"],
                                 cfg["head_dim"],
                                 cfg["short_conv_kernel_size"],
                                 cfg["kda_lower_bound"],
                                 cfg["kda_decay_bias"], eps)
        else:
            self.mixer = LatentAttention(
                hidden, cfg["num_attention_heads"], cfg["kv_lora_rank"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], cfg["rope_theta"], eps)
        self.post_norm = nn.RMSNorm(hidden, eps)
        if ffn == "dense":
            self.mlp = SwiGLU(hidden, cfg["intermediate_size"])
        else:
            width = cfg["moe_intermediate_size"]
            self.mlp = nn.RoutedExperts(
                hidden, width, cfg["num_experts"],
                cfg["num_experts_per_tok"], cfg["n_group"],
                cfg["topk_group"], cfg["routed_scaling_factor"],
                held=cfg["held"],
                shared_width=cfg["moe_shared_expert_intermediate_size"],
                weight_attr=ParamAttr(initializer=_Normal()),
                bias_attr=ParamAttr(initializer=_Normal(
                    cfg["router_bias_std"])))

    def cache_arrays(self, batch, max_len, state_dtype, dtype):
        """The arrays this layer keeps a sequence, slot on axis 0, and
        their tags."""
        m = self.mixer
        if self.mixer_kind == "kda":
            taps = m.conv_weight.shape[0] - 1
            return [(jnp.zeros((batch, m.num_heads, m.head_dim, m.head_dim),
                               state_dtype), "state_pool"),
                    (jnp.zeros((batch, taps, m.conv_weight.shape[1]), dtype),
                     "state_pool")]
        return [(jnp.zeros((batch, max_len, m.page_width), dtype),
                 "kv_pool")]

    def forward_cached(self, x, cache, positions, lengths, step,
                       scores=None, live=None):
        """cache: this layer's arrays. Returns (x, new arrays, the experts
        an expert layer chose [B, T, top_k] or None); `scores` (a list)
        gains an expert layer's biased scores [B, T, num_experts]; `live`
        [B, T] bool or None: the rows an expert layer routes."""
        u = self.input_norm(x)
        if self.mixer_kind == "kda":
            a, *new = self.mixer.forward_cached(u, *cache, lengths, step)
        else:
            a, *new = self.mixer.forward_cached(u, *cache, positions, lengths,
                                                step)
        x = x + a
        m = self.post_norm(x)
        if self.ffn_kind != "moe":
            return x + self.mlp(m), new, None
        y, experts, biased = self.mlp(m, return_choice=True, live=live)
        if scores is not None:
            scores.append(biased)
        return x + y, new, experts


class LingModel(nn.Layer):
    def __init__(self, vocab_size=157184, hidden_size=2560,
                 num_hidden_layers=42, layers=None, num_attention_heads=32,
                 head_dim=128, intermediate_size=6144,
                 moe_intermediate_size=768, num_experts=512,
                 num_experts_per_tok=8, n_group=8, topk_group=4,
                 routed_scaling_factor=2.5,
                 moe_shared_expert_intermediate_size=768, held=None,
                 first_k_dense_replace=2, layer_group_size=6,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, rope_theta=6000000.0, rms_norm_eps=1e-6,
                 short_conv_kernel_size=4, kda_lower_bound=-5.0,
                 kda_decay_bias=-7.0, router_bias_std=0.01,
                 initializer_range=0.02, dtype="float32"):
        super().__init__()
        cfg = dict(locals())
        self.layer_ids = list(range(num_hidden_layers)) if layers is None \
            else [int(l) for l in layers]
        kinds = layer_kinds(self.layer_ids, layer_group_size,
                            first_k_dense_replace)
        self.param_dtype = dtype
        with _parameters_in(dtype):
            self.embed_tokens = nn.Embedding(
                vocab_size, hidden_size, weight_attr=ParamAttr(
                    initializer=_Normal(initializer_range)))
            self.layers = nn.LayerList([LingLayer(mixer, ffn, cfg)
                                        for mixer, ffn in kinds])
            self.norm = nn.RMSNorm(hidden_size, rms_norm_eps)

    def cache_arrays(self, batch_size, max_len, state_dtype="float32"):
        out = []
        for layer in self.layers:
            out += layer.cache_arrays(batch_size, max_len, state_dtype,
                                      self.param_dtype)
        return out

    def forward(self, input_ids, choices=None):
        """The full forward (nothing kept); `choices` (a list) gains every
        expert layer's [chosen experts [B, T, top_k], biased scores [B, T,
        num_experts]]."""
        scores = None if choices is None else []
        x, _, routes = self.forward_cached(input_ids, None, None, None,
                                           scores)
        if choices is not None:
            choices += [list(pair) for pair in zip(routes, scores)]
        return x

    def forward_cached(self, input_ids, cache, positions, lengths=None,
                       scores=None):
        """`cache` None: the full forward. Returns (hidden states, the new
        cache, every expert layer's chosen experts [B, T, top_k] int32)."""
        step = cache is not None and lengths is None
        live = None if cache is None else _live_rows(
            positions, lengths, input_ids.shape[1])
        x = self.embed_tokens(input_ids)
        new, routes, at = [], [], 0
        for layer in self.layers:
            n = 2 if layer.mixer_kind == "kda" else 1
            mine = [None] * n if cache is None else cache[at:at + n]
            at += n
            x, kept, experts = layer.forward_cached(
                x, mine, positions, lengths, step, scores, live)
            new += kept
            if experts is not None:
                routes.append(experts)
        return x, new, routes          # the final norm is the head's


class LingForCausalLM(nn.Layer):
    def __init__(self, ling: LingModel):
        super().__init__()
        self.ling = ling
        hidden, vocab = (ling.embed_tokens.embedding_dim,
                         ling.embed_tokens.num_embeddings)
        with _parameters_in(ling.param_dtype):
            self.lm_head = _linear(hidden, vocab)
        # `serving.LLMEngine` reads this: one tag an array of `init_cache`
        self.cache_tag = tuple(tag for _, tag in ling.cache_arrays(1, 1))

    def forward(self, input_ids, at=None, choices=None):
        """Logits [B, T, vocab]; with `at` [B] or [B, P], those of the
        positions `at[b]` only. `choices` (a list) gains every expert
        layer's [chosen experts [B, T, top_k], biased scores [B, T,
        num_experts]]."""
        h = self.ling(input_ids, choices)
        return _logits(self.ling.norm, self.lm_head,
                       h if at is None else _rows_at(h, at))

    def init_cache(self, batch_size, max_len=None, dtype="float32"):
        """`dtype` is the recurrent states'; the convolution's rows and the
        page are held in the weights' dtype."""
        return [Tensor(a) for a, _ in self.ling.cache_arrays(
            batch_size, max_len or 1, dtype)]

    def forward_cached(self, input_ids, cache, positions, lengths=None):
        """Returns (logits, the new cache and AFTER it what the call
        reports: every expert layer's chosen experts [B, T, top_k] int32).
        A routed model's choice is discrete and its numbers follow from
        it, so whoever balances the experts' load, replays a route or
        holds the sums to a reference needs the choice the program made;
        `serving.LLMEngine`'s programs hand it out after the pool."""
        cache = list(cache)
        h, new, routes = self.ling.forward_cached(input_ids, cache, positions,
                                                  lengths)
        last = h[:, 0] if lengths is None else _rows_at(h, lengths - 1)
        return _logits(self.ling.norm, self.lm_head, last), new + routes
