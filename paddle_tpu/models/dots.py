"""Dots (`dots_vlm`'s language model): a DeepSeek-V3-shaped decoder whose
ONLY mixer is latent attention.

rednote-hilab's dots.vlm1 language model: pre-RMSNorm blocks, every one
with multi-head latent attention (`_decoder.LatentAttention`: the low-rank
query with its norm, YaRN's rotary frequencies and softmax scale), a dense
SwiGLU in the first `first_k_dense_replace` layers and DeepSeek-V3's routed
experts (`nn.RoutedExperts`: sigmoid scores, a bias for the choice, the
best groups by their two best, top k, a shared expert) after them; an
untied head after a final RMSNorm. Layer l (the published index):

    x'  = x  + MLA(RMSNorm(x))
    x'' = x' + FFN_l(RMSNorm(x'))      FFN_l = SwiGLU if l < first_k_dense else MoE

A model may hold a part of the depth (`layers`: the published indices it
holds), a part of the experts (`held`) and a slice of the vocabulary
(`vocab_size` is the rows held): the share of one chip in a stated
deployment. The multi-token-prediction module and the vision tower of the
published model are not built.

The cache contract `serving.LLMEngine` asks of a model: `init_cache` -> a
page `[B, max_len, latent + rope in whole 128s]` a layer; `cache_tag`
tags every page `kv_pool`; `forward_cached(tokens, cache, positions,
lengths=None)` as `models/ling.py`'s: with `lengths` a prompt from an EMPTY
cache, else one token a row through `cache`. After the cache's arrays it
returns what the call reports, an expert layer each: the experts chosen
`[B, T, top_k]`. As there, a step's row at position 0 carries no sequence
and its output is unspecified, as is a prompt's row past its length: the
expert layers route those rows nowhere (`_decoder._live_rows`).
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..framework.param_attr import ParamAttr
from ._decoder import (
    LatentAttention, SwiGLU, _linear, _live_rows, _logits, _Normal,
    _parameters_in, _rows_at,
)


class DotsLayer(nn.Layer):
    def __init__(self, dense: bool, cfg):
        super().__init__()
        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.ffn_kind = "dense" if dense else "moe"
        self.input_norm = nn.RMSNorm(hidden, eps)
        self.mixer = LatentAttention(
            hidden, cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["rope_theta"], eps,
            q_lora_rank=cfg["q_lora_rank"], rope_scaling=cfg["rope_scaling"])
        self.post_norm = nn.RMSNorm(hidden, eps)
        if dense:
            self.mlp = SwiGLU(hidden, cfg["intermediate_size"])
        else:
            width = cfg["moe_intermediate_size"]
            self.mlp = nn.RoutedExperts(
                hidden, width, cfg["n_routed_experts"],
                cfg["num_experts_per_tok"], cfg["n_group"],
                cfg["topk_group"], cfg["routed_scaling_factor"],
                held=cfg["held"],
                shared_width=cfg["n_shared_experts"] * width,
                weight_attr=ParamAttr(initializer=_Normal()),
                bias_attr=ParamAttr(initializer=_Normal(
                    cfg["router_bias_std"])))

    def forward_cached(self, x, page, positions, lengths, step, scores=None,
                       live=None):
        """Returns (x, the page, the experts an expert layer chose [B, T,
        top_k] or None); `scores` (a list) gains an expert layer's biased
        scores [B, T, n_routed_experts]; `live` [B, T] bool or None: the
        rows an expert layer routes."""
        a, page = self.mixer.forward_cached(self.input_norm(x), page,
                                            positions, lengths, step)
        x = x + a
        m = self.post_norm(x)
        if self.ffn_kind != "moe":
            return x + self.mlp(m), page, None
        y, experts, biased = self.mlp(m, return_choice=True, live=live)
        if scores is not None:
            scores.append(biased)
        return x + y, page, experts


class DotsModel(nn.Layer):
    def __init__(self, vocab_size=129280, hidden_size=7168,
                 num_hidden_layers=61, layers=None, num_attention_heads=128,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 n_routed_experts=256, n_shared_experts=1,
                 num_experts_per_tok=8, n_group=8, topk_group=4,
                 routed_scaling_factor=2.5, held=None,
                 first_k_dense_replace=3, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_theta=10000.0, rope_scaling=None, rms_norm_eps=1e-6,
                 router_bias_std=0.01, initializer_range=0.02,
                 dtype="float32"):
        super().__init__()
        cfg = dict(locals())
        self.layer_ids = list(range(num_hidden_layers)) if layers is None \
            else [int(l) for l in layers]
        self.param_dtype = dtype
        with _parameters_in(dtype):
            self.embed_tokens = nn.Embedding(
                vocab_size, hidden_size, weight_attr=ParamAttr(
                    initializer=_Normal(initializer_range)))
            self.layers = nn.LayerList(
                [DotsLayer(l < first_k_dense_replace, cfg)
                 for l in self.layer_ids])
            self.norm = nn.RMSNorm(hidden_size, rms_norm_eps)

    def cache_arrays(self, batch_size, max_len):
        return [jnp.zeros((batch_size, max_len, layer.mixer.page_width),
                          self.param_dtype) for layer in self.layers]

    def forward(self, input_ids, choices=None):
        """The full forward (nothing kept); `choices` (a list) gains every
        expert layer's [chosen experts [B, T, top_k], biased scores [B, T,
        n_routed_experts]]."""
        scores = None if choices is None else []
        x, _, routes = self.forward_cached(input_ids, None, None, None,
                                           scores)
        if choices is not None:
            choices += [list(pair) for pair in zip(routes, scores)]
        return x

    def forward_cached(self, input_ids, cache, positions, lengths=None,
                       scores=None):
        """`cache` None: the full forward. Returns (hidden states, the new
        pages, every expert layer's chosen experts [B, T, top_k] int32)."""
        step = cache is not None and lengths is None
        live = None if cache is None else _live_rows(
            positions, lengths, input_ids.shape[1])
        x = self.embed_tokens(input_ids)
        pages, routes = [], []
        for i, layer in enumerate(self.layers):
            x, page, experts = layer.forward_cached(
                x, None if cache is None else cache[i], positions, lengths,
                step, scores, live)
            pages.append(page)
            if experts is not None:
                routes.append(experts)
        return x, pages, routes        # the final norm is the head's


class DotsForCausalLM(nn.Layer):
    # `serving.LLMEngine` reads this: every array of `init_cache` is a page
    cache_tag = "kv_pool"

    def __init__(self, dots: DotsModel):
        super().__init__()
        self.dots = dots
        hidden, vocab = (dots.embed_tokens.embedding_dim,
                         dots.embed_tokens.num_embeddings)
        with _parameters_in(dots.param_dtype):
            self.lm_head = _linear(hidden, vocab)

    def forward(self, input_ids, at=None, choices=None):
        """Logits [B, T, vocab]; with `at` [B] or [B, P], those of the
        positions `at[b]` only. `choices` as `DotsModel.forward`'s."""
        h = self.dots(input_ids, choices)
        return _logits(self.dots.norm, self.lm_head,
                       h if at is None else _rows_at(h, at))

    def init_cache(self, batch_size, max_len=None, dtype=None):
        """The pages are held in the weights' dtype whatever `dtype` (a
        recurrent state's, in the contract) says."""
        return [Tensor(a) for a in self.dots.cache_arrays(batch_size,
                                                          max_len or 1)]

    def forward_cached(self, input_ids, cache, positions, lengths=None):
        """Returns (logits, the new pages and AFTER them what the call
        reports: every expert layer's chosen experts [B, T, top_k] int32),
        as `LingForCausalLM.forward_cached`: `serving.LLMEngine`'s programs
        hand the choice out after the pool, so whoever holds the sums to a
        reference has the choice the timed program made."""
        h, pages, routes = self.dots.forward_cached(
            input_ids, list(cache), positions, lengths)
        last = h[:, 0] if lengths is None else _rows_at(h, lengths - 1)
        return _logits(self.dots.norm, self.lm_head, last), pages + routes
