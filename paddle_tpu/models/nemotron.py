"""Nemotron-H (`nemotron_h`): a hybrid stack in which every block is ONE
part.

NVIDIA's Nemotron 3 Nano: pre-RMSNorm blocks, each a Mamba-2 layer (M), a
routed expert layer (E) or grouped-query attention (*), as the published
`hybrid_override_pattern` says by index; an untied head after a final
RMSNorm. Block l (the published index):

    x' = x + Part_l(RMSNorm(x))

Mamba-2 (u the normed input; H heads of P, G groups of B and C rows of N;
head h reads group h // (H / G)):

    [z | xBC | dt] = W_in u
    xBC = silu(conv4(xBC) + b_conv)           causal, depthwise
    x, B, C = split(xBC)                      H P | G N | G N
    Delta = softplus(dt + dt_bias);  A = -exp(A_log)
    S_h <- exp(Delta_h A_h) S_h + Delta_h x_h B_g^T;   y_h = S_h C_g + D_h x_h
    out = W_out (w * RMSNorm_groups(y * silu(z)))     over G groups of H P / G

(`kernels/ssd.py`: the chunked prompt form and the one-token step). The
expert layer is `nn.RoutedExperts(activation="relu2")`: the sigmoid router
with a bias for the choice over experts W_d relu(W_u m)^2 and one shared
expert of that kind. Attention: q heads grouped over fewer K/V heads,
causal, 1 / sqrt(head_dim), no bias and NO rotary position (the published
model takes positions from its Mamba layers).

A model may hold a part of the depth (`layers`: the published indices it
holds), a part of the experts (`held`) and a slice of the vocabulary
(`vocab_size` is the rows held): the share of one chip in a stated
deployment.

The cache contract `serving.LLMEngine` asks of a model, as
`models/ling.py`'s: `init_cache` -> a flat list, per Mamba layer the state
`[B, H, P, N]` float32 and the convolution's rows `[B, 3, H P + 2 G N]`
(`state_pool`), per attention layer a K page and a V page `[B, max_len,
kv_heads * head_dim]` (`kv_pool`); `cache_tag` one tag an array;
`forward_cached(tokens, cache, positions, lengths=None)`: with `lengths` a
prompt from an EMPTY cache, else one token a row through `cache`; after
the cache's arrays what the call reports, an expert layer each: the
experts chosen `[B, T, top_k]`. A step's row at position 0 carries no
sequence and its output is unspecified, as is a prompt's row past its
length: the expert layers route those rows nowhere (`_decoder._live_rows`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as rnd
from ..core.tensor import Tensor
from ..framework.param_attr import ParamAttr
from ..kernels import decode_attention as _da
from ..kernels import ssd as _ssd
from ..nn import functional as F
from ..nn import initializer as I
from ..ops._dispatch import run_op
from ..ops.manipulation import reshape
from ._decoder import (
    _linear, _live_rows, _logits, _Normal, _parameters_in, _rows_at,
)

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def layer_kinds(pattern: str, layers):
    """The kinds of the published layer indices `layers`."""
    kinds = []
    for l in layers:
        if pattern[l] not in KINDS:
            raise ValueError(f"layer {l}: kind {pattern[l]!r} is not built")
        kinds.append(KINDS[pattern[l]])
    return kinds


class _MambaInit(I.Initializer):
    """Mamba-2's own start for its per-head parameters, from the seed:
    `A_log` = log A with A ~ U[1, 16]; `dt_bias` the inverse softplus of a
    Delta drawn log-uniform in [dt_min, dt_max] and floored; `D` = 1."""

    def __init__(self, kind, dt_min=1e-3, dt_max=0.1, dt_floor=1e-4):
        self.kind, self.dt = kind, (dt_min, dt_max, dt_floor)

    def _generate(self, shape, dtype):
        if self.kind == "D":
            return jnp.ones(shape, dtype)
        u = jax.random.uniform(rnd.next_key(), shape, jnp.float32)
        if self.kind == "A_log":
            return jnp.log(1.0 + 15.0 * u).astype(dtype)
        lo, hi, floor = self.dt
        dt = jnp.maximum(jnp.exp(u * (math.log(hi) - math.log(lo))
                                 + math.log(lo)), floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _scan_inputs(y, dt, dt_bias, a_log, heads, head_dim, groups, state):
    """y [..., conv_dim] after the convolution (float32), dt [..., H] -> x
    [..., H, P], Delta [..., H], A [H], B, C [..., G, N]."""
    y = jax.nn.silu(y)
    lead, inner, gn = y.shape[:-1], heads * head_dim, groups * state
    x = y[..., :inner].reshape(lead + (heads, head_dim))
    b = y[..., inner:inner + gn].reshape(lead + (groups, state))
    c = y[..., inner + gn:].reshape(lead + (groups, state))
    delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    return x, delta, -jnp.exp(a_log), b, c


class NemotronMamba2(nn.Layer):
    def __init__(self, hidden_size, num_heads, head_dim, n_groups,
                 state_size, conv_kernel, chunk_size, eps, time_step_min,
                 time_step_max, time_step_floor):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.n_groups, self.state_size = n_groups, state_size
        self.chunk, self.eps = chunk_size, eps
        self.inner = num_heads * head_dim
        self.conv_dim = self.inner + 2 * n_groups * state_size
        self.in_proj = _linear(hidden_size, self.inner + self.conv_dim
                               + num_heads)
        # PyTorch's Conv1d start, U(+-1/sqrt(K)), has this deviation
        spread = _Normal(1.0 / math.sqrt(3.0 * conv_kernel))
        self.conv_weight = self.create_parameter(
            [conv_kernel, self.conv_dim], attr=ParamAttr(initializer=spread))
        self.conv_bias = self.create_parameter(
            [self.conv_dim], attr=ParamAttr(initializer=spread))
        steps = (time_step_min, time_step_max, time_step_floor)
        for name in ("A_log", "dt_bias", "D"):
            setattr(self, name, self.create_parameter(
                [num_heads], dtype="float32",
                attr=ParamAttr(initializer=_MambaInit(name, *steps))))
        self.norm_weight = self.create_parameter(
            [self.inner], default_initializer=I.Constant(1.0))
        self.out_proj = _linear(self.inner, hidden_size)

    def _out(self, y, z, dtype):
        """y [B, T, H, P] float32 (D x added), z [B, T, inner] -> [B, T,
        hidden]: the gate before the norm, the norm over each group."""
        g, eps = self.n_groups, self.eps

        def f(y, z, w):
            y = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
            y = y.reshape(z.shape[:-1] + (g, -1))
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                                  + eps)
            return (y.reshape(z.shape) * w.astype(jnp.float32)).astype(dtype)
        return self.out_proj(run_op(f, [y, z, self.norm_weight],
                                    "ssd_gated_norm"))

    def forward_cached(self, u, state, rows, lengths, step):
        """`step` false: a prompt [B, T, hidden] from an empty state
        (`state` and `rows` are not read; `lengths` [B] or None); true: one
        token [B, 1, hidden] through them. Returns (out, state, rows)."""
        proj = self.in_proj(u)
        z = proj[..., :self.inner]
        xbc = proj[..., self.inner:self.inner + self.conv_dim]
        dt = proj[..., self.inner + self.conv_dim:]
        params = [self.dt_bias, self.A_log, self.D]
        # what the closures below hold are numbers: the eager dispatch
        # caches a call by them (the router's balance runs eagerly)
        dims = (self.num_heads, self.head_dim, self.n_groups,
                self.state_size)
        chunk = self.chunk
        if not step:
            masked = [] if lengths is None else [lengths]
            y, rows = run_op(_ssd.conv_prompt, [xbc, self.conv_weight,
                                                self.conv_bias] + masked,
                             "ssd_conv")

            def scan(y, dt, dt_bias, a_log, d, *lengths):
                x, delta, a, b, c = _scan_inputs(y, dt, dt_bias, a_log, *dims)
                out, s = _ssd.ssd_chunked(x, delta, a, b, c, *lengths,
                                          chunk=chunk)
                return out + d[:, None] * x, s
            y, state = run_op(scan, [y, dt] + params + masked, "ssd_chunked")
        else:
            if u.shape[1] != 1:
                raise ValueError("a step through a recurrent state is one "
                                 f"token wide, got {u.shape[1]}")
            y, rows = run_op(
                lambda x, w, bias, r: _ssd.conv_step(x[:, 0], w, bias, r),
                [xbc, self.conv_weight, self.conv_bias, rows], "ssd_conv")

            def one(y, dt, dt_bias, a_log, d, s):
                x, delta, a, b, c = _scan_inputs(y, dt[:, 0], dt_bias, a_log,
                                                 *dims)
                out, s = _ssd.ssd_step(x, delta, a, b, c, s)
                return (out + d[:, None] * x)[:, None], s
            y, state = run_op(one, [y, dt] + params + [state], "ssd_step")
        return self._out(y, z, u.dtype), state, rows


def _attend_prompt(q, k, v, scale):
    """Causal attention of a prompt, q [B, T, H, d], k, v [B, T, H, d] (the
    K/V heads already repeated for their groups) -> [B, T, H, d]. A padded
    prompt needs no mask: a real row never sees a later key. On a TPU the
    flash kernel (no [H, T, T] array at any length); elsewhere dense."""
    b, t, h, d = q.shape
    if _da.engages(1, q.dtype):
        from ..kernels.flash_attention import flash_prompt_bhsd
        heads = lambda a: jnp.swapaxes(a, 1, 2).reshape(b * h, t, d)
        out = flash_prompt_bhsd(heads(q), heads(k), heads(v), scale=scale,
                                name="gqa_prefill")
        return jnp.swapaxes(out.reshape(b, h, t, d), 1, 2)
    s = jnp.einsum("bthd,bjhd->bhtj", q, k,
                   preferred_element_type=jnp.float32) * scale
    keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bhtj,bjhd->bthd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _attend_step(q, k_page, v_page, positions, scale):
    """One row a slot: q [B, H, d] over pages [B, L, kv_heads d], keys 0 ..
    positions[b]. On a TPU the ragged read of live rows
    (`decode_attention_gqa`); elsewhere dense, which is its reference."""
    if _da.engages(1, k_page.dtype):
        return _da.decode_attention_gqa(q, k_page, v_page, positions)
    b, h, d = q.shape
    kv = k_page.shape[2] // d
    k = jnp.repeat(k_page.reshape(b, -1, kv, d), h // kv, axis=2)
    v = jnp.repeat(v_page.reshape(b, -1, kv, d), h // kv, axis=2)
    s = jnp.einsum("bhd,blhd->bhl", q, k,
                   preferred_element_type=jnp.float32) * scale
    keep = jnp.arange(k.shape[1])[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhl,blhd->bhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


class NemotronAttention(nn.Layer):
    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             "K/V heads")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = head_dim
        self.scale = 1.0 / math.sqrt(head_dim)
        self.q_proj = _linear(hidden_size, num_heads * head_dim)
        self.k_proj = _linear(hidden_size, num_kv_heads * head_dim)
        self.v_proj = _linear(hidden_size, num_kv_heads * head_dim)
        self.o_proj = _linear(num_heads * head_dim, hidden_size)

    def forward_cached(self, u, k_page, v_page, positions, lengths, step):
        """`step` false: a prompt [B, T, hidden] from empty pages (the pages
        name the length only, or None); true: one token [B, 1, hidden]
        written at `positions` [B] and read back with the slot's prefix.
        Returns (out, k_page, v_page)."""
        b, t = u.shape[0], u.shape[1]
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        scale = self.scale
        q, k, v = self.q_proj(u), self.k_proj(u), self.v_proj(u)
        if not step:
            def f(q, k, v):
                grow = lambda a: jnp.repeat(a.reshape(b, t, kv, d), h // kv,
                                            axis=2)
                return _attend_prompt(q.reshape(b, t, h, d), grow(k),
                                      grow(v), scale).reshape(b, t, -1)
            y = run_op(f, [q, k, v], "gqa_prompt")
            if k_page is not None:
                # the slot's pages whole: what follows the prompt is zeros
                pad = lambda r, p: jnp.pad(r, ((0, 0), (0, p.shape[1] - t),
                                               (0, 0)))
                k_page = run_op(pad, [k, k_page], "gqa_page_fill")
                v_page = run_op(pad, [v, v_page], "gqa_page_fill")
            return self.o_proj(y), k_page, v_page
        if t != 1:
            raise ValueError("a decode step through K/V pages is one token "
                             f"wide, got {t}")
        k_page = F.latent_page_write(k_page, k[:, 0], positions)
        v_page = F.latent_page_write(v_page, v[:, 0], positions)
        y = run_op(lambda q, kp, vp, pos: _attend_step(
            q.reshape(b, h, d), kp, vp, pos.astype(jnp.int32), scale),
            [q, k_page, v_page, positions], "gqa_step")
        return self.o_proj(reshape(y, [b, 1, h * d])), k_page, v_page


class NemotronBlock(nn.Layer):
    def __init__(self, kind, cfg):
        super().__init__()
        self.kind = kind
        hidden, eps = cfg["hidden_size"], cfg["layer_norm_epsilon"]
        self.norm = nn.RMSNorm(hidden, eps)
        if kind == "mamba":
            self.mixer = NemotronMamba2(
                hidden, cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"],
                cfg["chunk_size"], eps, cfg["time_step_min"],
                cfg["time_step_max"], cfg["time_step_floor"])
        elif kind == "attention":
            self.mixer = NemotronAttention(
                hidden, cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"])
        else:
            self.mixer = nn.RoutedExperts(
                hidden, cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                cfg["num_experts_per_tok"], cfg["n_group"],
                cfg["topk_group"], cfg["routed_scaling_factor"],
                held=cfg["held"],
                shared_width=cfg["moe_shared_expert_intermediate_size"],
                weight_attr=ParamAttr(initializer=_Normal()),
                bias_attr=ParamAttr(initializer=_Normal(
                    cfg["router_bias_std"])),
                activation="relu2")

    def cache_arrays(self, batch, max_len, state_dtype, dtype):
        """The arrays this block keeps a sequence, slot on axis 0, and
        their tags."""
        m = self.mixer
        if self.kind == "mamba":
            taps = m.conv_weight.shape[0] - 1
            return [(jnp.zeros((batch, m.num_heads, m.head_dim,
                                m.state_size), state_dtype), "state_pool"),
                    (jnp.zeros((batch, taps, m.conv_dim), dtype),
                     "state_pool")]
        if self.kind == "attention":
            page = (batch, max_len, m.num_kv_heads * m.head_dim)
            return [(jnp.zeros(page, dtype), "kv_pool"),
                    (jnp.zeros(page, dtype), "kv_pool")]
        return []

    def forward_cached(self, x, cache, positions, lengths, step,
                       scores=None, live=None):
        """cache: this block's arrays. Returns (x, new arrays, the experts
        an expert block chose [B, T, top_k] or None); `scores` (a list)
        gains an expert block's biased scores [B, T, experts]; `live` [B, T]
        bool or None: the rows an expert block routes."""
        u = self.norm(x)
        if self.kind == "moe":
            y, experts, biased = self.mixer(u, return_choice=True, live=live)
            if scores is not None:
                scores.append(biased)
            return x + y, [], experts
        if self.kind == "mamba":
            y, *new = self.mixer.forward_cached(u, *cache, lengths, step)
        else:
            y, *new = self.mixer.forward_cached(u, *cache, positions,
                                                lengths, step)
        return x + y, new, None


class NemotronHModel(nn.Layer):
    def __init__(self, vocab_size=131072, hidden_size=2688,
                 num_hidden_layers=52, layers=None,
                 hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*"
                                         "EMEMEMEM*EMEMEMEME",
                 mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
                 ssm_state_size=128, conv_kernel=4, chunk_size=128,
                 time_step_min=1e-3, time_step_max=0.1, time_step_floor=1e-4,
                 num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                 moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 n_routed_experts=128, num_experts_per_tok=6, n_group=1,
                 topk_group=1, routed_scaling_factor=2.5, held=None,
                 layer_norm_epsilon=1e-5, router_bias_std=0.01,
                 initializer_range=0.02, dtype="float32"):
        super().__init__()
        cfg = dict(locals())
        if len(hybrid_override_pattern) != num_hidden_layers:
            raise ValueError(f"a pattern of {len(hybrid_override_pattern)} "
                             f"layers for {num_hidden_layers}")
        self.layer_ids = list(range(num_hidden_layers)) if layers is None \
            else [int(l) for l in layers]
        self.param_dtype = dtype
        with _parameters_in(dtype):
            self.embeddings = nn.Embedding(
                vocab_size, hidden_size, weight_attr=ParamAttr(
                    initializer=_Normal(initializer_range)))
            self.layers = nn.LayerList(
                [NemotronBlock(kind, cfg) for kind in layer_kinds(
                    hybrid_override_pattern, self.layer_ids)])
            self.norm_f = nn.RMSNorm(hidden_size, layer_norm_epsilon)

    def cache_arrays(self, batch_size, max_len, state_dtype="float32"):
        out = []
        for layer in self.layers:
            out += layer.cache_arrays(batch_size, max_len, state_dtype,
                                      self.param_dtype)
        return out

    def forward(self, input_ids, choices=None):
        """The full forward (nothing kept); `choices` (a list) gains every
        expert block's [chosen experts [B, T, top_k], biased scores [B, T,
        experts]]."""
        scores = None if choices is None else []
        x, _, routes = self.forward_cached(input_ids, None, None, None,
                                           scores)
        if choices is not None:
            choices += [list(pair) for pair in zip(routes, scores)]
        return x

    def forward_cached(self, input_ids, cache, positions, lengths=None,
                       scores=None):
        """`cache` None: the full forward. Returns (hidden states, the new
        cache, every expert block's chosen experts [B, T, top_k] int32)."""
        step = cache is not None and lengths is None
        live = None if cache is None else _live_rows(
            positions, lengths, input_ids.shape[1])
        x = self.embeddings(input_ids)
        new, routes, at = [], [], 0
        for layer in self.layers:
            n = {"mamba": 2, "attention": 2}.get(layer.kind, 0)
            mine = [None] * n if cache is None else cache[at:at + n]
            at += n
            x, kept, experts = layer.forward_cached(
                x, mine, positions, lengths, step, scores, live)
            new += kept
            if experts is not None:
                routes.append(experts)
        return x, new, routes          # the final norm is the head's


class NemotronHForCausalLM(nn.Layer):
    def __init__(self, backbone: NemotronHModel):
        super().__init__()
        self.backbone = backbone
        hidden, vocab = (backbone.embeddings.embedding_dim,
                         backbone.embeddings.num_embeddings)
        with _parameters_in(backbone.param_dtype):
            self.lm_head = _linear(hidden, vocab)
        # `serving.LLMEngine` reads this: one tag an array of `init_cache`
        self.cache_tag = tuple(tag for _, tag in backbone.cache_arrays(1, 1))

    def forward(self, input_ids, at=None, choices=None):
        """Logits [B, T, vocab]; with `at` [B] or [B, P], those of the
        positions `at[b]` only. `choices` as `NemotronHModel.forward`'s."""
        h = self.backbone(input_ids, choices)
        return _logits(self.backbone.norm_f, self.lm_head,
                       h if at is None else _rows_at(h, at))

    def init_cache(self, batch_size, max_len=None, dtype="float32"):
        """`dtype` is the recurrent states'; the convolution's rows and the
        pages are held in the weights' dtype."""
        return [Tensor(a) for a, _ in self.backbone.cache_arrays(
            batch_size, max_len or 1, dtype)]

    def forward_cached(self, input_ids, cache, positions, lengths=None):
        """Returns (logits, the new cache and AFTER it what the call
        reports: every expert block's chosen experts [B, T, top_k] int32),
        as `LingForCausalLM.forward_cached`."""
        h, new, routes = self.backbone.forward_cached(
            input_ids, list(cache), positions, lengths)
        last = h[:, 0] if lengths is None else _rows_at(h, lengths - 1)
        return _logits(self.backbone.norm_f, self.lm_head, last), \
            new + routes
