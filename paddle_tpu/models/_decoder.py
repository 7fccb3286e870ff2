"""What the pre-norm decoders with a declared cache share
(`models/brumby.py`, `models/ling.py`, `models/dots.py`): parameters drawn
straight into their dtype, the SwiGLU feed-forward, the latent-attention
mixer (Ling's sixth layer, every layer of Dots), the untied head on one
hidden state a row."""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as rnd
from ..core.dtype import get_default_dtype, set_default_dtype
from ..framework.param_attr import ParamAttr
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..ops._dispatch import nondiff_op, run_op
from ..ops.creation import arange
from ..ops.manipulation import concat, reshape, unsqueeze


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class _Normal(I.Initializer):
    """N(0, std) (std None: Xavier over the last two axes, sqrt(2 / (fan_in
    + fan_out)), as `nn.Linear` starts) drawn by ONE program straight into
    the parameter's dtype. The eager initializers hold up to three float32
    copies of what they draw: 9 GB for a [151936, 5120] table that is 1.6
    GB in bfloat16, beside the rest of a model that fills half the chip."""

    def __init__(self, std=None):
        self.std = std

    def _generate(self, shape, dtype):
        std = self.std or math.sqrt(2.0 / (shape[-2] + shape[-1]))
        return _draw(rnd.next_key(), tuple(shape), jnp.dtype(dtype), std)


def _linear(n_in, n_out, bias_attr=False):
    return nn.Linear(n_in, n_out, weight_attr=ParamAttr(initializer=_Normal()),
                     bias_attr=bias_attr)


@contextlib.contextmanager
def _parameters_in(dtype):
    """Layers built inside create their parameters in `dtype` (a 14B
    model built in float32 and cast would not fit beside itself)."""
    was = get_default_dtype()
    set_default_dtype(dtype)
    try:
        yield
    finally:
        set_default_dtype(was)


class SwiGLU(nn.Layer):
    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.gate_proj = _linear(hidden_size, intermediate_size)
        self.up_proj = _linear(hidden_size, intermediate_size)
        self.down_proj = _linear(intermediate_size, hidden_size)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LatentAttention(nn.Layer):
    """Multi-head latent attention (DeepSeek-V2 / V3), u the normed input:

        q = W_q u                      or, with `q_lora_rank`, the low-rank
        q = W_qb RMSNorm(W_qa u)       query of DeepSeek-V3
        q^h = [q_nope^h; q_rope^h];  [c; kr] = W_dkv u;  c <- RMSNorm(c)
        kr and q_rope rotated (interleaved pairs; one kr a position)
        [k_nope^h; v^h] = W_ukv^h c;   scores over nope + rope, causal
        y = W_o concat_h(sum_j a_j^h v_j^h)

    What a sequence keeps is the row [c; kr] a position (`F.latent_*`: the
    prompt expanded through a flash kernel, a step absorbed into the query
    and read off the page by `kernels/mla_decode.py`). `rope_scaling` (a
    published config's group, type yarn) blends the rotary frequencies
    (`F.yarn_inv_freq`), scales cos and sin by m(mscale) / m(mscale_all_dim)
    and the scores by m(mscale_all_dim)^2."""

    def __init__(self, hidden_size, num_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, rope_theta, rms_norm_eps,
                 q_lora_rank=None, rope_scaling=None):
        super().__init__()
        self.num_heads, self.latent = num_heads, kv_lora_rank
        self.nope, self.rope, self.v_dim = (qk_nope_head_dim,
                                            qk_rope_head_dim, v_head_dim)
        self.rope_theta = rope_theta
        self.scale = 1.0 / math.sqrt(self.nope + self.rope)
        self.inv_freq, self.rope_mscale = None, 1.0
        if rope_scaling:
            if rope_scaling.get("type", "yarn") != "yarn":
                raise ValueError("LatentAttention: rope_scaling of type "
                                 f"{rope_scaling['type']!r} is not built")
            factor = rope_scaling["factor"]
            self.inv_freq = F.yarn_inv_freq(
                self.rope, rope_theta, factor,
                rope_scaling["original_max_position_embeddings"],
                rope_scaling.get("beta_fast", 32),
                rope_scaling.get("beta_slow", 1))
            all_dim = F.yarn_mscale(factor,
                                    rope_scaling.get("mscale_all_dim", 0))
            self.rope_mscale = F.yarn_mscale(
                factor, rope_scaling.get("mscale", 1)) / all_dim
            self.scale *= all_dim * all_dim
        # a page's row, [latent; rotary key], in whole 128 lanes: a minor
        # axis of 576 is one the TPU holds in another order than it reads
        # (two copies of the page a step, 1.9 of 12.1 ms: PERF.md, PR 33)
        self.page_width = -(-(kv_lora_rank + qk_rope_head_dim) // 128) * 128
        width = num_heads * (self.nope + self.rope)
        if q_lora_rank:
            self.q_down = _linear(hidden_size, q_lora_rank)
            self.q_norm = nn.RMSNorm(q_lora_rank, rms_norm_eps)
            self.q_up = _linear(q_lora_rank, width)
        else:
            self.q_proj = _linear(hidden_size, width)
        self.kv_down = _linear(hidden_size, kv_lora_rank + self.rope)
        self.kv_norm = nn.RMSNorm(kv_lora_rank, rms_norm_eps)
        self.kv_up = _linear(kv_lora_rank, num_heads * (self.nope + self.v_dim))
        self.o_proj = _linear(num_heads * v_head_dim, hidden_size)

    def _rotate(self, x, positions):
        x = F.rotary_embedding(x, positions, self.rope_theta,
                               interleaved=True, inv_freq=self.inv_freq)
        return x if self.rope_mscale == 1.0 else x * self.rope_mscale

    def _project(self, u, positions):
        """u [B, T, hidden], positions [B, T] -> q_nope [B, T, H, nope],
        q_rope [B, T, H, rope] rotated, the page's rows [B, T, latent +
        rope] ([normed latent; rotated key])."""
        b, t = u.shape[0], u.shape[1]
        q = self.q_up(self.q_norm(self.q_down(u))) \
            if hasattr(self, "q_down") else self.q_proj(u)
        q = reshape(q, [b, t, self.num_heads, self.nope + self.rope])
        q_rope = self._rotate(q[..., self.nope:], positions)
        down = self.kv_down(u)
        k_rope = self._rotate(unsqueeze(down[..., self.latent:], 2),
                              positions)
        rows = concat([self.kv_norm(down[..., :self.latent]),
                       reshape(k_rope, [b, t, self.rope])], axis=-1)
        return q[..., :self.nope], q_rope, rows

    def _out(self, y):
        return self.o_proj(reshape(y, y.shape[:2] + [-1]))

    def forward_cached(self, u, page, positions, lengths, step):
        """`step` false: a prompt [B, T, hidden] from an empty page
        (`lengths` [B] or None); true: one token [B, 1, hidden] through
        `page`. A prompt returns its rows [B, T, latent + rope] padded with
        zeros to the page it was given (`page` names the length only), a
        step the page with its row written at `positions` [B]. Returns
        (out, page)."""
        t = u.shape[1]
        start = positions if positions is not None else \
            Tensor(jnp.zeros((u.shape[0],), jnp.int32))
        pos = unsqueeze(start, 1) + unsqueeze(arange(t, dtype="int32"), 0)
        q_nope, q_rope, rows = self._project(u, pos)
        if not step:
            y = F.latent_attention_prompt(
                q_nope, q_rope, rows[..., :self.latent],
                rows[..., self.latent:], self.kv_up.weight, lengths,
                scale=self.scale)
            if page is not None:
                # the slot's page whole: what follows the prompt is zeros
                rows = run_op(
                    lambda r, p: jnp.pad(r, ((0, 0), (0, p.shape[1]
                                                      - r.shape[1]),
                                             (0, p.shape[2] - r.shape[2]))),
                    [rows, page], "latent_page_fill")
            return self._out(y), rows
        if t != 1:
            raise ValueError("a decode step through a latent page is one "
                             f"token wide, got {t}")
        page = F.latent_page_write(page, rows[:, 0], positions)
        y = F.latent_attention_decode(q_nope[:, 0], q_rope[:, 0], page,
                                      positions, self.kv_up.weight,
                                      scale=self.scale)
        return self._out(unsqueeze(y, 1)), page


def _logits(norm, lm_head, h):
    """Final norm and the untied head; logits in float32."""
    return run_op(
        lambda a, w: jnp.matmul(a, w, preferred_element_type=jnp.float32),
        [norm(h), lm_head.weight], "lm_head")


def _rows_at(h, index):
    """h [B, T, hidden], index [B] or [B, P] -> h[b, index[b]] as
    [B, hidden] or [B, P, hidden]."""
    def f(a, i):
        i = i.astype(jnp.int32)
        if i.ndim == 1:
            return jnp.take_along_axis(a, i[:, None, None], axis=1)[:, 0]
        return jnp.take_along_axis(a, i[..., None], axis=1)
    return run_op(f, [h, index], "llm_last_hidden")


def _live_rows(positions, lengths, t):
    """The rows [B, T] of a cached call that carry a token anyone reads,
    bool: of a step (`lengths` None, T 1) those at a position past 0 (a
    sequence's first step is at its prompt's length, at least 1, so a row
    at position 0 is a free slot's or one left out), of a prompt those
    before the row's length (the rest is the bucket's padding)."""
    if lengths is None:
        return nondiff_op(lambda p: (p > 0)[:, None], [positions])
    return nondiff_op(
        lambda n: jnp.arange(t, dtype=jnp.int32)[None, :] < n[:, None],
        [lengths])
