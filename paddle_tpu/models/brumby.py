"""Brumby: a Qwen3-shaped decoder whose attention is power retention.

Manifest AI's Brumby-14B-Base (retrained from Qwen3-14B-Base) keeps the
Qwen3 block — pre-RMSNorm, grouped query heads, per-head RMSNorm of q and
k, rotary positions, SwiGLU, an untied head, no biases — and replaces
softmax attention with power retention of degree 2
(`kernels/power_retention.py`): a gate projection gives one log-gate a
key/value head, and what a sequence has read is a fixed-size state a
key/value head instead of keys and values a position.

    u    = RMSNorm(x)
    q, k = RoPE(RMSNorm_d(W_q u)), RoPE(RMSNorm_d(W_k u));  v = W_v u
    l    = logsigmoid(W_g u + b_g)                  one scalar a kv head
    x'   = x + W_o retention(q, k, v, l)
    x''  = x' + W_down(silu(W_gate m) * (W_up m)),  m = RMSNorm(x')

The cache contract `serving.LLMEngine` asks of a model (`cache_tag` names
the pool in the memory census: a recurrent state is `state_pool`):

- `init_cache(batch, max_len, dtype)` -> a flat list of arrays with the
  sequence (the engine's slot) on axis 0: per layer `S [B, G, d, R]` and
  `z [B, G, R]`. `max_len` changes nothing: a state does not grow.
- `forward_cached(tokens, cache, positions, lengths=None)` ->
  `(logits [B, vocab] float32, new_cache)`. With `lengths` it reads a
  prompt `[B, T]` from an EMPTY state (`cache` is not read), positions >=
  `lengths` folded into nothing, and gives the logits of position
  `lengths - 1`; without, `tokens` is `[B, 1]`, one step through `cache`
  at rotary position `positions`. Logits for every position are never
  built: the head runs on one hidden state a row.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import nn
from ..framework.param_attr import ParamAttr
from ..kernels import power_retention as _pr
from ..nn import functional as F
from ..nn import initializer as I
from ..ops._dispatch import run_op
from ..ops.creation import arange
from ..ops.manipulation import reshape, unsqueeze
from ._decoder import SwiGLU as BrumbyMLP
from ._decoder import _linear, _logits, _Normal, _parameters_in, _rows_at


class BrumbyRetention(nn.Layer):
    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 rms_norm_eps, rope_theta, gate_bias):
        super().__init__()
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.rope_theta = head_dim, rope_theta
        self.q_proj = _linear(hidden_size, num_heads * head_dim)
        self.k_proj = _linear(hidden_size, num_kv_heads * head_dim)
        self.v_proj = _linear(hidden_size, num_kv_heads * head_dim)
        # the gate's bias starts where a state remembers hundreds of tokens
        self.g_proj = _linear(
            hidden_size, num_kv_heads,
            bias_attr=ParamAttr(initializer=I.Constant(gate_bias)))
        self.o_proj = _linear(num_heads * head_dim, hidden_size)
        self.q_norm = nn.RMSNorm(head_dim, rms_norm_eps)
        self.k_norm = nn.RMSNorm(head_dim, rms_norm_eps)

    def _project(self, u, positions):
        """u [B, T, hidden], positions [B, T] -> q [B, T, H, d], k, v
        [B, T, G, d], log-gate [B, T, G] float32."""
        b, t = u.shape[0], u.shape[1]
        d = self.head_dim
        q = self.q_norm(reshape(self.q_proj(u), [b, t, self.num_heads, d]))
        k = self.k_norm(reshape(self.k_proj(u), [b, t, self.num_kv_heads, d]))
        v = reshape(self.v_proj(u), [b, t, self.num_kv_heads, d])
        q = F.rotary_embedding(q, positions, self.rope_theta)
        k = F.rotary_embedding(k, positions, self.rope_theta)
        log_g = F.log_sigmoid(self.g_proj(u).astype("float32"))
        return q, k, v, log_g

    def _out(self, y):
        return self.o_proj(reshape(y, [y.shape[0], y.shape[1], -1]))

    def forward(self, u):
        pos = unsqueeze(arange(u.shape[1], dtype="int32"), 0)
        y = run_op(lambda *a: _pr.power_retention_chunked(*a)[0],
                   list(self._project(u, pos)), "power_retention")
        return self._out(y)

    def forward_cached(self, u, state, z, positions, lengths=None):
        """One layer of `BrumbyForCausalLM.forward_cached`; returns
        (out, state, z)."""
        t = u.shape[1]
        pos = unsqueeze(positions, 1) + unsqueeze(arange(t, dtype="int32"), 0)
        q, k, v, log_g = self._project(u, pos)
        if lengths is not None:
            # a prompt starts from an empty state: what came in is not read
            def prompt(q, k, v, log_g, lengths):
                y, (s1, z1) = _pr.power_retention_chunked(q, k, v, log_g,
                                                          lengths)
                return y, s1, z1
            y, state, z = run_op(prompt, [q, k, v, log_g, lengths],
                                 "power_retention_prompt")
        else:
            if t != 1:
                raise ValueError("a step through a recurrent state is one "
                                 f"token wide, got {t}")

            def step(q, k, v, log_g, s0, z0):
                y, (s1, z1) = _pr.power_retention_step(
                    q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], (s0, z0))
                return y[:, None], s1, z1
            y, state, z = run_op(step, [q, k, v, log_g, state, z],
                                 "power_retention_step")
        return self._out(y), state, z


class BrumbyLayer(nn.Layer):
    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 intermediate_size, rms_norm_eps, rope_theta, gate_bias):
        super().__init__()
        self.input_norm = nn.RMSNorm(hidden_size, rms_norm_eps)
        self.retention = BrumbyRetention(hidden_size, num_heads, num_kv_heads,
                                         head_dim, rms_norm_eps, rope_theta,
                                         gate_bias)
        self.post_norm = nn.RMSNorm(hidden_size, rms_norm_eps)
        self.mlp = BrumbyMLP(hidden_size, intermediate_size)

    def forward(self, x):
        x = x + self.retention(self.input_norm(x))
        return x + self.mlp(self.post_norm(x))

    def forward_cached(self, x, state, z, positions, lengths=None):
        a, state, z = self.retention.forward_cached(
            self.input_norm(x), state, z, positions, lengths)
        x = x + a
        return x + self.mlp(self.post_norm(x)), state, z


class BrumbyModel(nn.Layer):
    def __init__(self, vocab_size=151936, hidden_size=5120, num_layers=40,
                 num_heads=40, num_kv_heads=8, head_dim=128,
                 intermediate_size=17408, rms_norm_eps=1e-6,
                 rope_theta=1000000.0, gate_bias=5.0, initializer_range=0.02,
                 dtype="float32"):
        super().__init__()
        with _parameters_in(dtype):
            self.embed_tokens = nn.Embedding(
                vocab_size, hidden_size, weight_attr=ParamAttr(
                    initializer=_Normal(initializer_range)))
            self.layers = nn.LayerList([
                BrumbyLayer(hidden_size, num_heads, num_kv_heads, head_dim,
                            intermediate_size, rms_norm_eps, rope_theta,
                            gate_bias)
                for _ in range(num_layers)])
            self.norm = nn.RMSNorm(hidden_size, rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return x                       # the final norm is the head's

    def init_cache(self, batch_size, max_len=None, dtype="float32"):
        from ..core.tensor import Tensor
        r = self.layers[0].retention
        rows = _pr.state_rows(r.head_dim)
        cache = []
        for _ in self.layers:
            cache += [Tensor(jnp.zeros((batch_size, r.num_kv_heads,
                                        r.head_dim, rows), dtype)),
                      Tensor(jnp.zeros((batch_size, r.num_kv_heads, rows),
                                       dtype))]
        return cache

    def forward_cached(self, input_ids, cache, positions, lengths=None):
        x = self.embed_tokens(input_ids)
        new = []
        for i, layer in enumerate(self.layers):
            x, state, z = layer.forward_cached(
                x, cache[2 * i], cache[2 * i + 1], positions, lengths)
            new += [state, z]
        return x, new


class BrumbyForCausalLM(nn.Layer):
    cache_tag = "state_pool"   # `serving.LLMEngine` reads this: see above

    def __init__(self, brumby: BrumbyModel):
        super().__init__()
        self.brumby = brumby
        hidden, vocab = (brumby.embed_tokens.embedding_dim,
                         brumby.embed_tokens.num_embeddings)
        with _parameters_in(brumby.norm.weight.dtype):
            self.lm_head = _linear(hidden, vocab)

    def forward(self, input_ids, at=None):
        """Logits [B, T, vocab]; with `at` [B] or [B, P], those of the
        positions `at[b]` only, [B, vocab] or [B, P, vocab]."""
        h = self.brumby(input_ids)
        return _logits(self.brumby.norm, self.lm_head,
                       h if at is None else _rows_at(h, at))

    def init_cache(self, batch_size, max_len=None, dtype="float32"):
        return self.brumby.init_cache(batch_size, max_len, dtype)

    def forward_cached(self, input_ids, cache, positions, lengths=None):
        h, cache = self.brumby.forward_cached(input_ids, cache, positions,
                                              lengths)
        last = h[:, 0] if lengths is None else _rows_at(h, lengths - 1)
        return _logits(self.brumby.norm, self.lm_head, last), cache
