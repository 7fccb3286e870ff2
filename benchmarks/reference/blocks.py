"""The plain reference: the two models' forward passes in straightforward
float32 `jax.numpy`, independent of `paddle_tpu` (nothing of it is imported).

One post-LN transformer block (`block`) serves both an encoder with the
MLM + NSP pretraining loss (`ernie_pretrain_loss`) and a causal decoder
(`gpt_logits`). Layers run under `lax.scan` over stacked weights, so the
reference compiles in seconds at any depth. Every matmul runs at
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul is
otherwise done in bf16 passes.

Weights come in as a flat `{parameter name: array}` dict under the names
the built model gives them (`named_parameters()`); the names are data here,
not an import.

Departures from the published models, shared with the program under test
and stated in the configuration files: the block is post-LN for the decoder
too (GPT-2 is pre-LN), GELU is the exact erf form.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
_LAYER_KEYS = ("attention.qkv.weight", "attention.qkv.bias",
               "attention.out.weight", "attention.out.bias",
               "norm1.weight", "norm1.bias",
               "mlp.fc1.weight", "mlp.fc1.bias",
               "mlp.fc2.weight", "mlp.fc2.bias",
               "norm2.weight", "norm2.bias")


def layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def block(x, w, heads: int, causal: bool):
    """x [B, S, H]; w: one layer's weights keyed by `_LAYER_KEYS`.
    Linear weights are [in, out]. qkv's output is laid out [3, heads, d]."""
    b, s, h = x.shape
    d = h // heads
    qkv = (x @ w["attention.qkv.weight"] + w["attention.qkv.bias"]
           ).reshape(b, s, 3, heads, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h)
    attn = ctx @ w["attention.out.weight"] + w["attention.out.bias"]
    x = layer_norm(x + attn, w["norm1.weight"], w["norm1.bias"])
    ff = jax.nn.gelu(x @ w["mlp.fc1.weight"] + w["mlp.fc1.bias"],
                     approximate=False)
    ff = ff @ w["mlp.fc2.weight"] + w["mlp.fc2.bias"]
    return layer_norm(x + ff, w["norm2.weight"], w["norm2.bias"])


def stack_layers(named: dict, prefix: str, n_layers: int) -> dict:
    """`{prefix}.{i}.{key}` for i < n_layers -> {key: [n_layers, ...]}."""
    return {k: jnp.stack([jnp.asarray(named[f"{prefix}.{i}.{k}"], jnp.float32)
                          for i in range(n_layers)]) for k in _LAYER_KEYS}


def run_stack(x, layers: dict, heads: int, causal: bool):
    def body(x, w):
        return block(x, w, heads, causal), None
    return jax.lax.scan(body, x, layers)[0]


def _cross_entropy(logits, labels):
    """Mean over rows of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _f32(named, name):
    return jnp.asarray(named[name], jnp.float32)


def ernie_pretrain_loss(named: dict, ids, nsp_labels, *, n_layers: int,
                        heads: int, prefix: str = "ernie"):
    """ErnieForPretraining's objective: MLM cross-entropy over every
    position (labels = `ids`, head weight-tied to the word embedding, no
    head bias) plus NSP cross-entropy on the pooled first position. Token
    type 0 everywhere, no padding mask, dropout 0."""
    with jax.default_matmul_precision("highest"):
        e = prefix + ".embeddings."
        s = ids.shape[1]
        word = _f32(named, e + "word_embeddings.weight")
        x = (word[ids] + _f32(named, e + "position_embeddings.weight")[:s]
             + _f32(named, e + "token_type_embeddings.weight")[0])
        x = layer_norm(x, _f32(named, e + "layer_norm.weight"),
                       _f32(named, e + "layer_norm.bias"))
        x = run_stack(x, stack_layers(named, prefix + ".layers", n_layers),
                      heads, causal=False)
        pooled = jnp.tanh(x[:, 0] @ _f32(named, prefix + ".pooler.weight")
                          + _f32(named, prefix + ".pooler.bias"))
        t = jax.nn.gelu(x @ _f32(named, "transform.weight")
                        + _f32(named, "transform.bias"), approximate=False)
        t = layer_norm(t, _f32(named, "transform_norm.weight"),
                       _f32(named, "transform_norm.bias"))
        logits = t @ word.T
        nsp = pooled @ _f32(named, "nsp.weight") + _f32(named, "nsp.bias")
        return (_cross_entropy(logits.reshape(-1, logits.shape[-1]),
                               ids.reshape(-1))
                + _cross_entropy(nsp, nsp_labels))


def gpt_logits(named: dict, ids, *, n_layers: int, heads: int,
               prefix: str = "gpt"):
    """GPTForCausalLM's full forward: logits [B, S, V] for tokens [B, S].
    Causal, so right-padding a row changes nothing to its left."""
    with jax.default_matmul_precision("highest"):
        e = prefix + ".embeddings."
        word = _f32(named, e + "word_embeddings.weight")
        x = word[ids] + _f32(named, e + "position_embeddings.weight")[
            :ids.shape[1]]
        x = run_stack(x, stack_layers(named, prefix + ".layers", n_layers),
                      heads, causal=True)
        x = layer_norm(x, _f32(named, prefix + ".final_norm.weight"),
                       _f32(named, prefix + ".final_norm.bias"))
        return x @ word.T
