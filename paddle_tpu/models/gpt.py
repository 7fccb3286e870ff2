"""GPT-style decoder LM with hybrid-parallel wiring (config 5 engine model).

Reference parity: the fleetx/PaddleNLP GPT consumed by the reference's
hybrid-parallel examples (`fleet/meta_parallel` tests use gpt runners).
Supports: tensor parallel (mp layers), sequence parallel (ring attention),
and a PipelineLayer factory for pipeline parallelism.
"""
from __future__ import annotations

from .. import nn
from ..ops.creation import arange
from ..ops.manipulation import reshape, unsqueeze
from .ernie import ErnieLayer


class GPTEmbeddings(nn.Layer):
    def __init__(self, vocab_size, hidden_size, max_seq_len=1024, dropout=0.1,
                 use_mp=False):
        super().__init__()
        if use_mp:
            from ..parallel.mp_layers import VocabParallelEmbedding
            self.word_embeddings = VocabParallelEmbedding(vocab_size, hidden_size)
        else:
            self.word_embeddings = nn.Embedding(vocab_size, hidden_size)
        self.position_embeddings = nn.Embedding(max_seq_len, hidden_size)
        self.dropout = nn.Dropout(dropout)

    def forward(self, input_ids, position_offset=None):
        pos = unsqueeze(arange(input_ids.shape[1], dtype="int32"), 0)
        if position_offset is not None:
            # cached decode: [B] tokens-already-seen offsets the block's
            # position ids so step N embeds position N, not 0
            pos = pos + reshape(position_offset, [-1, 1])
        return self.dropout(self.word_embeddings(input_ids)
                            + self.position_embeddings(pos))


class GPTModel(nn.Layer):
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None, max_seq_len=1024,
                 dropout=0.1, use_mp=False, use_sp=False):
        super().__init__()
        intermediate_size = intermediate_size or 4 * hidden_size
        self.embeddings = GPTEmbeddings(vocab_size, hidden_size, max_seq_len,
                                        dropout, use_mp)
        self.layers = nn.LayerList([
            ErnieLayer(hidden_size, num_heads, intermediate_size, dropout,
                       use_mp, use_sp, causal=True)
            for _ in range(num_layers)])
        self.final_norm = nn.LayerNorm(hidden_size)

    def forward(self, input_ids):
        x = self.embeddings(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.final_norm(x)

    def init_kv_cache(self, batch_size, max_len, dtype="float32"):
        """Fresh zero KV pages for forward_cached: one (k, v) pair per
        layer, each [batch, max_len, num_heads * head_dim] (a position is
        one contiguous row: `ErnieSelfAttention.forward_cached`). dtype
        "int8" builds the quantized-KV pages (scales start as None and
        are computed by the first forward_cached call)."""
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        attn = self.layers[0].attention
        shape = (batch_size, max_len, attn.num_heads * attn.head_dim)
        return [(Tensor(jnp.zeros(shape, dtype=dtype)),
                 Tensor(jnp.zeros(shape, dtype=dtype)))
                for _ in self.layers]

    def forward_cached(self, input_ids, past_kv, positions, kv_scales=None):
        """Prefill/decode step over explicit KV-cache carries.

        input_ids [B, T]; past_kv: list over layers of (k, v) fixed-shape
        pages [B, L, nh*hd]; positions [B] int32 tokens-already-cached
        per row (also the position-embedding offset). kv_scales: list of
        (k_scale, v_scale) [B] pairs for int8 pages, or None.
        Returns (hidden, new_past_kv, new_kv_scales)."""
        x = self.embeddings(input_ids, position_offset=positions)
        new_kv, new_scales = [], []
        for i, layer in enumerate(self.layers):
            ks, vs = (None, None) if kv_scales is None else kv_scales[i]
            k, v = past_kv[i]
            x, k, v, ks, vs = layer.forward_cached(x, k, v, positions, ks, vs)
            new_kv.append((k, v))
            new_scales.append((ks, vs))
        return self.final_norm(x), new_kv, new_scales


class GPTForCausalLM(nn.Layer):
    def __init__(self, gpt: GPTModel):
        super().__init__()
        self.gpt = gpt

    def forward(self, input_ids):
        from ..ops.math import matmul
        h = self.gpt(input_ids)
        w = self.gpt.embeddings.word_embeddings.weight
        return matmul(h, w, transpose_y=True)

    def forward_cached(self, input_ids, past_kv, positions, kv_scales=None):
        """Cached-attention LM step: (logits, new_past_kv, new_kv_scales).
        Weight-tied head over GPTModel.forward_cached — a decode step
        ([B, 1] input) is one-token work against the cache pages."""
        from ..ops.math import matmul
        h, new_kv, new_scales = self.gpt.forward_cached(
            input_ids, past_kv, positions, kv_scales)
        w = self.gpt.embeddings.word_embeddings.weight
        return matmul(h, w, transpose_y=True), new_kv, new_scales


class GPTPretrainingCriterion(nn.Layer):
    """Shifted-LM loss; vocab-parallel CE when logits are sharded."""

    def __init__(self, use_parallel_ce=False):
        super().__init__()
        if use_parallel_ce:
            from ..parallel.mp_layers import ParallelCrossEntropy
            self.ce = ParallelCrossEntropy()
            self._parallel = True
        else:
            self.ce = nn.CrossEntropyLoss()
            self._parallel = False

    def forward(self, logits, labels):
        shifted = logits[:, :-1]
        tgt = labels[:, 1:]
        if self._parallel:
            return self.ce(shifted, unsqueeze(tgt, -1)).mean()
        b, s, v = shifted.shape
        return self.ce(reshape(shifted, [b * s, v]), reshape(tgt, [b * s]))


def gpt_pipeline_layer(vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
                       num_stages=2, use_mp=False, dropout=0.1, max_seq_len=1024,
                       num_virtual_pipeline_stages=1):
    """PipelineLayer build of GPT for pp training (reference pp_layers pattern)."""
    from ..parallel.pp_layers import LayerDesc, PipelineLayer

    class _EmbedStage(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = GPTEmbeddings(vocab_size, hidden_size, max_seq_len, dropout,
                                     use_mp)

        def forward(self, ids):
            return self.emb(ids)

    class _HeadStage(nn.Layer):
        def __init__(self):
            super().__init__()
            self.norm = nn.LayerNorm(hidden_size)
            self.lm_head = nn.Linear(hidden_size, vocab_size, bias_attr=False)

        def forward(self, x):
            return self.lm_head(self.norm(x))

    descs = [LayerDesc(_EmbedStage)]
    for _ in range(num_layers):
        descs.append(LayerDesc(ErnieLayer, hidden_size, num_heads, 4 * hidden_size,
                               dropout, use_mp, False, True))
    descs.append(LayerDesc(_HeadStage))
    return PipelineLayer(descs, num_stages=num_stages,
                         loss_fn=GPTPretrainingCriterion(),
                         num_virtual_pipeline_stages=num_virtual_pipeline_stages)


# configs
def gpt2_small(**kw):
    return GPTModel(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt2_medium(**kw):
    return GPTModel(hidden_size=1024, num_layers=24, num_heads=16, **kw)


def gpt_10b(**kw):
    return GPTModel(hidden_size=4096, num_layers=48, num_heads=64,
                    max_seq_len=2048, **kw)
