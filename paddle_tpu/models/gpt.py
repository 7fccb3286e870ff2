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
from .ernie import DECODE_BLOCK, ErnieLayer


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

    def init_cache(self, batch_size, max_len, dtype="float32"):
        """Zero K/V pages for `max_len` positions a row, as one flat list
        k0, v0, k1, v1, ...: each [batch, max_len + DECODE_BLOCK, num_heads
        * head_dim] (a position is one contiguous row:
        `ErnieSelfAttention.forward_cached`; the extra rows take the
        decode block's junk). dtype "int8" builds quantised pages, followed
        by their [batch] float32 dequantisation scales ks0, vs0, ... (ones:
        a prompt computes them)."""
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        attn = self.layers[0].attention
        n = 2 * len(self.layers)
        shape = (batch_size, max_len + DECODE_BLOCK,
                 attn.num_heads * attn.head_dim)
        cache = [Tensor(jnp.zeros(shape, dtype=dtype)) for _ in range(n)]
        if dtype == "int8":
            cache += [Tensor(jnp.ones((batch_size,), jnp.float32))
                      for _ in range(n)]
        return cache

    def forward_cached(self, input_ids, cache, positions, lengths=None):
        """`GPTForCausalLM.forward_cached` up to the head: input_ids
        [B, T] through the pages of `init_cache`, written at positions[b]
        .. positions[b] + T - 1 (positions [B] int32: tokens already cached
        a row, also the position-embedding offset). Pages are int8 when
        scales follow them; a prompt (`lengths` given) computes the scales
        from its own K/V, a decode step clips into the ones it is handed.
        Returns (hidden [B, T, H], new cache)."""
        n = 2 * len(self.layers)
        handed = lengths is None and len(cache) > n
        scales = cache[n:] if handed else [None] * n
        x = self.embeddings(input_ids, position_offset=positions)
        pages, new_scales = [], []
        for i, layer in enumerate(self.layers):
            k, v = 2 * i, 2 * i + 1
            x, *kept = layer.forward_cached(x, cache[k], cache[v], positions,
                                            scales[k], scales[v])
            pages += kept[:2]
            new_scales += kept[2:]      # None, None without int8 pages
        return self.final_norm(x), pages + [s for s in new_scales
                                            if s is not None]


class GPTForCausalLM(nn.Layer):
    """The LM head over `GPTModel`, and the cache contract
    `serving.LLMEngine` asks of a model: what a sequence keeps is K/V
    pages, per layer one `[batch, page, heads * head_dim]` pair that a
    step only reads, with one row written (census tag `kv_pool`).

    - `init_cache(batch, max_len, dtype)` -> `GPTModel.init_cache`'s flat
      list, the sequence (the engine's slot) on axis 0 of every array.
    - `forward_cached(tokens, cache, positions, lengths=None)` ->
      `(logits [B, vocab] of each row's last real position, new cache)`.
      With `lengths` it reads prompts `[B, T]` right-padded to T (padding
      right of the last real position writes rows no query reads);
      without, one token a row (`[B, 1]`) through `cache`, widened here
      to a block `DECODE_BLOCK` wide with only row 0 real."""

    cache_tag = "kv_pool"

    def __init__(self, gpt: GPTModel):
        super().__init__()
        self.gpt = gpt

    def forward(self, input_ids):
        from ..ops.math import matmul
        h = self.gpt(input_ids)
        w = self.gpt.embeddings.word_embeddings.weight
        return matmul(h, w, transpose_y=True)

    def init_cache(self, batch_size, max_len, dtype="float32"):
        return self.gpt.init_cache(batch_size, max_len, dtype)

    def forward_cached(self, tokens, cache, positions, lengths=None):
        import jax.numpy as jnp

        from ..ops._dispatch import run_op
        from ..ops.math import matmul

        if lengths is None:
            tokens = run_op(
                lambda t: jnp.broadcast_to(t, (t.shape[0], DECODE_BLOCK)),
                [tokens], "llm_decode_block")
        h, cache = self.gpt.forward_cached(tokens, cache, positions, lengths)
        w = self.gpt.embeddings.word_embeddings.weight
        logits = matmul(h, w, transpose_y=True)
        if lengths is None:
            return logits[:, 0], cache

        def _last(la, ln):
            idx = (ln - 1).astype(jnp.int32)[:, None, None]
            return jnp.take_along_axis(la, idx, axis=1)[:, 0]

        return run_op(_last, [logits, lengths], "llm_last_logits"), cache


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
