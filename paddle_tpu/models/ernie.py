"""ERNIE/BERT encoder family — the flagship benchmark model (config 3).

Reference parity: ERNIE as consumed through PaddleNLP on the reference stack
(transformer encoder per `python/paddle/nn/layer/transformer.py`, trained
via Fleet). The TPU build wires tensor-parallel variants through
paddle_tpu.parallel.mp_layers so the same class scales from one chip to a
pod slice; attention lowers to the fused XLA/Pallas path.

Configs: ernie_base (12L/768H/12A — BERT-base geometry), ernie_large,
ernie_titan_10b approximation (48L/4096H/64A ≈ 10B params) for config 5.
"""
from __future__ import annotations

import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..ops.creation import arange, ones, zeros
from ..ops.manipulation import reshape, unsqueeze


class ErnieEmbeddings(nn.Layer):
    def __init__(self, vocab_size, hidden_size, max_position_embeddings=512,
                 type_vocab_size=2, dropout=0.1, use_mp=False):
        super().__init__()
        if use_mp:
            from ..parallel.mp_layers import VocabParallelEmbedding
            self.word_embeddings = VocabParallelEmbedding(vocab_size, hidden_size)
        else:
            self.word_embeddings = nn.Embedding(vocab_size, hidden_size)
        self.position_embeddings = nn.Embedding(max_position_embeddings, hidden_size)
        self.token_type_embeddings = nn.Embedding(type_vocab_size, hidden_size)
        self.layer_norm = nn.LayerNorm(hidden_size)
        self.dropout = nn.Dropout(dropout)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        seq_len = input_ids.shape[1]
        if position_ids is None:
            position_ids = arange(seq_len, dtype="int32")
            position_ids = unsqueeze(position_ids, 0)
        if token_type_ids is None:
            token_type_ids = zeros(list(input_ids.shape), dtype="int32")
        emb = (self.word_embeddings(input_ids)
               + self.position_embeddings(position_ids)
               + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(emb))


class ErnieMLP(nn.Layer):
    def __init__(self, hidden_size, intermediate_size, dropout=0.1, use_mp=False):
        super().__init__()
        if use_mp:
            from ..parallel.mp_layers import ColumnParallelLinear, RowParallelLinear
            self.fc1 = ColumnParallelLinear(hidden_size, intermediate_size,
                                            gather_output=False)
            self.fc2 = RowParallelLinear(intermediate_size, hidden_size,
                                         input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(hidden_size, intermediate_size)
            self.fc2 = nn.Linear(intermediate_size, hidden_size)
        self.act = nn.GELU()
        self.dropout = nn.Dropout(dropout)

    def forward(self, x):
        return self.dropout(self.fc2(self.act(self.fc1(x))))


# Width of one decode step through `ErnieSelfAttention.forward_cached` for
# a model that keeps K/V pages (`GPTForCausalLM`): the real token in row 0,
# junk behind it, and as many rows added to every page. On the XLA-CPU this
# was written against a block of 2 made decode bitwise equal to the full
# forward (a rank-1 matmul accumulated differently); jax 0.9's XLA gives no
# such equality at any width (cached and full logits agree to ~2e-6, tests
# hold them to 1e-4 with the same arg-max), so nothing relies on it. It is
# still 2 because a one-row step is another program, a `perf_opt` PR's to
# measure: ROADMAP S1(d) / D6 removes it. The junk row's write lands one
# past the live prefix and the next real token overwrites it before any
# mask admits it; under a row the engine discards it lands two past, still
# inside the page, because a sequence at `max_len` is never dispatched again.
DECODE_BLOCK = 2


class ErnieSelfAttention(nn.Layer):
    def __init__(self, hidden_size, num_heads, dropout=0.1, use_mp=False,
                 use_sp=False, causal=False):
        super().__init__()
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.causal = causal
        self.use_sp = use_sp
        if use_mp:
            from ..parallel.mp_layers import ColumnParallelLinear, RowParallelLinear
            self.qkv = ColumnParallelLinear(hidden_size, 3 * hidden_size,
                                            gather_output=True)
            self.out = RowParallelLinear(hidden_size, hidden_size)
        else:
            self.qkv = nn.Linear(hidden_size, 3 * hidden_size)
            self.out = nn.Linear(hidden_size, hidden_size)
        self.dropout_p = dropout

    def forward(self, x, attn_mask=None):
        from ..nn.functional.attention import scaled_dot_product_attention
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv(x)
        qkv = reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if self.use_sp:
            from ..parallel.sp import sequence_parallel_attention
            ctx = sequence_parallel_attention(q, k, v, impl="ring", causal=self.causal)
        else:
            ctx = scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask,
                dropout_p=self.dropout_p if self.training else 0.0,
                is_causal=self.causal, training=self.training)
        ctx = reshape(ctx, [b, s, self.num_heads * self.head_dim])
        return self.out(ctx)

    def forward_cached(self, x, k_cache, v_cache, positions,
                       k_scale=None, v_scale=None):
        """Cached-attention step over a fixed-shape KV cache (decode path).

        x: [B, T, H] current block (T = prompt length at prefill, 1 at
        decode). k_cache/v_cache: [B, L, nh*hd] with L fixed (the slot
        page) — fp32, or int8 for the weight-only KV arm. positions: [B]
        int32, tokens already cached per row; the block's K/V are written
        at positions[b]..positions[b]+T-1 and key j is visible to query i
        iff j <= positions[b]+i, so every (B, T, L) signature is ONE
        executable regardless of how full each row is. How the page is
        read follows T: a prompt attends over the whole page under that
        validity mask with dense einsums; a decode block (on a TPU, over
        floating-point pages, at the default matmul precision:
        `kernels.decode_attention.engages`) reads of each row's page only
        the row blocks below positions[b]+T, through one Pallas kernel
        keyed on `positions`. Both are the same function of (x, pages,
        positions); the kernel has no derivative (the serve path asks
        for none).

        A cached position is ONE row of nh*hd values, heads folded into
        the row, because of where the TPU puts a page in memory: of a
        [B, L, nh, hd] array with hd = 64 it lays the L positions along
        the 128 lanes, so one position's write touches every tile of the
        row's page (measured on a v5e at GPT-2-large widths: 15 us a row
        against 1.3 us with the position contiguous). The read splits
        the heads again inside the program.

        int8 mode (k_cache.dtype == int8): scale-per-row symmetric
        quantization. With k_scale/v_scale None the scales are computed
        fresh from this block's K/V (the prefill step); otherwise the
        given [B] scales are reused and new entries clip into their grid
        (the decode steps). Reads always dequantize cache * scale.

        Inference-only: dropout is not applied inside the attention (the
        surrounding norms/MLP still honor train/eval mode). Returns
        (out, k_cache, v_cache, k_scale, v_scale) — scales are None in
        fp32 mode.
        """
        import math as _math

        import jax
        import jax.numpy as jnp

        from ..kernels import decode_attention as ragged
        from ..ops._dispatch import run_op
        from ..ops.math import _precision

        b, t = x.shape[0], x.shape[1]
        qkv = self.qkv(x)
        qkv = reshape(qkv, [b, t, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scale = 1.0 / _math.sqrt(self.head_dim)
        quant = "int8" in str(k_cache.dtype)
        fresh = quant and k_scale is None
        ins = [q, k, v, k_cache, v_cache, positions]
        if quant and not fresh:
            ins += [k_scale, v_scale]

        def f(qa, ka, va, kc, vc, pos, *scales):
            if quant:
                if fresh:
                    # symmetric per-row grid from this block's dynamic
                    # range; later (decode) writes clip into it
                    ks = jnp.maximum(jnp.max(jnp.abs(ka), axis=(1, 2, 3)),
                                     1e-6) / 127.0
                    vs = jnp.maximum(jnp.max(jnp.abs(va), axis=(1, 2, 3)),
                                     1e-6) / 127.0
                else:
                    ks, vs = scales
                kw = jnp.clip(jnp.round(ka / ks[:, None, None, None]),
                              -127, 127).astype(jnp.int8)
                vw = jnp.clip(jnp.round(va / vs[:, None, None, None]),
                              -127, 127).astype(jnp.int8)
            else:
                kw, vw = ka, va

            def upd(page, blk, p):
                return jax.lax.dynamic_update_slice(page, blk, (p, 0))

            kc = jax.vmap(upd)(kc, kw.reshape(b, t, -1), pos)
            vc = jax.vmap(upd)(vc, vw.reshape(b, t, -1), pos)
            if ragged.engages(t, kc.dtype):
                # a decode block: read each page up to its own fill only
                out = ragged.decode_attention(qa.reshape(b, t, -1), kc, vc,
                                              pos, self.num_heads)
                return out.reshape(qa.shape), kc, vc
            kr = kc.reshape(b, -1, self.num_heads, self.head_dim)
            vr = vc.reshape(b, -1, self.num_heads, self.head_dim)
            if quant:
                kr = kr.astype(qa.dtype) * ks[:, None, None, None]
                vr = vr.astype(qa.dtype) * vs[:, None, None, None]
            # mirror scaled_dot_product_attention's fused path exactly
            # (same einsums/precision/mask value) so cached decode agrees
            # with the full-sequence forward to float32 rounding
            qh = jnp.swapaxes(qa, 1, 2)
            kh = jnp.swapaxes(kr, 1, 2)
            vh = jnp.swapaxes(vr, 1, 2)
            logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh,
                                precision=_precision()) * scale
            span = jnp.arange(kh.shape[2], dtype=pos.dtype)
            qpos = pos[:, None] + jnp.arange(qa.shape[1], dtype=pos.dtype)
            valid = span[None, None, None, :] <= qpos[:, None, :, None]
            logits = jnp.where(valid, logits, jnp.asarray(-1e9, logits.dtype))
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhst,bhtd->bhsd", probs, vh,
                             precision=_precision())
            out = jnp.swapaxes(out, 1, 2)
            if quant:
                return out, kc, vc, ks, vs
            return out, kc, vc

        outs = run_op(f, ins, "cached_attention")
        if quant:
            ctx, k_cache, v_cache, k_scale, v_scale = outs
        else:
            ctx, k_cache, v_cache = outs
        ctx = reshape(ctx, [b, t, self.num_heads * self.head_dim])
        return self.out(ctx), k_cache, v_cache, k_scale, v_scale


class ErnieLayer(nn.Layer):
    def __init__(self, hidden_size, num_heads, intermediate_size, dropout=0.1,
                 use_mp=False, use_sp=False, causal=False):
        super().__init__()
        self.attention = ErnieSelfAttention(hidden_size, num_heads, dropout, use_mp,
                                            use_sp, causal)
        self.mlp = ErnieMLP(hidden_size, intermediate_size, dropout, use_mp)
        self.norm1 = nn.LayerNorm(hidden_size)
        self.norm2 = nn.LayerNorm(hidden_size)
        self.dropout = nn.Dropout(dropout)

    def forward(self, x, attn_mask=None):
        x = self.norm1(x + self.dropout(self.attention(x, attn_mask)))
        x = self.norm2(x + self.mlp(x))
        return x

    def forward_cached(self, x, k_cache, v_cache, positions,
                       k_scale=None, v_scale=None):
        """One transformer block through the cached-attention path; same
        post-LN residual wiring as forward. Returns
        (x, k_cache, v_cache, k_scale, v_scale)."""
        attn, k_cache, v_cache, k_scale, v_scale = self.attention.forward_cached(
            x, k_cache, v_cache, positions, k_scale, v_scale)
        x = self.norm1(x + self.dropout(attn))
        x = self.norm2(x + self.mlp(x))
        return x, k_cache, v_cache, k_scale, v_scale


class ErnieModel(nn.Layer):
    def __init__(self, vocab_size=30522, hidden_size=768, num_hidden_layers=12,
                 num_attention_heads=12, intermediate_size=3072,
                 max_position_embeddings=512, type_vocab_size=2,
                 hidden_dropout_prob=0.1, use_mp=False, use_sp=False, causal=False):
        super().__init__()
        self.embeddings = ErnieEmbeddings(vocab_size, hidden_size,
                                          max_position_embeddings, type_vocab_size,
                                          hidden_dropout_prob, use_mp)
        self.layers = nn.LayerList([
            ErnieLayer(hidden_size, num_attention_heads, intermediate_size,
                       hidden_dropout_prob, use_mp, use_sp, causal)
            for _ in range(num_hidden_layers)])
        self.pooler = nn.Linear(hidden_size, hidden_size)
        self.pooler_act = nn.Tanh()

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, token_type_ids)
        if attention_mask is not None:
            # [B,S] 1/0 mask -> additive [B,1,1,S]
            m = unsqueeze(unsqueeze(attention_mask, 1), 1)
            attention_mask = (1.0 - m.astype("float32")) * -1e4
        for layer in self.layers:
            x = layer(x, attention_mask)
        pooled = self.pooler_act(self.pooler(x[:, 0]))
        return x, pooled


class ErnieForSequenceClassification(nn.Layer):
    def __init__(self, ernie: ErnieModel, num_classes=2, dropout=0.1):
        super().__init__()
        self.ernie = ernie
        self.dropout = nn.Dropout(dropout)
        self.classifier = nn.Linear(ernie.pooler.weight.shape[1], num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.ernie(input_ids, token_type_ids, attention_mask)
        return self.classifier(self.dropout(pooled))


class ErnieForPretraining(nn.Layer):
    """MLM + NSP heads (the pretraining objective the benchmark measures)."""

    def __init__(self, ernie: ErnieModel, use_mp=False):
        super().__init__()
        self.ernie = ernie
        hidden = ernie.pooler.weight.shape[1]
        self.transform = nn.Linear(hidden, hidden)
        self.transform_act = nn.GELU()
        self.transform_norm = nn.LayerNorm(hidden)
        self.nsp = nn.Linear(hidden, 2)
        self._use_mp = use_mp

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        seq, pooled = self.ernie(input_ids, token_type_ids, attention_mask)
        h = self.transform_norm(self.transform_act(self.transform(seq)))
        # weight-tied MLM logits against the (possibly vocab-sharded) embedding.
        # Flatten to 2D first: a batched [B,S,H]x[V,H]^T dot picks a
        # {1,2,0} output layout that costs a full-logits relayout copy
        # (250MB at vocab 30k) before the loss consumes it.
        from ..ops.math import matmul
        w = self.ernie.embeddings.word_embeddings.weight
        b, s = h.shape[0], h.shape[1]
        logits = matmul(h.reshape([-1, h.shape[-1]]), w, transpose_y=True)
        logits = logits.reshape([b, s, logits.shape[-1]])
        return logits, self.nsp(pooled)


# ---- configs ----
def ernie_base(**kw):
    return ErnieModel(vocab_size=30522, hidden_size=768, num_hidden_layers=12,
                      num_attention_heads=12, intermediate_size=3072, **kw)


def ernie_large(**kw):
    return ErnieModel(vocab_size=30522, hidden_size=1024, num_hidden_layers=24,
                      num_attention_heads=16, intermediate_size=4096, **kw)


def ernie_titan_10b(**kw):
    """≈10B-parameter geometry for the sharding+pipeline config (config 5)."""
    return ErnieModel(vocab_size=50304, hidden_size=4096, num_hidden_layers=48,
                      num_attention_heads=64, intermediate_size=16384,
                      max_position_embeddings=2048, **kw)


bert_base = ernie_base
bert_large = ernie_large


class ErnieScanStack(nn.Layer):
    """N identical transformer layers as ONE scanned, rematerialized layer.

    TPU-first design for the deep (48-layer titan) stack: instead of
    unrolling 48 python layers into the HLO (48x compile time, 48x code) the
    layer weights live STACKED ([L, ...] leading axis) and the forward is
    `lax.scan(jax.checkpoint(layer_fn))`:
      - compile time and program size are O(1) in depth;
      - `jax.checkpoint` per scan step = per-layer remat, so backward peak
        activation memory is one layer's activations + L boundary tensors
        (the enabler for ZeRO-3 titan training, reference
        `sharding_stage3.py:308` + `recompute` meta-optimizer);
      - GSPMD shards the stacked weights on their hidden axes exactly like
        the unrolled layers.
    Semantics match a loop of ErnieLayer(dropout=0) (post-LN residual
    blocks); dropout is compiled out (the large-scale configs train with
    dropout 0 anyway — reference ernie titan configs).
    """

    def __init__(self, hidden_size, num_heads, intermediate_size, n_layers,
                 remat=True, causal=False):
        """remat: False = no rematerialization; True = blanket per-layer
        remat (save only layer boundaries — minimum memory, the choice for
        HBM-bound pp-stage configs, tests/test_titan_feasibility.py);
        "dots" = selective checkpoint policy (save MXU/dot outputs +
        the flash-attention output, recompute elementwise+norm only —
        the reference recompute meta-optimizer's selective `checkpoints=`
        contract, fleet/meta_optimizers/recompute_optimizer.py, mapped to
        jax.checkpoint_policies). Blanket remat recomputes the expensive
        matmuls too and caps useful-FLOP fraction near 0.75; "dots" trades
        ~10*h bytes/token/layer of HBM to keep the MXU work single-pass.
        """
        super().__init__()
        import math as _math
        h, ffn, L = hidden_size, intermediate_size, n_layers
        self.hidden_size, self.num_heads, self.n_layers = h, num_heads, L
        self.remat, self.causal = remat, causal
        k = 1.0 / _math.sqrt(h)

        def mk(*shape):
            return self.create_parameter(
                shape, default_initializer=nn.initializer.Uniform(-k, k))

        def zeros_(*shape):
            return self.create_parameter(
                shape, default_initializer=nn.initializer.Constant(0.0))

        self.qkv_w = mk(L, h, 3 * h)
        self.qkv_b = zeros_(L, 3 * h)
        self.proj_w = mk(L, h, h)
        self.proj_b = zeros_(L, h)
        self.fc1_w = mk(L, h, ffn)
        self.fc1_b = zeros_(L, ffn)
        self.fc2_w = mk(L, ffn, h)
        self.fc2_b = zeros_(L, h)
        ones_ = nn.initializer.Constant(1.0)
        self.ln1_g = self.create_parameter((L, h), default_initializer=ones_)
        self.ln1_b = zeros_(L, h)
        self.ln2_g = self.create_parameter((L, h), default_initializer=ones_)
        self.ln2_b = zeros_(L, h)
        # GSPMD layout: shard the big matrices on their widest axis
        for p, attr in ((self.qkv_w, (None, None, "mp")),
                        (self.fc1_w, (None, None, "mp")),
                        (self.fc2_w, (None, "mp", None)),
                        (self.proj_w, (None, "mp", None))):
            p.dist_attr = attr

    def _layer_fn(self, x, wl):
        import jax
        import jax.numpy as jnp
        import math as _math
        (qkv_w, qkv_b, proj_w, proj_b, fc1_w, fc1_b, fc2_w, fc2_b,
         ln1_g, ln1_b, ln2_g, ln2_b) = wl
        B, S, H = x.shape
        nh = self.num_heads
        hd = H // nh

        def ln(v, g, b):
            # statistics in fp32 (bf16 mean/var over h=4096 loses ~3 bits),
            # result back in the residual dtype so the scan carry is stable
            v32 = v.astype(jnp.float32)
            mu = jnp.mean(v32, -1, keepdims=True)
            var = jnp.var(v32, -1, keepdims=True)
            # eps matches nn.LayerNorm's default so scan-stack and unrolled
            # ErnieLayer checkpoints are interchangeable
            n = ((v32 - mu) * jax.lax.rsqrt(var + 1e-5)).astype(v.dtype)
            return n * g + b

        qkv = x @ qkv_w + qkv_b
        q, k_, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, nh, hd)
        k_ = k_.reshape(B, S, nh, hd)
        v = v.reshape(B, S, nh, hd)
        from ..kernels.flash_attention import flash_attention_arrays
        o = flash_attention_arrays(q, k_, v, causal=self.causal)
        # named save point for the selective remat policy: the pallas
        # flash output is not a lax dot, so dots_saveable alone would
        # recompute the whole attention in the backward pass
        from jax.ad_checkpoint import checkpoint_name
        o = checkpoint_name(o, "flash_attn_out")
        o = o.reshape(B, S, H) @ proj_w + proj_b
        x = ln(x + o, ln1_g, ln1_b)
        m = jax.nn.gelu(x @ fc1_w + fc1_b, approximate=False) @ fc2_w + fc2_b
        x = ln(x + m, ln2_g, ln2_b)
        return x

    def forward(self, x):
        from ..ops._dispatch import ensure_tensor, run_op
        from ..amp.state import amp_enabled, amp_state
        import jax
        import jax.numpy as jnp
        x = ensure_tensor(x)
        ws = [self.qkv_w, self.qkv_b, self.proj_w, self.proj_b,
              self.fc1_w, self.fc1_b, self.fc2_w, self.fc2_b,
              self.ln1_g, self.ln1_b, self.ln2_g, self.ln2_b]
        remat = self.remat
        # _layer_fn is raw jnp, below the op-level autocast whitelist: an
        # fp32 carry would silently promote every dot (and every saved
        # residual) back to fp32. Capture the ambient AMP dtype at trace
        # time and pin the scan carry + weights to it.
        cdtype = jnp.dtype(amp_state().dtype) if amp_enabled() else None

        def f(xa, *warrs):
            if cdtype is not None and xa.dtype != cdtype:
                xa = xa.astype(cdtype)
            if cdtype is not None:
                warrs = tuple(
                    w.astype(cdtype)
                    if jnp.issubdtype(w.dtype, jnp.floating) else w
                    for w in warrs)
            def body(carry, wl):
                step = self._layer_fn
                if remat == "dots":
                    pol = jax.checkpoint_policies.save_from_both_policies(
                        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                        jax.checkpoint_policies.save_only_these_names(
                            "flash_attn_out"))
                    step = jax.checkpoint(step, policy=pol)
                elif remat:
                    step = jax.checkpoint(step)
                return step(carry, wl), None

            out, _ = jax.lax.scan(body, xa, tuple(warrs))
            return out

        return run_op(f, [x, *ws], "ernie_scan_stack")
