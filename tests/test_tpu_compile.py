"""Compile the main path's kernels for a DESCRIBED v5e, at real widths.

No chip is involved: `jax.experimental.topologies` describes a `v5e:2x2`
and the installed TPU compiler compiles for it, raising what the chip's
compiler would raise (a slice off the tiling, more VMEM than a kernel may
hold). Interpret-mode tests cannot see either. A compile that passes is
not a chip run and says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may hold libtpu, and every xdist worker imports every test file.
Keep all such compiles in THIS file (a second file may land on another
worker, whose fixture would then skip). JAX's persistent compilation
cache is off around them: an entry written for a described device cannot
be read back without a chip.
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the package re-exports the `flash_attention` function under the module's name
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

# (b*h, s, d, causal): the geometries chip_smoke.py's kernel phase runs
GEOMETRIES = [(96, 1024, 64, False), (12, 8192, 64, True),
              (12, 8192, 128, True)]
_IDS = [f"bh{bh}-s{s}-d{d}" for bh, s, d, _ in GEOMETRIES]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _kernels_in(fn, *args):
    """Compile `fn` for the described chip; count its Pallas kernels."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    n = text.count('custom_call_target="tpu_custom_call"')
    assert n, "no tpu_custom_call in the compiled program"
    return n


def _sum_loss(out):
    return out.astype(jnp.float32).sum()


@pytest.mark.parametrize("bh,s,d,causal", GEOMETRIES, ids=_IDS)
def test_flash_forward_compiles(sds, bh, s, d, causal):
    bq, bk, fwd, _ = fa.dispatch_plan(s, d, jnp.bfloat16)
    assert fwd == "pallas"
    x = sds((bh, s, d))
    assert _kernels_in(
        lambda q, k, v: fa._flash_core(q, k, v, causal, bq, bk, False),
        x, x, x) == 1


@pytest.mark.parametrize("bh,s,d,causal", GEOMETRIES, ids=_IDS)
def test_flash_grad_compiles(sds, bh, s, d, causal):
    """The WHOLE differentiated program: at bh12/s8192/d64 each backward
    kernel compiled alone while the program jax.grad composes around the
    custom call was refused (scoped VMEM, PR 22)."""
    bq, bk, _, bwd = fa.dispatch_plan(s, d, jnp.bfloat16)
    assert bwd == "fused"
    x = sds((bh, s, d))

    def loss(q, k, v):
        return _sum_loss(fa._flash_core(q, k, v, causal, bq, bk, False))

    # forward + the fused single-pass backward
    assert _kernels_in(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == 2


@pytest.mark.parametrize("bwd,n_kernels", [("_flash_bwd_fused_bhsd", 1),
                                           ("_flash_bwd_bhsd", 2)])
def test_each_backward_forced(sds, bwd, n_kernels):
    bh, s, d = 12, 8192, 64
    x, row = sds((bh, s, d)), sds((bh, 1, s), jnp.float32)
    fn = functools.partial(getattr(fa, bwd), causal=True, block_q=512,
                           block_k=512, interpret=False)
    assert _kernels_in(fn, x, x, x, x, row, x) == n_kernels


def test_two_pass_grad_compiles_past_the_fused_cap(sds):
    """s16384/d64 is where the old residency guard (counting d, not the
    128 padded lanes) still chose the fused kernel and the compiler
    refused it; the dispatcher now streams it through the two-pass."""
    bh, s, d = 12, 16384, 64
    bq, bk, _, bwd = fa.dispatch_plan(s, d, jnp.bfloat16)
    assert bwd == "two_pass"
    x = sds((bh, s, d))

    def loss(q, k, v):
        return _sum_loss(fa._flash_core(q, k, v, True, bq, bk, False))

    assert _kernels_in(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == 3


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_ring_block_kernels_compile(sds, kernel):
    """One hop of the sp=4 ring at s8192: each chip's 2048-row shard."""
    bh, s_loc, d = 12, 2048, 64
    x, row = sds((bh, s_loc, d)), sds((bh, 1, s_loc), jnp.float32)
    offs = sds((2,), jnp.int32)
    kw = dict(causal=True, block_q=512, block_k=512, interpret=False)
    if kernel == "fwd":
        _kernels_in(functools.partial(fa.ring_block_fwd, **kw), x, x, x, offs)
    else:
        fn = fa.ring_block_dq if kernel == "dq" else fa.ring_block_dkv
        _kernels_in(functools.partial(fn, **kw), x, x, x, x, row, row, offs)


def test_ernie_scan_layer_step_compiles(sds, monkeypatch):
    """One ErnieScanStack layer, forward and backward, hidden 768 / seq
    1024 / bf16. The layer asks `jax.default_backend()` whether to
    interpret its kernel and would see this process's CPU, so the test
    answers for the described chip."""
    from paddle_tpu.models.ernie import ErnieScanStack
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    net = ErnieScanStack(768, 12, 3072, n_layers=1)
    ws = [net.qkv_w, net.qkv_b, net.proj_w, net.proj_b, net.fc1_w,
          net.fc1_b, net.fc2_w, net.fc2_b, net.ln1_g, net.ln1_b,
          net.ln2_g, net.ln2_b]
    wl = tuple(sds(tuple(w.shape[1:])) for w in ws)
    x = sds((8, 1024, 768))

    def loss(x, wl):
        return _sum_loss(net._layer_fn(x, wl))

    assert _kernels_in(jax.grad(loss, argnums=(0, 1)), x, wl) == 2


@pytest.mark.parametrize("on_tpu", [False, True],
                         ids=["dense_read", "ragged_kernel"])
def test_llm_decode_program_updates_the_pool_in_place(sds, monkeypatch,
                                                      on_tpu):
    """The serving engine's decode program at GPT-2-large widths (hidden
    1280, 20 heads, 12 slots, page 1026) cut to 2 layers, from shapes: the
    donated pool is aliased to the pool outputs, no synchronous copy of a
    pool array is left, and the device layout of a pool array keeps one
    cached position a contiguous row (heads * head_dim on the lanes; with
    heads and head_dim as two axes the compiler lays the positions along
    the lanes and a row's write touches the slot's whole page). The
    per-slot write is still the scatter's loop, one per pool array: what
    it costs is a chip's to say (PERF.md section 5). As the chip lowers it
    (`on_tpu`: the backend test answers "tpu") each layer reads its pages
    through the `decode_attention` kernel, which takes the float32 page
    itself: no page is converted to bfloat16 or copied for it."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import split_state
    from paddle_tpu.models.gpt import GPTForCausalLM, GPTModel
    from paddle_tpu.serving import LLMConfig, LLMEngine

    layers, slots = 2, 12
    paddle.seed(0)
    lm = GPTForCausalLM(GPTModel(
        vocab_size=512, hidden_size=1280, num_layers=layers, num_heads=20,
        intermediate_size=5120, max_seq_len=1024, dropout=0.0))
    eng = LLMEngine(lm, LLMConfig(num_slots=slots, max_len=1024,
                                  prefill_buckets=(64,),
                                  warmup_on_start=False))
    net, static = eng._decode, eng._decode.forward
    trainable, frozen = split_state(net)
    inputs = [sds((slots,), jnp.int32), sds((slots,), jnp.int32)] + [
        sds(tuple(t.shape), t._value.dtype) for t in eng._pool]
    pool_shape = "f32[" + ",".join(str(d) for d in eng._pool[0].shape) + "]"
    pool_bytes = sum(t._value.nbytes for t in eng._pool)
    eng._pool = []                      # shapes are all the compile needs
    donated = static._donated(len(inputs))
    assert donated == tuple(range(2, 2 + 2 * layers))
    jitted = static._get_jitted(
        tuple(l.training for l in net.sublayers(include_self=True)),
        list(trainable), list(frozen), {}, False, donated)
    if on_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with paddle.no_grad():
        compiled = jitted.lower(*static._call_args(
            [sds(tuple(t.shape), t._value.dtype)
             for t in trainable.values()],
            [sds(tuple(t.shape), t._value.dtype) for t in frozen.values()],
            sds((), jax.random.key(0).dtype), inputs, donated)).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert not re.findall(r"= " + re.escape(pool_shape) + r"\S* copy\(", text)
    entry = text.split("\n", 1)[0]
    assert set(re.findall(re.escape(pool_shape) + r"\{([\d,]+):", entry)) \
        == {"2,1,0"}
    assert text.count(" while(") == 2 * layers
    da = importlib.import_module("paddle_tpu.kernels.decode_attention")
    kernels = text.count('custom_call_target="tpu_custom_call"')
    assert kernels == (layers if on_tpu else 0)
    assert (f"%{da.KERNEL}" in text) == on_tpu  # the name a trace reader finds
    assert ("bf16" + pool_shape[3:] in text) != on_tpu


def test_power_retention_step_kernel_compiles_in_place(sds, monkeypatch):
    """The decode state update at Brumby-14B's widths (12 slots, 8 kv heads
    of 5 query heads, head_dim 128: a state [12, 8, 128, 8320] float32,
    409 MB a layer): one Pallas kernel under its own name, the donated
    state aliased to the state it returns, nothing of its size left as a
    temporary."""
    pr = importlib.import_module("paddle_tpu.kernels.power_retention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, heads, groups, d = 12, 40, 8, 128
    rows = pr.state_rows(d)
    state = sds((slots, groups, d, rows), jnp.float32)
    compiled = jax.jit(
        lambda q, k, v, g, s, z: pr.power_retention_step(q, k, v, g, (s, z)),
        donate_argnums=(4, 5)).lower(
            sds((slots, heads, d)), sds((slots, groups, d)),
            sds((slots, groups, d)), sds((slots, groups), jnp.float32),
            state, sds((slots, groups, rows), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"%{pr.STEP_KERNEL}" in text     # the name a trace reader finds
    mem = compiled.memory_analysis()
    nbytes = slots * groups * d * rows * 4
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < nbytes // 8


@pytest.mark.parametrize("bucket", [512, 4096])
def test_power_retention_prompt_form_compiles(sds, bucket):
    """One layer's prompt form at the published widths, one chunk a
    bucket: the scores stay in blocks of rows (well under a gigabyte of
    temporaries at the 4096 bucket, where [40, 4096, 4096] float32 scores
    alone would be 2.7 GB)."""
    pr = importlib.import_module("paddle_tpu.kernels.power_retention")
    compiled = jax.jit(pr.power_retention_chunked).lower(
        sds((1, bucket, 40, 128)), sds((1, bucket, 8, 128)),
        sds((1, bucket, 8, 128)), sds((1, bucket, 8), jnp.float32),
        sds((1,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_brumby_decode_program_rewrites_the_state_pool_in_place(sds,
                                                                monkeypatch):
    """The engine's decode program over a Brumby model at the published
    widths (vocabulary cut to 1024 rows: the head is not the subject), 2
    layers, 12 slots, from shapes: the whole state pool is donated and
    aliased out, each layer's update is the named kernel, and the program
    is one token wide (no `llm_decode_block` broadcast: that is
    `GPTForCausalLM`'s, `models.ernie.DECODE_BLOCK` wide)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import split_state
    from paddle_tpu.models.brumby import BrumbyForCausalLM, BrumbyModel
    from paddle_tpu.serving import LLMConfig, LLMEngine

    pr = importlib.import_module("paddle_tpu.kernels.power_retention")
    layers, slots = 2, 12
    paddle.seed(0)
    lm = BrumbyForCausalLM(BrumbyModel(vocab_size=1024, num_layers=layers,
                                       dtype="bfloat16"))
    eng = LLMEngine(lm, LLMConfig(num_slots=slots, max_len=8192,
                                  prefill_buckets=(512,),
                                  warmup_on_start=False))
    net, static = eng._decode, eng._decode.forward
    trainable, frozen = split_state(net)
    inputs = [sds((slots,), jnp.int32), sds((slots,), jnp.int32)] + [
        sds(tuple(t.shape), t._value.dtype) for t in eng._pool]
    pool_bytes = sum(t._value.nbytes for t in eng._pool)
    assert pool_bytes == layers * slots * 8 * 8320 * 129 * 4
    eng._pool = []                      # shapes are all the compile needs
    donated = static._donated(len(inputs))
    assert donated == tuple(range(2, 2 + 2 * layers))
    jitted = static._get_jitted(
        tuple(l.training for l in net.sublayers(include_self=True)),
        list(trainable), list(frozen), {}, False, donated)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with paddle.no_grad():
        compiled = jitted.lower(*static._call_args(
            [sds(tuple(t.shape), t._value.dtype)
             for t in trainable.values()],
            [sds(tuple(t.shape), t._value.dtype) for t in frozen.values()],
            sds((), jax.random.key(0).dtype), inputs, donated)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 16
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert text.count(f"%{pr.STEP_KERNEL}") >= layers
    assert text.count('custom_call_target="tpu_custom_call"') == layers


@pytest.mark.parametrize("cell,arrays", [
    ("gpt2_large", [(12, 1026, 1280)] * 72),
    ("brumby_14b", [(12, 8, 128, 8320), (12, 8, 8320)] * 8)])
def test_llm_slot_write_program_writes_the_pool_in_place(sds, monkeypatch,
                                                         cell, arrays):
    """The engine's slot-write program at the two cells' pools (GPT-2
    large: 72 float32 pages; Brumby, 8 layers: a state matrix and its
    normaliser a layer), from shapes: every pool input is donated and
    aliased to its output, and nothing the size of a page or a state is
    left beside the pool (the eager writes copied each array whole). The
    program is generic over what `init_cache` returns, so a model that
    keeps 72 or 16 tiny arrays builds it."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.serving import LLMConfig, LLMEngine

    class Keeps(paddle.nn.Layer):
        cache_tag = "kv_pool"

        def init_cache(self, batch_size, max_len, dtype="float32"):
            return [paddle.zeros([batch_size, 1]) for _ in arrays]

        forward_cached = None

    eng = LLMEngine(Keeps(), LLMConfig(num_slots=12, max_len=16,
                                       warmup_on_start=False))
    inputs = [sds((), jnp.int32)] \
        + [sds(a, jnp.float32) for a in arrays] \
        + [sds((1,) + a[1:], jnp.float32) for a in arrays]
    donated = eng._slot_write.forward._donated(len(inputs))
    assert donated == tuple(range(1, 1 + len(arrays)))
    compiled = _compile_net(eng._slot_write, inputs, donated, sds,
                            monkeypatch)
    sizes = [4 * functools.reduce(int.__mul__, a) for a in arrays]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(sizes)   # 1026 rows occupy 1032
    assert mem.temp_size_in_bytes < min(sizes) // 16
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_slot_write")
    entry = text.split("\n", 1)[0]
    assert entry.count("may-alias") == len(arrays)
    # in place: a pool array is never the result of a plain copy
    for shape in {"f32[" + ",".join(map(str, a)) + "]" for a in arrays}:
        assert not re.findall(r"= " + re.escape(shape) + r"\S* copy\(", text)


def _ling_engine(slots):
    """A Ling model at the published widths cut to three layers (the dense
    KDA layer 0, the KDA expert layer 10, the MLA expert layer 11), 16 of
    512 experts held and 1024 rows of vocabulary (neither is the subject:
    the kernels' geometry is the widths'), in an engine at the cell's
    shapes, from shapes."""
    import paddle_tpu as paddle
    from paddle_tpu.models.ling import LingForCausalLM, LingModel
    from paddle_tpu.serving import LLMConfig, LLMEngine

    paddle.seed(0)
    lm = LingForCausalLM(LingModel(vocab_size=1024, layers=[0, 10, 11],
                                   held=(0, 16), dtype="bfloat16"))
    eng = LLMEngine(lm, LLMConfig(num_slots=slots, max_len=5120,
                                  prefill_buckets=(256, 768, 1536, 3072),
                                  warmup_on_start=False))
    return lm, eng


def _compile_net(net, inputs, donated, sds, monkeypatch):
    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import split_state

    static = net.forward
    trainable, frozen = split_state(net)
    jitted = static._get_jitted(
        tuple(l.training for l in net.sublayers(include_self=True)),
        list(trainable), list(frozen), {}, False, donated)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with paddle.no_grad():
        return jitted.lower(*static._call_args(
            [sds(tuple(t.shape), t._value.dtype)
             for t in trainable.values()],
            [sds(tuple(t.shape), t._value.dtype) for t in frozen.values()],
            sds((), jax.random.key(0).dtype), inputs, donated)).compile()


def test_ling_decode_program_updates_its_mixed_pool_in_place(sds,
                                                             monkeypatch):
    """The engine's decode program over a Ling model, 48 slots: the whole
    pool (two KDA states, their convolution rows, one latent page of 5120
    rows of 640: 576 in whole 128 lanes) is donated and aliased out; each KDA layer's update is the named
    kernel, each expert layer's pass two calls of the grouped matmul
    under its name, and the latent page is read by `mla_decode`."""
    kda = importlib.import_module("paddle_tpu.kernels.kda")
    gm = importlib.import_module("paddle_tpu.kernels.grouped_matmul")
    md = importlib.import_module("paddle_tpu.kernels.mla_decode")
    slots = 48
    lm, eng = _ling_engine(slots)
    assert lm.cache_tag == ("state_pool",) * 4 + ("kv_pool",)
    inputs = [sds((slots,), jnp.int32), sds((slots,), jnp.int32)] + [
        sds(tuple(t.shape), t._value.dtype) for t in eng._pool]
    pool_bytes = sum(t._value.nbytes for t in eng._pool)
    assert pool_bytes == slots * (2 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
                                  + 5120 * 640 * 2)
    eng._pool = []                      # shapes are all the compile needs
    donated = eng._decode.forward._donated(len(inputs))
    assert donated == tuple(range(2, 7))
    compiled = _compile_net(eng._decode, inputs, donated, sds, monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # no copy of the page: its rows are whole 128-lane tiles (at 576 the
    # compiler held it in another order and copied it in and out: 0.33 GB)
    assert mem.temp_size_in_bytes < pool_bytes // 16
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 2 * 2 + 1
    assert text.count(f"%{kda.STEP_KERNEL}") >= 2
    assert text.count(f"%{gm.KERNEL}") >= 4
    assert text.count(f"%{md.KERNEL}") >= 1


def test_ling_prefill_program_compiles_at_the_largest_bucket(sds,
                                                             monkeypatch):
    """One prompt at the 3072 bucket: the chunked KDA form, expanded latent
    attention through the flash kernel (`mla_prefill`) and the grouped
    matmul at its tile of 128 rows, in under 1.5 GB of temporaries; it
    returns a slot's whole cache."""
    gm = importlib.import_module("paddle_tpu.kernels.grouped_matmul")
    lm, eng = _ling_engine(4)
    eng._pool = []
    compiled = _compile_net(
        eng._prefill, [sds((1, 3072), jnp.int32), sds((1,), jnp.int32)], (),
        sds, monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5 * 2 ** 30
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_prefill")
    assert text.count(f"%{gm.KERNEL}") >= 4
    assert "%mla_prefill" in text
    assert "bf16[1,5120,640]" in text         # the page, whole


def _dots_engine(slots):
    """A Dots model at the published widths cut to ONE expert layer
    (published layer 3), 2 of 256 experts held and 1024 rows of vocabulary
    (neither is the subject: the kernels' geometry is the widths'), in an
    engine at the cell's shapes, from shapes."""
    import paddle_tpu as paddle
    from paddle_tpu.models.dots import DotsForCausalLM, DotsModel
    from paddle_tpu.serving import LLMConfig, LLMEngine

    paddle.seed(0)
    lm = DotsForCausalLM(DotsModel(
        vocab_size=1024, layers=[3], held=(0, 2), dtype="bfloat16",
        rope_scaling=dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                          mscale=1, mscale_all_dim=1,
                          original_max_position_embeddings=4096)))
    eng = LLMEngine(lm, LLMConfig(num_slots=slots, max_len=13312,
                                  prefill_buckets=(3072, 6144, 12288),
                                  warmup_on_start=False))
    return lm, eng


def test_mla_decode_kernel_compiles_at_the_cells_pool(sds):
    """The read alone at `dots_vlm1.serve_long_context`'s shapes: 16 slots
    of a [13312, 640] bfloat16 page, 128 heads, no temporaries."""
    md = importlib.import_module("paddle_tpu.kernels.mla_decode")
    fn = functools.partial(md._attend, scale=0.1, block_rows=md.BLOCK_ROWS,
                           mxu=jnp.bfloat16, interpret=False)
    compiled = jax.jit(fn).lower(
        sds((16, 128, 640)), sds((16, 13312, 640)),
        sds((16,), jnp.int32)).compile()
    assert f"%{md.KERNEL}" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("bucket", [3072, 12288])
def test_latent_prompt_form_compiles_without_the_scores(sds, monkeypatch,
                                                        bucket):
    """128 heads at score width 192 and value width 128 through the flash
    kernel: what is held beside the inputs is queries, keys, values and
    the output ([128, bucket, 192 / 128] each), never [heads, rows, keys]
    (at 12288: 77 GB in float32)."""
    attention = importlib.import_module(
        "paddle_tpu.nn.functional.attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = lambda qn, qr, c, kr, w: attention.latent_prompt_flash(
        qn, qr, c, kr, w, 0.1)
    compiled = jax.jit(fn).lower(
        sds((1, bucket, 128, 128)), sds((1, bucket, 128, 64)),
        sds((1, bucket, 512)), sds((1, bucket, 64)),
        sds((512, 128 * 256))).compile()
    assert "%mla_prefill" in compiled.as_text()
    per_row = 128 * (192 + 192 + 128 + 128) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * bucket * per_row


def test_dots_decode_program_reads_its_pages_through_the_kernel(sds,
                                                                monkeypatch):
    """The engine's decode program over a Dots layer, 16 slots of 13,312
    positions: the page is donated and aliased out, read by `mla_decode`,
    and the expert pass is two calls of the grouped matmul."""
    gm = importlib.import_module("paddle_tpu.kernels.grouped_matmul")
    md = importlib.import_module("paddle_tpu.kernels.mla_decode")
    slots = 16
    lm, eng = _dots_engine(slots)
    assert lm.cache_tag == "kv_pool"
    inputs = [sds((slots,), jnp.int32), sds((slots,), jnp.int32)] + [
        sds(tuple(t.shape), t._value.dtype) for t in eng._pool]
    pool_bytes = sum(t._value.nbytes for t in eng._pool)
    assert pool_bytes == slots * 13312 * 640 * 2
    eng._pool = []                      # shapes are all the compile needs
    donated = eng._decode.forward._donated(len(inputs))
    assert donated == (2,)
    compiled = _compile_net(eng._decode, inputs, donated, sds, monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 4
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert text.count('custom_call_target="tpu_custom_call"') == 1 + 2
    assert text.count(f"%{md.KERNEL}") >= 1
    assert text.count(f"%{gm.KERNEL}") >= 2


def test_dots_prefill_program_compiles_at_the_largest_bucket(sds,
                                                             monkeypatch):
    """One prompt at the 12288 bucket through a Dots expert layer: the
    flash prompt form and the grouped matmul, in under 4 GB of
    temporaries; it returns a slot's whole page."""
    lm, eng = _dots_engine(2)
    eng._pool = []
    compiled = _compile_net(
        eng._prefill, [sds((1, 12288), jnp.int32), sds((1,), jnp.int32)], (),
        sds, monkeypatch)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_prefill")
    assert "%mla_prefill" in text
    assert "bf16[1,13312,640]" in text        # the page, whole


def _nemotron_engine(slots):
    """A Nemotron-H model at the published widths cut to one block of each
    kind (published layers 0, 1 and 5: Mamba-2, experts, attention), 2 of
    128 experts held and 1024 rows of vocabulary, in an engine at the
    cell's page and buckets."""
    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron import NemotronHForCausalLM, NemotronHModel
    from paddle_tpu.serving import LLMConfig, LLMEngine

    paddle.seed(0)
    lm = NemotronHForCausalLM(NemotronHModel(
        vocab_size=1024, layers=[0, 1, 5], held=(0, 2), dtype="bfloat16"))
    eng = LLMEngine(lm, LLMConfig(num_slots=slots, max_len=5120,
                                  prefill_buckets=(256, 4096),
                                  warmup_on_start=False))
    return lm, eng


def test_nemotron_decode_program_rewrites_its_states_in_place(sds,
                                                             monkeypatch):
    """The engine's decode program over a Nemotron-H model: the Mamba-2
    state and its convolution rows and the two grouped K/V pages are
    donated and aliased out; the state update is `ssd_step`, the experts
    two grouped matmuls, the page read the grouped `decode_attention`."""
    ssd = importlib.import_module("paddle_tpu.kernels.ssd")
    gm = importlib.import_module("paddle_tpu.kernels.grouped_matmul")
    da = importlib.import_module("paddle_tpu.kernels.decode_attention")
    slots = 8
    lm, eng = _nemotron_engine(slots)
    assert lm.cache_tag == ("state_pool",) * 2 + ("kv_pool",) * 2
    inputs = [sds((slots,), jnp.int32), sds((slots,), jnp.int32)] + [
        sds(tuple(t.shape), t._value.dtype) for t in eng._pool]
    pool_bytes = sum(t._value.nbytes for t in eng._pool)
    assert pool_bytes == slots * (64 * 64 * 128 * 4 + 3 * 6144 * 2
                                  + 2 * 5120 * 256 * 2)
    eng._pool = []
    donated = eng._decode.forward._donated(len(inputs))
    compiled = _compile_net(eng._decode, inputs, donated, sds, monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 4
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert text.count('custom_call_target="tpu_custom_call"') == 1 + 2 + 1
    assert f"%{ssd.STEP_KERNEL}" in text and f"%{da.KERNEL}" in text
    assert text.count(f"%{gm.KERNEL}") >= 2


def test_nemotron_prefill_program_compiles_at_the_largest_bucket(
        sds, monkeypatch):
    """One prompt at the 4096 bucket: the chunked scan's kernel, the flash
    prompt form over the grouped heads and the grouped matmul; it returns a
    slot's whole pages."""
    lm, eng = _nemotron_engine(2)
    eng._pool = []
    compiled = _compile_net(
        eng._prefill, [sds((1, 4096), jnp.int32), sds((1,), jnp.int32)], (),
        sds, monkeypatch)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_prefill")
    assert "%ssd_chunked" in text and "%gqa_prefill" in text
    assert "bf16[1,5120,256]" in text         # a page, whole


def _lfm2_engine(slots):
    """An LFM2-MoE model at the published widths cut to one block of each
    kind that follows the first (published layers 1-3: a conv mixer with
    the dense FFN, attention with experts, a conv mixer with experts), 4
    of 64 experts held and 1024 rows of vocabulary, in an engine at the
    cell's page and buckets."""
    import paddle_tpu as paddle
    from paddle_tpu.models.lfm2 import Lfm2MoeForCausalLM, Lfm2MoeModel
    from paddle_tpu.serving import LLMConfig, LLMEngine

    paddle.seed(0)
    lm = Lfm2MoeForCausalLM(Lfm2MoeModel(
        vocab_size=1024, layers=[1, 2, 3], held=(0, 4), dtype="bfloat16"))
    eng = LLMEngine(lm, LLMConfig(num_slots=slots, max_len=5120,
                                  prefill_buckets=(256, 4096),
                                  warmup_on_start=False))
    return lm, eng


@pytest.mark.parametrize("wide", [True, False], ids=["wide", "sliced"])
def test_grouped_read_compiles_at_head_64_over_the_cells_pool(sds, wide,
                                                             monkeypatch):
    """`decode_attention_gqa` over the LFM2 cell's pages, 128 slots of
    [5120, 8 x 64] bfloat16, 32 query heads, in both forms: each K/V
    head's 64 lanes sliced out of the 128-lane tile, or the query laid
    over the whole row."""
    da = importlib.import_module("paddle_tpu.kernels.decode_attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    page = sds((128, 5120, 512))
    assert _kernels_in(functools.partial(da._gqa_read, wide=wide),
                       sds((128, 32, 64)), page, page,
                       sds((128,), jnp.int32)) == 1


def test_lfm2_decode_program_updates_its_mixed_pool_in_place(sds,
                                                             monkeypatch):
    """The engine's decode program over an LFM2-MoE model: the convolution
    rows and the two grouped K/V pages are donated and aliased out; the
    page read is the grouped `decode_attention`, the experts two grouped
    matmuls a layer; the tokens joined to the step's counts come out
    beside them."""
    gm = importlib.import_module("paddle_tpu.kernels.grouped_matmul")
    da = importlib.import_module("paddle_tpu.kernels.decode_attention")
    slots = 8
    lm, eng = _lfm2_engine(slots)
    assert lm.cache_tag == ("state_pool", "kv_pool", "kv_pool", "state_pool")
    inputs = [sds((slots,), jnp.int32), sds((slots,), jnp.int32)] + [
        sds(tuple(t.shape), t._value.dtype) for t in eng._pool]
    pool_bytes = sum(t._value.nbytes for t in eng._pool)
    assert pool_bytes == slots * (2 * 2 * 2048 * 2 + 2 * 5120 * 512 * 2)
    eng._pool = []
    donated = eng._decode.forward._donated(len(inputs))
    compiled = _compile_net(eng._decode, inputs, donated, sds, monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 4
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_decode")
    assert text.count('custom_call_target="tpu_custom_call"') == 1 + 2 * 2
    assert f"%{da.KERNEL}" in text and text.count(f"%{gm.KERNEL}") >= 4
    assert f"s32[{slots + 3}]" in text       # the tokens and the counts


def test_lfm2_prefill_program_compiles_at_the_largest_bucket(sds,
                                                             monkeypatch):
    """One prompt at the 4096 bucket: the gated convolution's prompt form,
    the flash prompt form over the grouped heads of 64 and the grouped
    matmul; it returns a slot's whole pages."""
    lm, eng = _lfm2_engine(2)
    eng._pool = []
    compiled = _compile_net(
        eng._prefill, [sds((1, 4096), jnp.int32), sds((1,), jnp.int32)], (),
        sds, monkeypatch)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    text = compiled.as_text()
    assert text.startswith("HloModule jit_llm_prefill")
    assert "%gqa_prefill" in text
    assert "bf16[1,5120,512]" in text         # a page, whole
