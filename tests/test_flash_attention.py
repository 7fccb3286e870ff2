"""Flash-attention kernel tests (Pallas interpret mode on CPU)."""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.kernels.flash_attention import (
    _reference_bhsd, flash_attention, flash_attention_arrays,
)


def _r(*shape):
    return np.random.rand(*shape).astype("float32")


@pytest.mark.parametrize("causal", [False, True])
def test_matches_reference(causal):
    b, s, h, d = 1, 256, 2, 64
    q, k, v = _r(b, s, h, d), _r(b, s, h, d), _r(b, s, h, d)
    out = flash_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                          paddle.to_tensor(v), causal=causal, block_q=128, block_k=128)
    qb = jnp.swapaxes(jnp.asarray(q), 1, 2).reshape(b * h, s, d)
    kb = jnp.swapaxes(jnp.asarray(k), 1, 2).reshape(b * h, s, d)
    vb = jnp.swapaxes(jnp.asarray(v), 1, 2).reshape(b * h, s, d)
    ref = np.asarray(_reference_bhsd(qb, kb, vb, causal))
    ref = ref.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-5)


def test_grad_matches_reference_grad():
    b, s, h, d = 1, 128, 2, 32
    q, k, v = _r(b, s, h, d), _r(b, s, h, d), _r(b, s, h, d)
    qt = paddle.to_tensor(q, stop_gradient=False)
    out = flash_attention(qt, paddle.to_tensor(k), paddle.to_tensor(v), causal=True,
                          block_q=128, block_k=128)
    out.sum().backward()
    g_flash = qt.gradient()

    import jax
    qb = jnp.swapaxes(jnp.asarray(q), 1, 2).reshape(b * h, s, d)
    kb = jnp.swapaxes(jnp.asarray(k), 1, 2).reshape(b * h, s, d)
    vb = jnp.swapaxes(jnp.asarray(v), 1, 2).reshape(b * h, s, d)
    g_ref = jax.grad(lambda a: _reference_bhsd(a, kb, vb, True).sum())(qb)
    g_ref = np.asarray(g_ref).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(g_flash, g_ref, rtol=1e-4, atol=1e-4)


def test_ragged_seq_falls_back():
    b, s, h, d = 1, 100, 2, 32  # not a block multiple
    out = flash_attention_arrays(jnp.asarray(_r(b, s, h, d)), jnp.asarray(_r(b, s, h, d)),
                                 jnp.asarray(_r(b, s, h, d)), causal=False)
    assert out.shape == (b, s, h, d)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128), (128, 256)])
def test_bwd_kernel_all_grads_match_reference(causal, block_q, block_k):
    # dq/dk/dv from the Pallas backward kernels vs XLA reference VJP,
    # including unequal block sizes (regression: tail-block fallback check).
    import jax
    bh, s, d = 2, 256, 32
    q, k, v = (jnp.asarray(_r(bh, s, d)) for _ in range(3))
    g = jnp.asarray(_r(bh, s, d))
    from paddle_tpu.kernels.flash_attention import _flash_core

    def f(a, b_, c):
        return (_flash_core(a, b_, c, causal, block_q, block_k, True) * g).sum()

    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(
        lambda a, b_, c: (_reference_bhsd(a, b_, c, causal) * g).sum(),
        argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=2e-4, atol=2e-4)


def test_unequal_blocks_ragged_for_one_falls_back():
    # seq divisible by block_q but not block_k must NOT take the kernel path
    b, s, h, d = 1, 384, 1, 32   # 384 % 128 == 0, 384 % 256 != 0
    out = flash_attention_arrays(jnp.asarray(_r(b, s, h, d)),
                                 jnp.asarray(_r(b, s, h, d)),
                                 jnp.asarray(_r(b, s, h, d)),
                                 causal=True, block_q=128, block_k=256)
    assert out.shape == (b, s, h, d)


@pytest.mark.parametrize("causal", [False, True])
def test_bf16_native_dtype_path_matches_reference(causal):
    # the kernels keep dots in the INPUT dtype (bf16 MXU path); parity vs
    # a float32 oracle within bf16 tolerance, fwd and all three grads
    import jax
    bh, s, d = 2, 256, 64
    # centered inputs (realistic activation stats): all-positive q/k make
    # near-one-hot softmaxes whose grad cancellation amplifies bf16 noise
    q, k, v = (jnp.asarray(_r(bh, s, d) - 0.5).astype(jnp.bfloat16)
               for _ in range(3))
    # oracle sees the SAME bf16-quantized values in f32, so the comparison
    # isolates kernel error from input quantization
    q32, k32, v32 = (a.astype(jnp.float32) for a in (q, k, v))
    from paddle_tpu.kernels.flash_attention import _flash_core

    out = _flash_core(q, k, v, causal, 128, 128, True)
    assert out.dtype == jnp.bfloat16
    want = _reference_bhsd(q32, k32, v32, causal)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)

    def f(a, b_, c):
        return (_flash_core(a, b_, c, causal, 128, 128, True)
                .astype(jnp.float32) ** 2).sum()

    def ref(a, b_, c):
        return (_reference_bhsd(a, b_, c, causal)
                .astype(jnp.float32) ** 2).sum()

    grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(ref, argnums=(0, 1, 2))(q32, k32, v32)
    for got, w, nm in zip(grads, wants, ("dq", "dk", "dv")):
        ga = np.asarray(got, dtype=np.float32)
        wa = np.asarray(w)
        rel = np.abs(ga - wa).max() / (np.abs(wa).max() + 1e-9)
        assert rel < 6e-2, (nm, rel)


def test_mixed_dtype_inputs_promoted():
    # fp32 KV cache against bf16 activations: promoted, no trace error
    b, s, h, d = 1, 128, 2, 32
    q = jnp.asarray(_r(b, s, h, d)).astype(jnp.bfloat16)
    k = jnp.asarray(_r(b, s, h, d))
    v = jnp.asarray(_r(b, s, h, d))
    out = flash_attention_arrays(q, k, v, causal=True, block_q=128, block_k=128)
    assert out.shape == (b, s, h, d)
    assert out.dtype == jnp.float32


class TestFusedBackwardParity:
    def test_fused_matches_two_pass(self):
        """The fused single-pass backward is the tested-equal alternative
        to the default two-pass path — their gradients must agree (shared
        _bwd_tile_pds math, independent loop structures)."""
        import importlib
        fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
        import jax.numpy as jnp
        rng = np.random.RandomState(0)
        bh, s, d = 2, 256, 32
        bq = bk = 128
        q = jnp.asarray(rng.randn(bh, s, d).astype(np.float32) * 0.2)
        k = jnp.asarray(rng.randn(bh, s, d).astype(np.float32) * 0.2)
        v = jnp.asarray(rng.randn(bh, s, d).astype(np.float32) * 0.2)
        g = jnp.asarray(rng.randn(bh, s, d).astype(np.float32))
        for causal in (False, True):
            out, lse = fa._flash_fwd_bhsd(q, k, v, causal=causal, block_q=bq,
                                          block_k=bk, interpret=True)
            two = fa._flash_bwd_bhsd(q, k, v, out, lse, g, causal=causal,
                                     block_q=bq, block_k=bk, interpret=True)
            fused = fa._flash_bwd_fused_bhsd(q, k, v, out, lse, g,
                                             causal=causal, block_q=bq,
                                             block_k=bk, interpret=True)
            for a, b, nm in zip(two, fused, ("dq", "dk", "dv")):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                    err_msg=f"{nm} mismatch (causal={causal})")


# ---- the dispatcher's own predicate (what chip_smoke.py prints) -------------

@pytest.mark.parametrize("s,d,dtype,want", [
    # bf16: the fused backward's resident set fits up to s8192 at d<=128
    (1024, 64, "bfloat16", (512, 512, "pallas", "fused")),
    (8192, 64, "bfloat16", (512, 512, "pallas", "fused")),
    (8192, 128, "bfloat16", (512, 512, "pallas", "fused")),
    (4096, 256, "bfloat16", (512, 512, "pallas", "fused")),
    # d=64 occupies the 128 lanes d=128 does: s16384 streams (the guard
    # that counted d sent it to a fused kernel the compiler refused)
    (16384, 64, "bfloat16", (512, 512, "pallas", "two_pass")),
    (8192, 256, "bfloat16", (512, 512, "pallas", "two_pass")),
    # fp32 doubles every stream
    (4096, 64, "float32", (512, 512, "pallas", "fused")),
    (8192, 64, "float32", (512, 512, "pallas", "two_pass")),
    # short sequences shrink the blocks to the sequence
    (256, 64, "bfloat16", (256, 256, "pallas", "fused")),
    # a length no block divides takes the XLA reference, backward included
    (1000, 64, "bfloat16", (512, 512, "reference", "reference")),
    (640, 64, "bfloat16", (512, 512, "reference", "reference")),
])
def test_dispatch_plan(s, d, dtype, want):
    import importlib
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    assert fa.dispatch_plan(s, d, dtype) == want


def test_vmem_request_counts_padded_lanes_and_both_buffers():
    """A [S, 64] bf16 block occupies what [S, 128] does; every pipelined
    block is double-buffered; the request never drops under Mosaic's
    default nor passes the cap."""
    import importlib
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    assert fa._padded_bytes((8192, 64), jnp.bfloat16) \
        == fa._padded_bytes((8192, 128), jnp.bfloat16) == 8192 * 128 * 2
    # second-minor pads to the sublane tile: 8 rows of f32, 16 of bf16
    assert fa._padded_bytes((1, 1, 512), jnp.float32) == 8 * 512 * 4
    assert fa._padded_bytes((3, 512), jnp.bfloat16) == 16 * 512 * 2
    assert fa._VMEM_DEFAULT < fa._FUSED_BWD_VMEM_CAP * 2 <= fa._VMEM_MAX
