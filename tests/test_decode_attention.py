"""`kernels.decode_attention` interpreted on the CPU, against the dense
read of `ErnieSelfAttention.forward_cached` on the same inputs, in float32.

The serve cell's `correct` compares a full forward and the first (prefill)
token with the reference and never a decode step's logits, so these cases
(and `chip_smoke.py`'s kernel phase, on the chip) are what hold the decode
path's numbers.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle

# the package would hand back a function of the module's name, were one exported
da = importlib.import_module("paddle_tpu.kernels.decode_attention")

HEADS, HEAD_DIM = 5, 16         # heads not a multiple of the 8 sublanes
MAX_LEN, BLOCK = 64, 16
PAGE = MAX_LEN + 2              # the decode block's rows: no block divides 66


def _dense(q, k_page, v_page, pos, heads):
    """The dense path's own lines (`models/ernie.py`): every row of the
    page under a validity mask."""
    b, t, width = q.shape
    split = lambda x: jnp.swapaxes(
        x.reshape(b, -1, heads, width // heads), 1, 2)
    qh, kh, vh = split(q), split(k_page), split(v_page)
    logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh) / np.sqrt(width // heads)
    span = jnp.arange(kh.shape[2], dtype=pos.dtype)
    qpos = pos[:, None] + jnp.arange(t, dtype=pos.dtype)
    valid = span[None, None, None, :] <= qpos[:, None, :, None]
    probs = jax.nn.softmax(jnp.where(valid, logits, -1e9), axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.swapaxes(out, 1, 2).reshape(b, t, width)


def _kernel(q, k_page, v_page, pos, block=BLOCK):
    return da._attend(jnp.asarray(q), jnp.asarray(k_page),
                      jnp.asarray(v_page), jnp.asarray(pos) + q.shape[1],
                      num_heads=HEADS, block_k=block, mxu=jnp.float32,
                      interpret=True)


def _inputs(positions, t, seed=0, page=PAGE):
    rng = np.random.default_rng(seed)
    shape = (len(positions), page, HEADS * HEAD_DIM)
    return (rng.standard_normal((len(positions), t, shape[2]), np.float32),
            rng.standard_normal(shape, np.float32),
            rng.standard_normal(shape, np.float32),
            np.asarray(positions, np.int32))


@pytest.mark.parametrize("t", [1, 2], ids=["T1", "T2"])
@pytest.mark.parametrize("pos", [0, 1, 7, 15, 16, 40, MAX_LEN - 1],
                         ids=["at_0", "at_1", "mid_block", "block_edge_below",
                              "block_edge_above", "third_block",
                              "max_len_less_1_ragged_tail"])
def test_kernel_is_the_dense_read_of_the_live_rows(pos, t):
    """A row at `pos` between a free slot (position 0) and a row in
    another block: the dense path's numbers; with every row at or above a
    slot's length poisoned, the same numbers, finite: nothing above a
    length reaches the result. With T = 2 the junk query row (i = 1) sees
    key pos + 1 and the real row does not."""
    q, kp, vp, positions = _inputs([0, pos, 23], t, seed=pos)
    want = np.asarray(_dense(jnp.asarray(q), jnp.asarray(kp),
                             jnp.asarray(vp), jnp.asarray(positions), HEADS))
    got = np.asarray(_kernel(q, kp, vp, positions))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)

    kn, vn = kp.copy(), vp.copy()
    for r, p in enumerate(positions):
        kn[r, p + t:] = np.nan
        vn[r, p + t:] = np.nan
    poisoned = np.asarray(_kernel(q, kn, vn, positions))
    assert np.isfinite(poisoned).all()
    np.testing.assert_array_equal(poisoned, got)

    if t == 2:
        vp2 = vp.copy()
        vp2[1, pos + 1] += 1.0
        moved = np.asarray(_kernel(q, kp, vp2, positions))
        np.testing.assert_array_equal(moved[1, 0], got[1, 0])
        assert np.abs(moved[1, 1] - got[1, 1]).max() > 1e-3
        np.testing.assert_array_equal(moved[[0, 2]], got[[0, 2]])


@pytest.mark.parametrize("page", [PAGE, 300],
                         ids=["page_under_one_block", "page_of_2_blocks_and_44"])
def test_entry_point_picks_its_block_from_the_page(page):
    """`decode_attention` as `forward_cached` calls it: interpreted off a
    TPU, operands in their own dtype, the block `BLOCK_K` or the whole of
    a shorter page."""
    q, kp, vp, positions = _inputs([0, 1, page - 3, page // 2], 2, page=page)
    want = _dense(*map(jnp.asarray, (q, kp, vp, positions)), HEADS)
    got = da.decode_attention(*map(jnp.asarray, (q, kp, vp, positions)),
                              HEADS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5)


def test_engages_on_what_the_code_can_observe(monkeypatch):
    """A TPU, a decode block of rows, floating-point pages, the default
    matmul precision: anything else keeps the dense einsums."""
    assert not da.engages(2, jnp.float32)           # the CPU
    monkeypatch.setattr(da, "_on_tpu", lambda: True)
    assert da.engages(1, jnp.float32) and da.engages(2, jnp.bfloat16)
    assert da.engages(da.MAX_QUERY_ROWS, jnp.float32)
    assert not da.engages(64, jnp.float32)          # a prompt bucket
    assert not da.engages(2, jnp.int8)              # kv_int8 pages
    paddle.set_flags({"FLAGS_tpu_matmul_precision": "highest"})
    try:
        assert not da.engages(2, jnp.float32)
    finally:
        paddle.set_flags({"FLAGS_tpu_matmul_precision": "default"})
