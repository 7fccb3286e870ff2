"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself only passes on a TPU (`main()` has no way round that);
these tests keep its control flow honest between chip runs: every phase
function runs end to end on 2-layer hidden-64 models, with the Pallas
kernels interpreted, and the four-chip phases on four of the eight
virtual CPU devices. They check nothing about speed.
"""
import json
import os
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import models  # noqa: E402
from paddle_tpu.parallel.topology import set_mesh  # noqa: E402

_TINY_ERNIE = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=128,
                   max_position_embeddings=64)


# 24 rows x 2 routes over the 16 experts held of 32: a decode step's tile
_TINY_ROUTED = dict(rows=24, top_k=2, held=16, total=32, hidden=32, width=128)


@pytest.fixture(autouse=True)
def _restore_process_state():
    """The phases switch the monitor on, pick a device and set a mesh, as
    a user's script would; put the process back for the next test file."""
    yield
    paddle.set_flags({"FLAGS_monitor": False})
    set_mesh(None)
    jax.config.update("jax_default_device", None)


@pytest.fixture(scope="module")
def lowerings():
    return chip_smoke.Lowerings()


def test_main_on_a_cpu_exits_nonzero_and_prints_no_ok(capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "needs a TPU" in err


def test_train_phase(lowerings, capsys):
    cfg = chip_smoke.TrainCfg(build=lambda: models.ErnieModel(**_TINY_ERNIE),
                              batch=4, seq=16, steps=3)
    line = chip_smoke.phase_train(cfg, "cpu", lowerings)
    assert line["check"]["loss_last"] < line["check"]["loss_first"]
    assert line["check"]["model"] == {"layers": 2, "hidden": 64, "heads": 4,
                                      "vocab": 512}
    assert line["timing"] == chip_smoke.NOTE
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == line


def test_train_phase_fails_when_a_check_does_not_hold(lowerings,
                                                      monkeypatch):
    """No phase carries a failed check past: a ledger that books a compile
    on the warm signature must end the phase."""
    ticks = iter(range(100))
    monkeypatch.setattr(chip_smoke, "_trace_compiles", lambda: next(ticks))
    cfg = chip_smoke.TrainCfg(build=lambda: models.ErnieModel(**_TINY_ERNIE),
                              batch=4, seq=16, steps=2)
    with pytest.raises(chip_smoke.SmokeFailure, match="compiled"):
        chip_smoke.phase_train(cfg, "cpu", lowerings)


def test_serve_phase(lowerings):
    from paddle_tpu.models.gpt import GPTModel
    cfg = chip_smoke.ServeCfg(
        build=lambda: GPTModel(vocab_size=128, hidden_size=64, num_layers=2,
                               num_heads=4, max_seq_len=64),
        num_slots=4, max_len=32, prefill_buckets=(8, 32), max_new_tokens=4,
        prompt_lens=(3, 9, 17))
    line = chip_smoke.phase_serve(cfg, "cpu", lowerings)
    assert line["check"]["first_token_top1_agrees"] == 3
    assert line["check"]["evictions_error"] == 0


def test_kernel_phase_interprets_the_kernel_on_a_cpu(monkeypatch):
    """`nn.functional` attention picks the kernel only on a TPU; the test
    answers "tpu" to that one question, and the kernel entry, which asks
    JAX itself, then interprets."""
    from paddle_tpu.nn.functional import attention
    monkeypatch.setattr(attention, "jax", types.SimpleNamespace(
        default_backend=lambda: "tpu", nn=jax.nn, random=jax.random))
    import importlib
    da = importlib.import_module("paddle_tpu.kernels.decode_attention")
    monkeypatch.setattr(da, "_on_tpu", lambda: True)
    routed = []
    attend = da.decode_attention
    monkeypatch.setattr(da, "decode_attention", lambda *a: routed.append(
        a[0].shape) or attend(*a))
    line = chip_smoke.phase_kernel(
        geometries=((1, 1024, 2, 64, True),),
        scan=dict(hidden=128, heads=2, ffn=256, layers=2, batch=1, seq=256),
        decode=dict(slots=3, page=34, heads=2, head_dim=16, rows=2,
                    positions=(0, 31, 9)),
        routed=_TINY_ROUTED, min_kernels=0)
    attn, scan, decode, experts = line["check"]["paths"]
    assert experts["routed_step"]["live_rows"] == 12
    assert (attn["forward"], attn["backward"]) == ("pallas", "fused")
    assert attn["tpu_custom_calls"] == scan["tpu_custom_calls"] == 0
    assert max(attn["rel_err"].values()) <= chip_smoke.BF16_TOL
    # the decode step went through the (interpreted) kernel once, and the
    # dense read it is compared with did not
    assert routed == [(3, 2, 32)]
    assert decode["rel_err"]["k_page"] == decode["rel_err"]["v_page"] == 0
    assert decode["rel_err"]["out"] <= 1e-5


def test_routed_step_holds_the_masked_pass_to_ragged_dot(monkeypatch):
    """Tiny, the grouped matmul interpreted as a TPU runs it: the pass with
    every other row routed nowhere goes through the kernel, what it is
    compared with through `lax.ragged_dot`, and fewer tiles hold rows."""
    import importlib
    gm = importlib.import_module("paddle_tpu.kernels.grouped_matmul")
    calls = []
    monkeypatch.setattr(gm, "grouped_matmul", lambda x, g, a, t, ws, tile:
                        calls.append(len(ws)) or gm._pallas(x, g, a, ws, tile,
                                                            True))
    row, _, _ = chip_smoke._routed_step(**_TINY_ROUTED, seed=0, min_kernels=0,
                                        tol=chip_smoke.BF16_TOL)
    assert calls == [2, 1]              # one trace: gate and up, then down
    assert row["rel_err"]["out"] <= 1e-2
    tiles = row["active_tiles"]
    assert 0 < tiles["half_masked"] < tiles["all_live"] <= 16
    assert set(row["pass_ms_smoke"]) == {"all_live", "half_masked"}


def test_routed_step_refuses_dead_rows_that_reach_experts(monkeypatch):
    """The check the chip run relies on: a layout that still gives the
    masked rows tiles cannot pass."""
    import importlib
    gm = importlib.import_module("paddle_tpu.kernels.grouped_matmul")
    layout = gm.layout
    monkeypatch.setattr(gm, "layout", lambda group_of, groups, tile: layout(
        group_of % groups, groups, tile))
    with pytest.raises(chip_smoke.SmokeFailure, match="still reach experts"):
        chip_smoke._routed_step(**_TINY_ROUTED, seed=0, min_kernels=0,
                                tol=chip_smoke.BF16_TOL)


def test_latent_phase_routes_both_forms_through_their_kernels(monkeypatch):
    """Tiny, interpreted: the decode step's read goes through `mla_decode`
    and the prompt through the flash kernel when latent attention engages,
    and what they are compared with goes through neither."""
    import importlib
    md = importlib.import_module("paddle_tpu.kernels.mla_decode")
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    monkeypatch.setattr(md, "engages", lambda dtype: True)
    routed = []
    read, flash = md.mla_decode, fa.flash_prompt_bhsd
    monkeypatch.setattr(md, "mla_decode", lambda *a, **k: routed.append(
        ("read", a[0].shape)) or read(*a, **k))
    monkeypatch.setattr(fa, "flash_prompt_bhsd", lambda *a, **k: routed.append(
        ("prompt", a[0].shape, k["name"])) or flash(*a, **k))
    line = chip_smoke.phase_latent(
        slots=3, page=300, heads=4, latent=24, rope=8, nope=16, v_dim=16,
        prompt=200, positions=(0, 299, 130), min_kernels=0, blocks=(128,))
    decode, prompt = line["check"]["paths"]
    assert routed[:2] == [("read", (3, 4, 128)),
                          ("prompt", (4, 200, 24), "mla_prefill")]
    assert decode["rel_err"]["out"] <= chip_smoke.BF16_TOL
    assert prompt["rel_err"]["out"] <= chip_smoke.BF16_TOL
    assert line["check"]["live_rows"] == 1 + 300 + 131
    assert set(line["check"]["read_ms_smoke"]) == {"block_128", "dense"}


def test_latent_phase_refuses_a_program_without_the_kernel():
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_latent(
            slots=2, page=64, heads=2, latent=24, rope=8, nope=16, v_dim=16,
            prompt=64, positions=(0, 63), blocks=())


def test_kernel_phase_refuses_a_program_without_the_kernel():
    """What the chip run relies on: with the default `min_kernels` a
    program whose compiled text holds no tpu_custom_call cannot pass."""
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_kernel(geometries=((1, 256, 2, 64, True),))


def test_spmd_phase_on_four_virtual_devices():
    cfg = chip_smoke.SpmdCfg(
        model=dict(_TINY_ERNIE, hidden_dropout_prob=0.0, use_mp=True),
        batch=8, seq=16, steps=3)
    line = chip_smoke.phase_spmd(cfg, jax.devices()[:4], "cpu")
    check = line["check"]
    assert check["mesh"] == {"dp": 2, "pp": 1, "sharding": 1, "mp": 2}
    assert len(check["devices_covered"]) == 4
    assert check["collectives"]["all-reduce"] > 0
    assert check["worst_rel_diff"] <= cfg.loss_rtol


def test_ring_phase_on_four_virtual_devices():
    line = chip_smoke.phase_ring(jax.devices()[:4], geometry=(1, 512, 2, 64),
                                 min_kernels=0)
    assert line["check"]["collective_permutes"] > 0
    assert max(line["check"]["rel_err"].values()) <= chip_smoke.BF16_TOL


def test_ring_phase_refuses_a_ring_without_the_kernel():
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_ring(jax.devices()[:4], geometry=(1, 512, 2, 64))


_TINY_SSD = dict(slots=3, heads=4, head_dim=8, groups=2, state=16, prompt=40,
                 length=29, page=40, q_heads=4, kv_heads=2, kv_dim=16)


def test_ssd_phase_interprets_both_forms_and_the_grouped_read(monkeypatch):
    """Tiny, interpreted: the step and the chunked prompt through their
    Pallas forms against the `jax.numpy` ones, and the grouped read
    through `decode_attention_gqa` when it engages, against the dense
    read that the comparison takes."""
    import importlib
    da = importlib.import_module("paddle_tpu.kernels.decode_attention")
    monkeypatch.setattr(da, "engages", lambda *a: True)
    read, seen = da.decode_attention_gqa, []
    monkeypatch.setattr(da, "decode_attention_gqa", lambda *a: seen.append(
        a[1].shape) or read(*a))
    line = chip_smoke.phase_ssd(**_TINY_SSD, min_kernels=0)
    step, chunked, grouped = line["check"]["paths"]
    assert step["step"] == [3, 4, 8] and chunked["chunked"] == [1, 40, 4, 8]
    assert max(step["rel_err"].values()) <= chip_smoke.SSD_TOL
    # one Mamba layer's bytes: every state read and written, the inputs
    assert step["step_bytes"] == 4 * 3 * (2 * 4 * 8 * 16 + 2 * 4 * 8 + 4
                                          + 2 * 2 * 16)
    assert step["step_gb_per_s"] is None      # no rate off the chip
    assert max(chunked["rel_err"].values()) <= chip_smoke.SSD_TOL
    assert grouped["rel_err"]["out"] <= chip_smoke.BF16_TOL
    assert seen == [(3, 40, 32)]


def test_ssd_phase_refuses_a_program_without_the_kernel():
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_ssd(**_TINY_SSD)


_TINY_LFM2 = dict(slots=3, page=40, heads=8, kv_heads=4, head_dim=16,
                  layers=(1, 2, 3), prompts=(21, 9), steps=3)


def test_lfm2_phase_reads_both_forms_and_follows_the_reference(monkeypatch):
    """Tiny, interpreted: the grouped read in both its forms against the
    dense read, then a tiny LFM2 (the rehearsal's configuration) holding
    one block of each kind through its cached path against the reference.
    """
    from benchmarks import harness
    sizes = harness.load_json("tests", "configs", "tiny_lfm2.json")
    line = chip_smoke.phase_lfm2(**_TINY_LFM2, sizes=sizes, min_kernels=0)
    wide, sliced, model = line["check"]["paths"]
    assert (wide["form"], sliced["form"]) == ("wide", "sliced")
    for read in (wide, sliced):
        assert read["read"] == [3, 40, 64]
        assert read["rel_err"]["out"] <= chip_smoke.BF16_TOL
        assert read["read_ms_smoke"] is None       # no time off the chip
    assert model["model_layers"] == [1, 2, 3]
    assert model["rel_err"] <= model["tolerance"]


def test_lfm2_phase_refuses_a_program_without_the_kernel():
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_lfm2(**_TINY_LFM2)
