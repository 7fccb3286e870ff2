"""The `brumby_serve` runner end to end at tiny widths on the CPU, and the
unit tests of what came with it: `retention_cost.py`, the `named_op` and
`monitor_counter_share` readers. Counts and control flow only: no number
from here is a speed."""
import json

import pytest

from benchmarks import harness, retention_cost, state_precision_control
from benchmarks.readers import monitor_counter_share, named_op

from .test_rehearsal import _run

CFG = harness.load_json("configs", "brumby_14b.json")


def test_brumby_runner_takes_a_cell_as_data_files(tmp_path):
    ctx, _, res = _run("tiny_brumby.serve", 2.0, False, tmp_path)
    assert res["checks"] == {
        "logits_match_reference": True,
        "state_path_matches_reference": True,
        "state_adds_no_error": True,
        "engine_matches_reference": True,
        "engine_state_keeps_its_precision": True,
        "streamed_tokens_are_reference_argmax": True,
        "streams_have_the_tokens_asked": True}
    assert not any(ctx.compiled_in_window().values())
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["end_to_end"]) == {"ttft_p50_ms", "itl_p50_ms",
                                      "itl_p99_ms"}


def test_traced_brumby_run_feeds_the_counter_readers(tmp_path):
    ctx, setup, res = _run("tiny_brumby.serve", 3.0, True, tmp_path)
    evidence = dict(res["evidence"], setup=setup, trace=None)
    got = harness.per_layer_metrics(harness.load_benchmark(),
                                    "brumby_14b.serve_long_prompt", evidence)
    # no TPU plane in a CPU trace: the device_trace metrics are left out
    assert set(got) == {"server_itl_ms.serve", "slots_per_step.serve",
                        "compile_s.setup", "cache_hits.setup",
                        "prefill_padding_share.serve"}
    assert 0.0 < got["prefill_padding_share.serve"]["value"] < 100.0
    before, after = res["evidence"]["monitor"]
    steps = (after["counters"]["llm.decode.steps"]
             - before["counters"].get("llm.decode.steps", 0))
    grew = (after["counters"]["llm.decode.state_bytes"]
            - before["counters"].get("llm.decode.state_bytes", 0))
    tiny = harness.load_cell("tiny_brumby.serve", base="tests")
    # the counter's bytes a step = what retention_cost says is held
    assert grew == steps * retention_cost.state_bytes(
        tiny["config_sizes"], tiny["engine"]["num_slots"])


def test_a_state_held_in_bfloat16_fails_the_engine_check():
    """The control at tiny widths: the same engine, programs and
    comparison; only what the state is rounded to between steps differs."""
    tiny = harness.load_cell("tiny_brumby.serve", base="tests")
    out = state_precision_control.readings(tiny, 5, say=lambda m: None)
    assert out["float32"]["correct"] and not out["bfloat16"]["correct"]
    assert out["float32"]["growth"] < 1.08 < out["bfloat16"]["growth"]
    assert len(out["n"]) == tiny["engine"]["num_slots"]


def test_the_engine_check_tells_a_neighbours_slot(monkeypatch):
    """Every slot is compared: a prefill written one slot off (or a decode
    step that reads a neighbour's state) is not correct."""
    from paddle_tpu.serving import LLMConfig, LLMEngine

    from benchmarks.runners import brumby_serve as runner
    tiny = harness.load_cell("tiny_brumby.serve", base="tests")
    sizes, steps = tiny["config_sizes"], tiny["check"]["decode_tokens"]
    lm = runner.build_model(sizes, 7)
    ids, n = runner.check_rows(tiny, 7)
    ref = runner.reference_logits(lm, sizes, ids, n, steps)

    def engine():
        return LLMEngine(lm, LLMConfig(
            num_slots=4, max_len=128, prefill_buckets=(16, 32, 64),
            warmup_on_start=False))

    good = runner.engine_logits(engine(), ids, n, steps)
    assert runner.rel_err(good, ref) < 1e-4
    write = LLMEngine._prefill_slot
    monkeypatch.setattr(
        LLMEngine, "_prefill_slot",
        lambda self, prompt, slot, rid=0: write(
            self, prompt, slot + 1 if slot == 2 else slot, rid))
    bad = runner.engine_logits(engine(), ids, n, steps)
    assert runner.rel_err(bad[:, 0], ref[:, 0]) < 1e-4    # the prefill's own
    assert runner.rel_err(bad, ref) > runner.STATE_TOL


def test_retention_cost_from_the_configuration():
    # 8 layers x 12 slots x 8 heads x 8320 rows x 129 columns x 4 bytes
    assert retention_cost.state_bytes(CFG, 12) == 3_297_116_160
    assert retention_cost.state_bytes(CFG, 1) == \
        8 * CFG["state"]["bytes_per_layer_and_slot"]
    assert retention_cost.step_bytes(CFG, 12) == 2 * 3_297_116_160
    assert retention_cost.step_bytes(CFG, 12, "matrix") == \
        2 * 8 * 12 * 8 * 8320 * 128 * 4
    with pytest.raises(ValueError):
        retention_cost.step_bytes(CFG, 12, "half")
    # bound by memory: a dozen operations a 4-byte entry moved twice
    assert retention_cost.step_flops(CFG, 12) == \
        8 * 12 * 8320 * 129 * (3 * 8 + 2 * 40)
    assert retention_cost.roofline_share_pct(8.19e9, 0.02, 8.19e11) == \
        pytest.approx(50.0)
    held = CFG["state"]["rows_held"]
    assert held % 128 == 0 and 0 <= held - CFG["state"]["rows"] < 128
    assert CFG["state"]["rows"] == CFG["head_dim"] * (CFG["head_dim"] + 1) // 2


def test_named_op_matches_a_kernel_by_its_instruction_name():
    mine = named_op.matcher("power_retention_step")
    assert mine("%power_retention_step.3 = (f32[12,8,128,8320]{3,2,1,0}"
                ", f32[12,8,8,128]) custom-call(...)")
    assert mine("power_retention_step")
    assert not mine("%power_retention_step_prologue.1 = f32[] fusion()")
    assert not mine("%fusion.7 = f32[12,8,128,8320] fusion(...)")


def test_named_op_reads_nothing_without_a_trace():
    for field in ("ms_per_run", "roofline_pct"):
        assert named_op.read({"trace": None}, field=field,
                             op="power_retention_step",
                             per="jit_llm_decode") is None
    with pytest.raises(ValueError):
        named_op.read({"trace": None}, field="ms", op="x", per="y")


def test_monitor_counter_share_is_a_percentage():
    snap = lambda real, bucket: {"counters": {
        "llm.prefill.tokens_real": real, "llm.prefill.tokens_bucket": bucket}}
    spec = harness.load_json("layer_metrics",
                             "prefill_padding_share.serve.json")
    evidence = {"monitor": (snap(100, 128), snap(100 + 1800, 128 + 2048))}
    assert monitor_counter_share.read(evidence, **spec["args"]) == \
        pytest.approx(100.0 * (1 - 1800 / 2048))
    assert monitor_counter_share.read({"monitor": None},
                                      **spec["args"]) is None


def test_the_new_cells_state_what_the_issue_gives():
    b = harness.load_cell("brumby_14b.serve_long_prompt")
    assert b["mix"]["prompt_tokens"] == {"median": 1024, "sigma": 0.7,
                                         "min": 256, "max": 4096}
    assert b["mix"]["output_tokens"] == {"median": 192, "sigma": 0.5,
                                         "min": 64, "max": 512}
    assert (b["mix"]["ramp_s"], b["mix"]["drain_s"],
            b["mix"]["schedule_seed"]) == (10.0, 25.0, 29)
    assert b["engine"] == {"num_slots": 12, "max_len": 8192,
                           "prefill_buckets": [512, 1024, 2048, 4096],
                           "queue_depth": 256}
    assert b["check"] == {"prompts": 4, "pad_to": 1024, "decode_tokens": 16}
    g = harness.load_cell("gpt2_large.serve_prefill")
    assert g["mix"]["prompt_tokens"] == {"median": 640, "sigma": 0.3,
                                         "min": 384, "max": 992}
    assert g["mix"]["output_tokens"] == {"median": 16, "sigma": 0.5,
                                         "min": 4, "max": 24}
    decode = harness.load_cell("gpt2_large.serve_decode")
    assert g["engine"] == decode["engine"] and g["runner"] == "llm_serve"
    catalog = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu",
               "hidden_size": 5120, "intermediate_size": 17408,
               "max_position_embeddings": 32768, "max_window_layers": 40,
               "model_type": "brumby", "num_attention_heads": 40,
               "num_hidden_layers": 40, "num_key_value_heads": 8,
               "rms_norm_eps": 1e-06, "rope_scaling": None,
               "rope_theta": 1000000, "sliding_window": None,
               "tie_word_embeddings": False, "use_sliding_window": False,
               "vocab_size": 151936}
    differs = sorted(k for k, v in catalog.items() if CFG.get(k, "-") != v)
    assert differs == CFG["reduced"] == ["num_hidden_layers"]
    assert set(CFG["assumed"]) >= {"power", "gate", "normaliser",
                                   "rope_and_qk_norm", "state_dtype"}
    assert json.dumps(CFG)  # plain data
