"""The `dots_serve` runner end to end at tiny widths on the CPU, and the
unit tests of what came with it: `dots_cost.py` and the precision control.
Counts and control flow only: no number from here is a speed."""
import numpy as np

from benchmarks import dots_cost, dots_precision_control, harness
from benchmarks.runners import dots_serve

from .test_rehearsal import _run

CFG = harness.load_json("configs", "dots_vlm1.json")
CELL = "dots_vlm1.serve_long_context"


def test_dots_runner_takes_a_cell_as_data_files(tmp_path):
    ctx, _, res = _run("tiny_dots.serve", 2.0, False, tmp_path)
    assert res["checks"] == {
        "logits_match_reference": True,
        "cached_path_matches_reference": True,
        "routing_matches_reference": True,
        "routing_is_decided_often": True,
        "routing_agreement_holds": True,
        "router_keeps_its_precision": True,
        "engine_matches_reference": True,
        "latent_read_keeps_its_precision": True,
        "streamed_tokens_are_the_engines_own": True,
        "streamed_tokens_are_reference_argmax": True,
        "streams_have_the_tokens_asked": True}
    assert not any(ctx.compiled_in_window().values())
    assert res["attempted"] == 12 and res["failed"] == 0


def test_traced_dots_run_feeds_the_counter_readers(tmp_path):
    ctx, setup, res = _run("tiny_dots.serve", 3.0, True, tmp_path)
    evidence = dict(res["evidence"], setup=setup, trace=None)
    got = harness.per_layer_metrics(harness.load_benchmark(), CELL, evidence)
    # no TPU plane in a CPU trace: the device_trace metrics are left out
    assert set(got) == {"server_itl_ms.serve", "slots_per_step.serve",
                        "compile_s.setup", "cache_hits.setup",
                        "decode_ahead_share.serve", "kv_live_share.serve",
                        "gen_late_p95_ms.serve", "queue_wait_ms.serve",
                        "prefill_ms.serve"}
    assert 0.0 < got["kv_live_share.serve"]["value"] < 100.0
    before, after = res["evidence"]["monitor"]
    grew = lambda name: (after["counters"][name]
                         - before["counters"].get(name, 0))
    tiny = harness.load_cell("tiny_dots.serve", base="tests")
    slots = tiny["engine"]["num_slots"]
    assert grew("llm.decode.kv_rows_pool") == \
        grew("llm.decode.steps") * slots * tiny["engine"]["max_len"]
    assert 0 < grew("llm.decode.kv_rows_live") < grew("llm.decode.kv_rows_pool")
    assert 0 < grew("llm.decode.rows") <= grew("llm.decode.steps") * slots
    assert "llm.decode.state_bytes" not in after["counters"]
    assert after["gauges"]["moe.experts_held"] == 8
    assert after["gauges"]["moe.experts_total"] == 32


def test_the_check_rows_reach_every_bucket_and_a_position_past_8192():
    cell = harness.load_cell(CELL)
    buckets = cell["engine"]["prefill_buckets"]
    for seed in (0, 2 ** 31 + 17):
        ids, n, width = dots_serve.check_rows(cell, seed)
        assert len(n) == cell["engine"]["num_slots"]
        assert {next(b for b in buckets if b >= m) for m in n} == set(buckets)
        steps = cell["check"]["decode_tokens"]
        assert (n + steps).max() > 8192 and np.all(n + steps <= width)
        assert ids.max() < cell["config_sizes"]["vocab_size"]
        order = dots_serve._one_of_every_group(cell["check"]["rows"],
                                               cell["check"]["full_rows"])
        assert len(set(width[order].tolist())) == 3
        # the cached-path check's rows fit its prompt program
        few = cell["check"]["prompts"]
        assert n[-few:].max() <= cell["check"]["pad_to"]


def test_the_control_moves_what_the_read_alone_reads(monkeypatch):
    """The control at tiny widths with the kernel interpreted (on the CPU
    the decode step takes the dense read: the test steers it through the
    kernel, as a TPU does): the same engine, programs and comparisons; only
    the kernel's statistics differ. (A tiny page is one block of a few rows,
    so the control's reading is smaller than on the chip, where the limit
    lies between the chip's two readings: here the two readings are held
    apart.)"""
    from paddle_tpu.kernels import mla_decode
    monkeypatch.setattr(mla_decode, "engages", lambda dtype: True)
    tiny = harness.load_cell("tiny_dots.serve", base="tests")
    lm = dots_serve.build_model(tiny["config_sizes"], 5)
    engine = dots_serve.make_engine(lm, tiny["engine"])
    try:
        found = dots_serve.run_checks(lm, engine, tiny, 5, lambda m: None)
    finally:
        engine.stop(drain=False)
    assert all(found["checks"].values()), found["checks"]
    assert found["readings"]["latent_read"] < 1e-5
    out = dots_precision_control.readings(tiny, 5, say=lambda m: None)
    low = out["statistics_in_bfloat16"]
    assert low["readings"]["latent_read"] > 1e-3
    assert low["checks"]["engine_matches_reference"]


def test_cost_functions_at_the_published_widths():
    assert dots_cost.layers_held(CFG) == [0, 3, 4, 5, 6]
    assert dots_cost.page_row_bytes(CFG) == 1280
    # 1,280 B a live row a layer, five layers
    assert dots_cost.mla_step_bytes(CFG, 1000.0) == 1000 * 1280 * 5
    # 16 rows reach 6.4 of the 16 held experts a layer in expectation
    assert abs(dots_cost.experts_reached(CFG, 16) - 6.37) < 0.01
    # the file's own arithmetic: 4.566 B parameters
    h, e = CFG["hidden_size"], CFG["moe_intermediate_size"]
    heads = CFG["num_attention_heads"]
    mixer = (h * CFG["q_lora_rank"] + CFG["q_lora_rank"] * heads * 192
             + h * 576 + CFG["kv_lora_rank"] * heads * 256 + heads * 128 * h)
    expert = 3 * h * e
    total = (mixer + 3 * h * CFG["intermediate_size"]
             + 4 * (mixer + (CFG["n_routed_experts"] + 1) * expert + h * 256)
             + 2 * CFG["vocab_size"] * h)
    assert abs(total / 1e9 - 4.566) < 0.002
