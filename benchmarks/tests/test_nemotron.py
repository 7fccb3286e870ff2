"""The `nemotron_serve` runner end to end at tiny widths on the CPU, and
the unit tests of what came with it: `nemotron_cost.py` and the check's
rows. Counts and control flow only: no number from here is a speed."""
import numpy as np

from benchmarks import harness, nemotron_cost
from benchmarks.runners import nemotron_serve

from .test_rehearsal import _run

CFG = harness.load_json("configs", "nemotron3_nano.json")
CELL = "nemotron3_nano.serve_chat"


def test_nemotron_runner_takes_a_cell_as_data_files(tmp_path):
    ctx, _, res = _run("tiny_nemotron.serve", 2.0, False, tmp_path)
    assert res["checks"] == {
        "logits_match_reference": True,
        "cached_path_matches_reference": True,
        "routing_matches_reference": True,
        "routing_is_decided_often": True,
        "routing_agreement_holds": True,
        "router_keeps_its_precision": True,
        "engine_matches_reference": True,
        "engine_state_keeps_its_precision": True,
        "streamed_tokens_are_the_engines_own": True,
        "streamed_tokens_are_reference_argmax": True,
        "streams_have_the_tokens_asked": True}
    assert not any(ctx.compiled_in_window().values())
    assert res["attempted"] == 12 and res["failed"] == 0


def test_traced_nemotron_run_feeds_the_counter_readers(tmp_path):
    ctx, setup, res = _run("tiny_nemotron.serve", 3.0, True, tmp_path)
    evidence = dict(res["evidence"], setup=setup, trace=None)
    got = harness.per_layer_metrics(harness.load_benchmark(), CELL, evidence)
    # no TPU plane in a CPU trace: the device_trace metrics are left out
    assert set(got) == {"server_itl_ms.serve", "slots_per_step.serve",
                        "compile_s.setup", "cache_hits.setup",
                        "decode_ahead_share.serve", "kv_live_share.serve",
                        "gen_late_p95_ms.serve", "queue_wait_ms.serve",
                        "prefill_ms.serve"}
    before, after = res["evidence"]["monitor"]
    grew = lambda name: (after["counters"][name]
                         - before["counters"].get(name, 0))
    tiny = harness.load_cell("tiny_nemotron.serve", base="tests")
    slots, sizes = tiny["engine"]["num_slots"], tiny["config_sizes"]
    steps = grew("llm.decode.steps")
    assert grew("llm.decode.state_bytes") == steps * slots * 4 * (
        4 * 16 * 16 * 4 + 3 * (64 + 64) * 4)
    live, pool = (grew(f"llm.decode.kv_rows_{k}") for k in ("live", "pool"))
    assert 0 < live < pool
    assert after["gauges"]["moe.experts_held"] == 8
    assert after["gauges"]["moe.experts_total"] == 32
    assert nemotron_cost.mamba_layers(sizes) == 4


def test_the_check_rows_fill_every_slot_and_reach_every_bucket():
    cell = harness.load_cell(CELL)
    buckets = cell["engine"]["prefill_buckets"]
    for seed in (0, 2 ** 31 + 17):
        ids, n, width = nemotron_serve.check_rows(cell, seed)
        assert len(n) == cell["engine"]["num_slots"]
        assert {next(b for b in buckets if b >= m) for m in n} == set(buckets)
        steps = cell["check"]["decode_tokens"]
        assert np.all(n + steps <= width)
        assert ids.max() < cell["config_sizes"]["vocab_size"]


def test_cost_function_and_the_file_arithmetic_at_the_published_widths():
    assert nemotron_cost.layers_held(CFG) == list(range(9))
    assert nemotron_cost.mamba_layers(CFG) == 4
    assert nemotron_serve.kinds(CFG) == [
        "mamba", "moe", "mamba", "moe", "mamba", "attention", "moe", "mamba",
        "moe"]
    # 256 states of 2 MiB read and written, four layers: 4.29 GB and a
    # little for x, Delta, B, C and y
    got = nemotron_cost.ssd_step_bytes(CFG, 256)
    assert got == 4 * 256 * 4 * (2 * 64 * 64 * 128 + 2 * 64 * 64 + 64
                                 + 2 * 8 * 128)
    assert 4.29e9 < got < 4.34e9
    h, e, s = (CFG["hidden_size"], CFG["moe_intermediate_size"],
               CFG["moe_shared_expert_intermediate_size"])
    inner = CFG["mamba_num_heads"] * CFG["mamba_head_dim"]
    conv = inner + 2 * CFG["n_groups"] * CFG["ssm_state_size"]
    mamba = (h * (inner + conv + 64) + 4 * conv + conv + 3 * 64 + inner
             + inner * h)
    expert = CFG["n_routed_experts"] * 2 * h * e + 2 * h * s + h * 128
    attention = 2 * h * 4096 + 2 * h * 256
    total = 4 * mamba + 4 * expert + attention + 2 * CFG["vocab_size"] * h
    assert abs(mamba / 1e6 - 38.74) < 0.01
    assert abs(expert / 1e6 - 179.95) < 0.01
    assert abs(total / 1e6 - 986.2) < 0.1
