"""The `lfm2_serve` runner end to end at tiny widths on the CPU, and the
unit tests of what came with it: `lfm2_cost.py`, the step-span reader and
the check's rows. Counts and control flow only: no number from here is a
speed."""
import numpy as np

from benchmarks import harness, lfm2_cost
from benchmarks.readers import step_span_roofline
from benchmarks.runners import lfm2_serve

from .test_rehearsal import _run

CFG = harness.load_json("configs", "lfm2_24b_a2b.json")
CELL = "lfm2_24b_a2b.serve_many_chats"


def test_lfm2_runner_takes_a_cell_as_data_files(tmp_path):
    ctx, _, res = _run("tiny_lfm2.serve", 2.0, False, tmp_path)
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


def test_traced_lfm2_run_feeds_the_counter_readers(tmp_path):
    ctx, setup, res = _run("tiny_lfm2.serve", 3.0, True, tmp_path)
    evidence = dict(res["evidence"], setup=setup, trace=None)
    got = harness.per_layer_metrics(harness.load_benchmark(), CELL, evidence)
    # no TPU plane in a CPU trace: the device_trace metrics are left out
    assert set(got) == {"server_itl_ms.serve", "slots_per_step.serve",
                        "compile_s.setup", "cache_hits.setup",
                        "decode_ahead_share.serve", "kv_live_share.serve",
                        "gen_late_p95_ms.serve", "queue_wait_ms.serve",
                        "prefill_ms.serve", "experts_reached_share.serve"}
    assert 0 < got["experts_reached_share.serve"]["value"] <= 100
    before, after = res["evidence"]["monitor"]
    grew = lambda name: (after["counters"][name]
                         - before["counters"].get(name, 0))
    steps = grew("llm.decode.steps")
    assert grew("moe.decode.experts_read_max") == steps * 8 * 16
    assert grew("moe.decode.routes_reached") == grew("llm.decode.rows") * 32
    assert grew("llm.decode.state_bytes") == steps * 4 * 8 * 2 * 64 * 4


def test_the_check_rows_fill_every_slot_and_reach_every_bucket():
    cell = harness.load_cell(CELL)
    buckets = cell["engine"]["prefill_buckets"]
    for seed in (0, 2 ** 31 + 17):
        ids, n, width = lfm2_serve.check_rows(cell, seed)
        assert len(n) == cell["engine"]["num_slots"]
        assert {next(b for b in buckets if b >= m) for m in n} == set(buckets)
        steps = cell["check"]["decode_tokens"]
        assert np.all(n + steps <= width)
        assert ids.max() < cell["config_sizes"]["vocab_size"]


def test_cost_function_and_the_file_arithmetic_at_the_published_widths():
    assert lfm2_cost.layers_held(CFG) == list(range(10))
    assert lfm2_cost.expert_layers(CFG) == 8
    assert lfm2_serve.kinds(CFG) == [
        ("conv", "dense"), ("conv", "dense"), ("attention", "moe"),
        ("conv", "moe"), ("conv", "moe"), ("conv", "moe"),
        ("attention", "moe"), ("conv", "moe"), ("conv", "moe"),
        ("conv", "moe")]
    assert lfm2_cost.expert_bytes(CFG) == 3 * 2048 * 1536 * 2
    # every expert of the 8 layers once, and 40 rows x 4 routes a layer
    got = lfm2_cost.expert_read_bytes(CFG, 8 * 64, 8 * 160)
    assert got == 8 * 64 * 18874368 + 8 * 160 * 2 * (2048 + 1536) * 2
    assert 9.6e9 < got < 9.7e9
    assert abs(lfm2_cost.experts_reached(CFG, 40) - 59.2) < 0.1
    h, v = CFG["hidden_size"], CFG["vocab_size"]
    conv = h * 3 * h + 3 * h + h * h
    attention = 2 * h * h + 2 * h * 512 + 2 * 64
    dense = 3 * h * CFG["intermediate_size"]
    expert = CFG["num_experts"] * 3 * h * 1536 + h * 64
    total = (2 * (conv + dense) + 2 * (attention + expert)
             + 6 * (conv + expert) + 2 * v * h)
    assert abs(conv / 1e6 - 16.78) < 0.01
    assert abs(expert / 1e6 - 604.11) < 0.01
    assert abs(total / 1e9 - 5.401) < 0.001


def test_a_step_is_matched_to_the_first_read_that_ends_after_it():
    """Each execution takes the first read not yet taken that ends after
    it: a read of an execution before the slice is passed over, a read
    delayed past the next execution's end still goes to its own."""
    runs = [(10.0, 20.0), (20.0, 30.0), (30.0, 40.0)]
    reads = [(5.0, 8.0, (1,)), (15.0, 31.0, (2,)), (31.5, 32.0, (3,)),
             (34.0, 41.0, (4,))]
    got = step_span_roofline.match(runs, reads)
    assert [v for _, v in got] == [(2,), (3,), (4,)]
    # the last execution's read fell after the trace: it is left out
    assert len(step_span_roofline.match(runs, reads[:3])) == 2
