"""The `ling_serve` runner end to end at tiny widths on the CPU, and the
unit tests of what came with it: `ling_cost.py`, the `named_op_roofline`
reader, the two precision controls. Counts and control flow only: no
number from here is a speed."""
import json

import numpy as np
import pytest

from benchmarks import harness, ling_cost, ling_precision_control
from benchmarks.readers import named_op, named_op_roofline
from benchmarks.runners import ling_serve

from .test_rehearsal import _run

CFG = harness.load_json("configs", "ling3_flash_vl.json")
CELL = "ling3_flash_vl.serve_long_answer"


def test_ling_runner_takes_a_cell_as_data_files(tmp_path):
    ctx, _, res = _run("tiny_ling.serve", 2.0, False, tmp_path)
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


def test_traced_ling_run_feeds_the_counter_readers(tmp_path):
    ctx, setup, res = _run("tiny_ling.serve", 3.0, True, tmp_path)
    evidence = dict(res["evidence"], setup=setup, trace=None)
    got = harness.per_layer_metrics(harness.load_benchmark(), CELL, evidence)
    # no TPU plane in a CPU trace: the device_trace metrics are left out
    assert set(got) == {"server_itl_ms.serve", "slots_per_step.serve",
                        "compile_s.setup", "cache_hits.setup",
                        "decode_ahead_share.serve", "kv_live_share.serve"}
    assert 0.0 < got["kv_live_share.serve"]["value"] < 100.0
    before, after = res["evidence"]["monitor"]
    grew = lambda name: (after["counters"][name]
                         - before["counters"].get(name, 0))
    tiny = harness.load_cell("tiny_ling.serve", base="tests")
    slots = tiny["engine"]["num_slots"]
    # the state group's bytes a step: the KDA states (what ling_cost says
    # a step rewrites, once) and the convolution's rows
    cfg = tiny["config_sizes"]
    conv = 3 * 3 * cfg["num_attention_heads"] * cfg["head_dim"] * 4
    assert grew("llm.decode.state_bytes") == grew("llm.decode.steps") * (
        ling_cost.kda_step_bytes(cfg, slots) // 2
        + slots * ling_cost.kda_layers(cfg) * conv)
    assert grew("llm.decode.kv_rows_pool") == \
        grew("llm.decode.steps") * slots * tiny["engine"]["max_len"]
    assert 0 < grew("llm.decode.rows") <= grew("llm.decode.steps") * slots
    assert after["gauges"]["moe.experts_held"] == 8
    assert after["gauges"]["moe.experts_total"] == 32


def test_both_precision_controls_move_what_the_checks_read():
    """The controls at tiny widths: the same engine, programs and
    comparisons; only what the state is rounded to between the programs'
    calls, or the router's product, differs. (The float32 rehearsal's
    state error is rounding alone, far under the chip's limit, which lies
    between the chip's two readings: here the two readings are held
    apart.)"""
    tiny = harness.load_cell("tiny_ling.serve", base="tests")
    out = ling_precision_control.readings(tiny, 5, say=lambda m: None)
    assert out["as_it_is"]["correct"]
    assert out["as_it_is"]["readings"]["state_error"][0] < 1e-5
    assert out["state_in_bfloat16"]["readings"]["state_error"][0] > 1e-3
    assert not out["router_in_bfloat16"]["correct"]
    assert out["as_it_is"]["routing"]["router_error"] < 1e-5 < 1e-3 < \
        out["router_in_bfloat16"]["router_error"]


def test_a_router_fed_other_rows_fails_the_routing_checks(monkeypatch):
    """The upper reading of the routing limits: every router reads its
    rows shifted by one position (at tiny widths: a score is a sigmoid
    whatever the width, so the reading's size carries over)."""
    import jax.numpy as jnp

    from paddle_tpu.nn.layer import routed_experts as layer
    was = layer.RoutedExperts.choose
    monkeypatch.setattr(
        layer.RoutedExperts, "choose",
        lambda self, m: was(self, m.__class__(jnp.roll(m._value, 1, axis=0))))
    tiny = harness.load_cell("tiny_ling.serve", base="tests")
    lm = ling_serve.build_model(tiny["config_sizes"], 7)
    engine = ling_serve.make_engine(lm, tiny["engine"])
    try:
        found = ling_serve.run_checks(lm, engine, tiny, 7, lambda m: None)
    finally:
        engine.stop(drain=False)
    routing, checks = found["routing"], found["checks"]
    assert routing["score_error"] > 2 * ling_serve.ROUTE_SCORE_TOL
    assert routing["agree_share"] < 0.5 * ling_serve.ROUTE_AGREE_MIN
    assert routing["decided_share"] < 0.2 * ling_serve.ROUTE_DECIDED_MIN
    assert not (checks["routing_matches_reference"]
                or checks["routing_agreement_holds"]
                or checks["routing_is_decided_often"])


def test_a_decided_choice_is_one_the_score_error_cannot_flip():
    """`_Tally`: a position is decided when the reference's margins exceed
    2 (experts) and 4 (groups) of ITS OWN score error; a decided choice
    that differs is counted against the program, an undecided one is not."""
    experts = np.array([[0, 1], [0, 1], [0, 2], [0, 2]])
    ref = {"experts": np.array([[1, 0], [0, 1], [0, 1], [0, 1]]),
           "margin": np.array([0.05, 0.05, 0.05, 0.05]),
           "group_margin": np.array([np.inf, 0.05, np.inf, np.inf]),
           "biased": np.zeros((4, 3))}
    got = np.zeros((4, 3))
    got[1, 2], got[2, 1], got[3, 0] = 0.02, 0.01, 0.03
    tally = ling_serve._Tally(steps=0)
    tally.add(4, [experts], [got], [ref])
    # 0: exact, agrees; 1: group margin 0.05 < 4 x 0.02, undecided; 2:
    # decided (0.05 > 2 x 0.01) and differs; 3: 0.05 < 2 x 0.03, undecided
    assert tally.readings() == {
        "score_error": 0.03, "choices": 4, "agree_share": 0.5,
        "decided_share": 0.5, "decided": 2, "decided_agree": 1}


def _plane(name, lines):
    ids, out = {}, []
    for line, events in lines:
        body = " ".join(
            f"events {{ metadata_id: {ids.setdefault(n, len(ids) + 1)} "
            f"offset_ps: {a * 1000} duration_ps: {(b - a) * 1000} }}"
            for n, a, b in events)
        out.append(f'lines {{ name: "{line}" {body} }}')
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for n, i in ids.items())
    return f'planes {{ name: "{name}" {" ".join(out)} {meta} }}'


def test_the_reader_leaves_a_prefills_calls_of_the_kernel_out(tmp_path):
    """A device plane as the profiler writes it: two whole decode
    executions in the slice, a prefill between them that calls the same
    kernel, a third decode that the slice cuts."""
    import jax
    text = _plane("/device:TPU:0", [
        ("XLA Modules", [("jit_llm_decode(1)", 100, 200),
                         ("jit_llm_prefill(2)", 250, 290),
                         ("jit_llm_decode(1)", 300, 400),
                         ("jit_llm_decode(1)", 950, 1100)]),
        ("XLA Ops", [("%moe_experts.1 = bf16[16,768] custom-call()", 110, 120),
                     ("%moe_experts.2 = bf16[16,2560] custom-call()", 130,
                      150),
                     ("%moe_experts.1 = bf16[128,768] custom-call()", 255,
                      285),
                     ("%kda_step.3 = f32[48,32,128,128] custom-call()", 310,
                      330),
                     ("%moe_experts.1 = bf16[16,768] custom-call()", 340,
                      360),
                     ("%moe_experts_gather.1 = fusion()", 160, 170),
                     ("%moe_experts.1 = bf16[16,768] custom-call()", 960,
                      980)])]) + " " + _plane(
        "/host:CPU", [("main", [("bench.trace_window", 50, 1000)])])
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    inside = named_op_roofline.ms_per_run
    assert inside(path, "moe_experts", "jit_llm_decode") == \
        pytest.approx(25e-6)                  # (10 + 20 + 20) ns / 2 runs
    assert inside(path, "kda_step", "jit_llm_decode") == pytest.approx(10e-6)
    # `named_op` sums the slice: the prefill's 30 ns and the cut run's 20
    assert named_op.ms_per_run(path, "moe_experts", "jit_llm_decode") == \
        pytest.approx(50e-6)
    assert inside(path, "power_retention_step", "jit_llm_decode") is None
    assert inside(path, "moe_experts", "jit_train_step") is None


def test_named_operations_count_inside_their_programs_executions_only():
    runs = [(100.0, 200.0), (300.0, 400.0)]
    events = [(110.0, 120.0), (190.0, 210.0), (250.0, 260.0), (300.0, 400.0),
              (50.0, 60.0)]
    assert named_op_roofline.seconds_inside(events, runs) == \
        pytest.approx(110e-9)
    assert named_op_roofline.seconds_inside(events, []) == 0.0


def test_ling_cost_from_the_configuration():
    assert ling_cost.layers_held(CFG) == [0, 6, 7, 8, 9, 10, 11]
    assert ling_cost.expert_layers(CFG) == 6
    assert ling_cost.kda_layers(CFG) == 6
    # 128 x (1 - (63/64)^36) = 55.4 of 128 held experts a layer
    assert ling_cost.experts_reached(CFG, 36) == pytest.approx(55.39, abs=.01)
    assert ling_cost.experts_reached(CFG, 48) == pytest.approx(67.89, abs=.01)
    assert ling_cost.experts_reached(CFG, 0) == 0.0
    assert ling_cost.experts_reached(CFG, 1e9) == pytest.approx(128.0)
    # x 3 x 2560 x 768 x 2 bytes x 6 expert layers = 3.92 GB
    assert ling_cost.expert_step_bytes(CFG, 36) == pytest.approx(
        ling_cost.experts_reached(CFG, 36) * 11_796_480 * 6)
    assert ling_cost.expert_step_bytes(CFG, 36) == pytest.approx(3.92e9,
                                                                 rel=5e-3)
    # 2 x 48 slots x 6 layers x 32 heads x 128 x 128 x 4 bytes = 1.21 GB
    assert ling_cost.kda_step_bytes(CFG, 48) == 2 * 48 * 6 * 2_097_152
    assert CFG["state"]["kda_state_bytes_per_layer_and_slot"] == 2_097_152
    assert ling_cost.roofline_share_pct(8.19e9, 0.02, 8.19e11) == \
        pytest.approx(50.0)


def test_the_new_metrics_name_their_kernels_and_costs():
    for name, op in (("expert_ffn", "moe_experts"), ("kda_update",
                                                     "kda_step")):
        ms = harness.load_json("layer_metrics", f"{name}_ms.serve.json")
        share = harness.load_json("layer_metrics",
                                  f"{name}_roofline_share.serve.json")
        assert ms["reader"] == share["reader"] == "named_op_roofline"
        assert ms["args"] == {"field": "ms_per_run", "op": op,
                              "per": "jit_llm_decode"}
        assert share["args"]["field"] == "roofline_pct"
        assert share["args"]["op"] == op
        assert share["args"]["workload"] == CELL
        module, fn = share["args"]["cost"].split(".")
        assert module == "ling_cost" and callable(getattr(ling_cost, fn))
    mine = named_op.matcher("moe_experts")
    assert mine("%moe_experts.5 = bf16[2432,768]{1,0} custom-call(...)")
    assert not mine("%moe_experts_gather.1 = fusion()")


def test_named_op_roofline_reads_nothing_without_a_trace():
    spec = harness.load_json("layer_metrics",
                             "expert_ffn_roofline_share.serve.json")
    assert named_op_roofline.read({"trace": None}, **spec["args"]) is None
    spec = harness.load_json("layer_metrics", "kda_update_ms.serve.json")
    assert named_op_roofline.read({"trace": None}, **spec["args"]) is None
    with pytest.raises(ValueError):
        named_op_roofline.read({"trace": None}, field="roofline_pct", op="x",
                               per="y", cost="ling_cost.kda_step_bytes",
                               workload=CELL)
    with pytest.raises(ValueError):
        named_op_roofline.read({"trace": None}, field="share", op="x",
                               per="y")


def test_the_cell_and_configuration_state_what_the_issue_gives():
    c = harness.load_cell(CELL)
    assert c["mix"]["prompt_tokens"] == {"median": 512, "sigma": 0.6,
                                         "min": 128, "max": 3072}
    assert c["mix"]["output_tokens"] == {"median": 768, "sigma": 0.5,
                                         "min": 256, "max": 1536}
    assert (c["mix"]["ramp_s"], c["mix"]["drain_s"],
            c["mix"]["schedule_seed"]) == (20.0, 25.0, 33)
    assert c["engine"] == {"num_slots": 48, "max_len": 5120,
                           "prefill_buckets": [256, 768, 1536, 3072],
                           "queue_depth": 256}
    assert c["check"]["decode_tokens"] == 16
    assert c["engine"]["num_slots"] % c["check"]["prompts"] == 0
    declared = {w["name"]: w for w in harness.load_benchmark()["workloads"]}
    assert declared[CELL]["why"] == c["why"] and declared[CELL]["chips"] == 1
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    differs = sorted(k for k, v in row["config"].items()
                     if CFG.get(k, "-") != v)
    assert differs == sorted(CFG["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert CFG["source"] == row["source_url"]
    assert (CFG["num_experts"], CFG["num_experts_published"]) == (128, 512)
    assert (CFG["vocab_size"], CFG["vocab_size_published"]) == (39296, 157184)
    assert set(CFG) >= {"assumed", "departures", "not_built", "deployment",
                        "memory_arithmetic"}
