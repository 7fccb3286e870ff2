"""BENCHMARK.json against the contract's limits and against the files the
harness finds by name."""
import json
import os
import re

from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
B = harness.load_benchmark()


def test_keys_names_units():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in B[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert len(json.dumps(B)) < 64 * 1024


def test_cells_and_configs_have_their_files():
    configs = {c["name"]: c for c in B["configs"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            sizes = json.load(f)
        assert sizes["reduced"] == c["reduced"]
        assert sizes["source"] == c["source"]
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) \
            == (w["config"], w["traffic"], w["chips"], w["why"])
        assert w["config"] in configs and len(w["why"]) <= 200
        harness.module("runners", cell["runner"])
        harness.module("traffic", cell["generator"])


def test_every_metric_is_reported_where_it_says():
    cells = [w["name"] for w in B["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    for cell in cells:
        assert sum(cell in ws for ws in e2e.values()) >= 2
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        for k in ("unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert hasattr(harness.module("readers", spec["reader"]), "read")
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in B["per_layer"])


def test_peaks_and_flops():
    import pytest

    from benchmarks import flops
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 1.97e14
    with pytest.raises(KeyError):
        flops.peak("TPU v99")             # never a default
    cfg = harness.load_json("configs", "ernie_base.json")
    # 6 x (12 blocks + transform + tied head + pooler/NSP share) + attention
    assert flops.train_flops_per_token(cfg, 128) == pytest.approx(
        667_975_752.0)
