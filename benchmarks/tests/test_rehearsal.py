"""Both runners end to end at tiny widths on the CPU, through the runner
functions (`run.py` itself has no way to pass without a TPU). Counts and
control flow only: no number from here is a speed."""
import subprocess
import sys

import pytest

from benchmarks import harness

BIG = 2 ** 31 + 17


def _run(cell_name, seconds, trace, tmp_path):
    cell = harness.load_cell(cell_name, base="tests")
    ctx = harness.Context("cpu", BIG, seconds, trace, harness.CompileWatch(),
                          harness.Tracer(str(tmp_path / "trace")),
                          say=lambda m: None)
    runner = harness.module("runners", cell["runner"])
    state = runner.build(cell, ctx)
    try:
        runner.warm(state)
        setup = ctx.watch.snapshot()
        result = runner.measure(state)
    finally:
        runner.close(state)
    return ctx, setup, result


def test_train_runner(tmp_path):
    ctx, _, res = _run("tiny_ernie.train", 1.5, False, tmp_path)
    assert all(res["checks"].values()), res["checks"]
    assert not any(ctx.compiled_in_window().values())
    assert res["attempted"] > 16 and res["failed"] == 0
    assert res["end_to_end"]["train_tokens_per_s"] > 0


@pytest.mark.parametrize("cell", ["tiny_gpt.decode", "tiny_gpt.prefill"])
def test_serve_runner_takes_a_new_cell_as_one_data_file(cell, tmp_path):
    ctx, _, res = _run(cell, 2.0, False, tmp_path)
    assert all(res["checks"].values()), res["checks"]
    assert not any(ctx.compiled_in_window().values())
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["end_to_end"]) == {"ttft_p50_ms", "itl_p50_ms",
                                      "itl_p99_ms"}


def test_traced_serve_run_feeds_every_host_side_reader(tmp_path):
    ctx, setup, res = _run("tiny_gpt.decode", 4.0, True, tmp_path)
    evidence = dict(res["evidence"], setup=setup, trace=None)
    got = harness.per_layer_metrics(harness.load_benchmark(),
                                    "gpt2_large.serve_decode", evidence)
    # no TPU plane in a CPU trace: the device_trace metrics are left out
    assert set(got) == {"gen_late_p95_ms.serve", "queue_wait_ms.serve",
                        "prefill_ms.serve", "server_itl_ms.serve",
                        "slots_per_step.serve", "compile_s.setup",
                        "cache_hits.setup"}
    assert got["slots_per_step.serve"]["value"] >= 1.0
    assert got["prefill_ms.serve"]["value"] > 0


def test_run_py_refuses_a_machine_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "ernie_base.train_b128_s128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, capture_output=True, text=True)
    assert p.returncode == 2 and "needs 1 TPU" in p.stderr
    assert not p.stdout.strip().startswith("{")
