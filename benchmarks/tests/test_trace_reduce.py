"""`trace_reduce.reduce` on a hand-written event list (no trace recorded on
the chip is under 1 MB): busy share, the top operation, one gap."""
import pytest

from benchmarks import trace_reduce as tr

MS = 1e6     # ns


def _events():
    # window 0..100 ms. Two programs: 10..40 and 60..90. Inside the first,
    # ops 10..20 (a), 20..35 (b), a 5 ms hole, then nothing; inside the
    # second one op 60..90 (b). Host: dispatch open over 40..60.
    ops = [("a", 10 * MS, 20 * MS), ("b", 20 * MS, 35 * MS),
           ("b", 60 * MS, 90 * MS)]
    modules = [("jit_step", 10 * MS, 40 * MS), ("jit_step", 60 * MS, 90 * MS)]
    host = [(tr.WINDOW_ANNOTATION, 0.0, 100 * MS),
            ("bench.train.dispatch", 38 * MS, 62 * MS),
            ("bench.train.next_batch", 1 * MS, 2 * MS)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_busy_idle_top_op_and_gaps():
    s = tr.reduce(_events())
    assert s["window_s"] == pytest.approx(0.100)
    assert s["busy_s"] == pytest.approx(0.055)
    assert s["idle_share_pct"] == pytest.approx(45.0)
    assert s["device_ops"][0] == ["b", pytest.approx(0.045)]
    assert s["modules"][0] == ["jit_step", 2, pytest.approx(0.060)]
    gaps = dict(s["idle_gaps"])
    # 35..60: its middle (47.5) is between programs, under the dispatch span
    assert gaps["bench.train.dispatch"] == pytest.approx(0.025)
    # 0..10 and 90..100: nothing of ours open on the host
    assert gaps["unattributed"] == pytest.approx(0.020)
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    assert s["longest_gap"] == ["bench.train.dispatch", pytest.approx(0.025)]


def test_gap_inside_a_program_is_the_devices_own():
    ev = _events()
    ev["devices"]["/device:TPU:0"]["ops"].append(("c", 37 * MS, 40 * MS))
    gaps = dict(tr.reduce(ev)["idle_gaps"])
    assert gaps["within_program"] == pytest.approx(0.002)      # 35..37


def test_window_clips_and_two_devices_average():
    ev = _events()
    ev["host"][0] = (tr.WINDOW_ANNOTATION, 15 * MS, 65 * MS)
    ev["devices"]["/device:TPU:1"] = {"ops": [], "modules": []}
    s = tr.reduce(ev)
    assert s["window_s"] == pytest.approx(0.050)
    assert s["busy_s"] == pytest.approx((20 + 5) / 2 / 1000)   # 15..35, 60..65
    assert s["devices"] == 2


def test_no_device_operation_is_no_summary():
    assert tr.reduce({"devices": {}, "host": []}) is None
