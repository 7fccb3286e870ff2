"""`readers/program_trace` on hand-written event lists (as
`test_trace_reduce.py` does for the device plane): the overlap split, the
one-thread rule, `unattributed`, the add-up identity and the named-module
time; and on a CPU trace of `tiny_gpt.decode` that the reader finds the
program's `llm.*` spans."""
import pytest

from benchmarks import harness
from benchmarks import trace_reduce as tr
from benchmarks.readers import program_trace as pt

MS = 1e6     # ns


def _events():
    # window 0..100 ms, one device. Programs: decode 10..30 and 60..80, a
    # slot write 44..45, a prefill 40..42. Between-program gaps: 0..10,
    # 30..40, 42..44, 45..60, 80..100 (57 ms); inside the first decode a
    # hole 18..20 that is the device's own.
    ops = [("a", 10 * MS, 18 * MS), ("a", 20 * MS, 30 * MS),
           ("p", 40 * MS, 42 * MS), ("w", 44 * MS, 45 * MS),
           ("a", 60 * MS, 80 * MS)]
    modules = [("jit_llm_decode(123)", 10 * MS, 30 * MS),
               ("jit_llm_prefill(77)", 40 * MS, 42 * MS),
               ("jit_dynamic_update_slice(5)", 44 * MS, 45 * MS),
               ("jit_llm_decode(123)", 60 * MS, 80 * MS),
               ("jit_llm_decode(123)", 95 * MS, 120 * MS)]   # not whole
    host = [(tr.WINDOW_ANNOTATION, 0.0, 100 * MS)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def _scheduler():
    # one thread: step 4..36 (dispatch 5..9 with its to_static children,
    # read 9..32, emit 32..35), admit 36..52 (prefill 37..43, slot write
    # 43..50, emit 50..51), step 52..90 (dispatch 53..58, read 58..82,
    # emit 82..88), park 92..99
    return [("llm.step", 4 * MS, 36 * MS),
            ("llm.decode.dispatch", 5 * MS, 9 * MS),
            ("jit.to_static.prepare", 5 * MS, 7 * MS),
            ("jit.to_static.call", 7 * MS, 9 * MS),
            ("llm.decode.read", 9 * MS, 32 * MS),
            ("llm.emit", 32 * MS, 35 * MS),
            ("llm.admit", 36 * MS, 52 * MS),
            ("llm.prefill", 37 * MS, 43 * MS),
            ("jit.to_static.call", 38 * MS, 39 * MS),
            ("llm.slot_write", 43 * MS, 50 * MS),
            ("llm.emit", 50 * MS, 51 * MS),
            ("llm.step", 52 * MS, 90 * MS),
            ("llm.decode.dispatch", 53 * MS, 58 * MS),
            ("llm.decode.read", 58 * MS, 82 * MS),
            ("llm.emit", 82 * MS, 88 * MS),
            ("llm.park", 92 * MS, 99 * MS)]


def test_a_gap_is_split_by_overlap_not_labelled_at_its_middle():
    ev = _events()
    idle = pt.idle_split(ev, _scheduler(), 0.0, 100 * MS)
    # 0..10: 0..4 nothing, 4..5 step, 5..9 dispatch, 9..10 read.
    # 30..40: 30..32 read, 32..35 emit, 35..36 step, 36..40 admit.
    # 42..44 and 45..52 admit, 52..53 step, 53..58 dispatch, 58..60 read.
    # 80..100: 80..82 read, 82..88 emit, 88..90 step, 90..92 nothing,
    # 92..99 park, 99..100 nothing
    assert idle["dispatch"] == pytest.approx(9 * MS)
    assert idle["read"] == pytest.approx(7 * MS)
    assert idle["emit"] == pytest.approx((1 + 3 + 1 + 1 + 6 + 2) * MS)
    assert idle["admit"] == pytest.approx((4 + 2 + 7) * MS)
    assert idle["park"] == pytest.approx(7 * MS)
    assert idle[pt.UNATTRIBUTED] == pytest.approx((4 + 2 + 1) * MS)
    # trace_reduce gives the whole 45..60 gap to whatever is open at 52.5


def test_the_groups_add_up_to_trace_reduces_between_program_idle_time():
    ev = _events()
    idle = pt.idle_split(ev, _scheduler(), 0.0, 100 * MS)
    gaps = dict(tr.reduce(ev)["idle_gaps"])
    between = sum(v for k, v in gaps.items() if k != "within_program")
    assert gaps["within_program"] == pytest.approx(0.002)
    assert sum(idle.values()) / 1e9 == pytest.approx(between)
    assert between == pytest.approx(0.057)


def test_children_of_an_admission_stay_with_the_admission():
    assert pt.group_of(["llm.admit", "llm.prefill",
                        "jit.to_static.call"]) == "admit"
    assert pt.group_of(["llm.admit", "llm.emit"]) == "admit"
    assert pt.group_of(["llm.step", "llm.decode.dispatch",
                        "jit.to_static.prepare"]) == "dispatch"
    assert pt.group_of(["llm.step", "llm.emit"]) == "emit"
    assert pt.group_of(["llm.step"]) == "emit"
    assert pt.group_of(["jit.to_static.call"]) == "other"


def test_only_the_thread_that_holds_llm_step_labels_gaps():
    handler = [("jit.to_static.call", 0.0, 100 * MS),       # a client's own
               ("llm.emit", 0.0, 100 * MS)]
    other = [("PjitFunction(add)", 1 * MS, 2 * MS)]
    sched = _scheduler() + [("PjitFunction(add)", 6 * MS, 7 * MS)]
    spans = pt.scheduler_spans([handler, sched, other])
    assert sorted(spans) == sorted(_scheduler())
    assert pt.scheduler_spans([handler, other]) == []
    assert pt.scheduler_spans([]) == []


def test_nothing_covered_is_all_unattributed():
    ev = _events()
    idle = pt.idle_split(ev, [], 0.0, 100 * MS)
    assert idle == {pt.UNATTRIBUTED: pytest.approx(57 * MS)}


def test_named_module_time_counts_whole_executions_only():
    ev = _events()
    runs, ns = pt.module_runs(ev, "jit_llm_decode", 0.0, 100 * MS)
    assert (runs, ns) == (2, pytest.approx(40 * MS))
    assert pt.module_runs(ev, "jit_llm", 0.0, 100 * MS) == (0, 0.0)
    assert pt.module_runs(ev, "jit_llm_prefill", 0.0, 100 * MS)[0] == 1


def _read_with(monkeypatch, events, spans, field, **args):
    win = pt.window(events)
    idle = pt.idle_split(events, spans, *win) if spans and win else None
    monkeypatch.setattr(tr, "find_xplane", lambda _dir: "a.xplane.pb")
    monkeypatch.setattr(pt, "parse", lambda _path: {
        "events": events, "spans": spans, "window": win, "idle": idle})
    return pt.read({"trace": {"busy_s": 1.0}}, field, **args)


def test_read_gives_each_metric_and_the_four_idle_metrics_add(monkeypatch):
    ev, sp = _events(), _scheduler()
    per = {u: _read_with(monkeypatch, ev, sp, "idle_ms", under=u,
                         per="jit_llm_decode")
           for u in ("dispatch", "read", "emit", "admit", "park")}
    assert per["dispatch"] == pytest.approx(4.5)
    assert per["admit"] == pytest.approx(6.5)
    un = _read_with(monkeypatch, ev, sp, "unattributed_pct")
    assert un == pytest.approx(100 * 7 / 57)
    assert sum(per.values()) + un / 100 * 57 / 2 == pytest.approx(57 / 2)
    assert _read_with(monkeypatch, ev, sp, "module_ms",
                      module="jit_llm_decode") == pytest.approx(20.0)
    assert _read_with(monkeypatch, ev, sp, "span_ms",
                      span="llm.decode.dispatch") == pytest.approx(4.5)
    assert _read_with(monkeypatch, ev, sp, "span_ms",
                      span="llm.slot_write") == pytest.approx(7.0)


def test_read_is_none_without_a_trace_or_without_the_programs_spans(
        monkeypatch):
    assert pt.read({"trace": None}, "unattributed_pct") is None
    assert pt.read({}, "module_ms", module="jit_llm_decode") is None
    ev = _events()
    # a commit before the spans and the names: every program is `jit_pure`
    for dev in ev["devices"].values():
        dev["modules"] = [("jit_pure(1)", a, b) for _, a, b in dev["modules"]]
    for field, args in (("module_ms", {"module": "jit_llm_decode"}),
                        ("span_ms", {"span": "llm.slot_write"}),
                        ("idle_ms", {"under": "read",
                                     "per": "jit_llm_decode"}),
                        ("unattributed_pct", {})):
        assert _read_with(monkeypatch, ev, [], field, **args) is None
    with pytest.raises(ValueError):
        _read_with(monkeypatch, ev, _scheduler(), "no_such_field")


def test_cpu_trace_of_the_tiny_serve_cell_holds_the_programs_spans(tmp_path):
    from benchmarks.tests.test_rehearsal import _run
    _run("tiny_gpt.decode", 4.0, True, tmp_path)
    path = tr.find_xplane(str(tmp_path / "trace"))
    events, spans = pt.load(path)
    names = {n for n, _, _ in spans}
    assert {"llm.step", "llm.decode.dispatch", "llm.decode.read", "llm.emit",
            "llm.admit", "llm.prefill", "llm.slot_write",
            "jit.to_static.prepare", "jit.to_static.call"} <= names
    assert all(b > a for _, a, b in spans)
    segs = pt.segments(spans)
    assert all(a1 <= b1 <= a2 for (a1, b1, _), (a2, _, _)
               in zip(segs, segs[1:]))
    assert {"dispatch", "read", "emit", "admit"} <= {g for _, _, g in segs}
    # no device plane in a CPU trace: only the window annotation is there
    assert events["devices"] == {} and pt.window(events) is not None
    bench = harness.load_benchmark()
    new = [m["name"] for m in bench["per_layer"]
           if harness.load_json("layer_metrics", m["name"] + ".json")
           ["reader"] == "program_trace"]
    assert len(new) == 8
