"""tpu-lint static-analysis plane: source rules (one positive + one clean
fixture per rule), suppressions, graph rules (dead ops, unused inputs, f64
widening, host callbacks), collective-ordering verification between
deliberately-skewed pipeline-stage programs, the dead_op_elim/lint passes,
the CLI (exit codes + JSON), FLAGS_lint trace-time wiring with its
disabled-path overhead guard, and the repo self-lint gate (shipped models/
nn/ops must stay trace-clean).

Reference roles: the analysis half of `paddle/fluid/framework/ir/` (pass
framework graph walks) + compile-time precondition checks.
"""
import json
import os
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import analysis, monitor
from paddle_tpu.analysis import cli as lint_cli
from paddle_tpu.analysis import graph as agraph
from paddle_tpu.analysis.lint import lint_source

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "paddle_tpu")


def rules_of(findings):
    return [f.rule for f in findings]


@pytest.fixture()
def linted():
    """Enable FLAGS_lint on a clean registry/cache; always restore."""
    monitor.reset()
    analysis._reset_trace_cache()
    paddle.set_flags({"FLAGS_lint": True})
    try:
        yield
    finally:
        paddle.set_flags({"FLAGS_lint": False})
        analysis._reset_trace_cache()
        monitor.reset()


# ---------------------------------------------------------------------------
# level 1: source lint
# ---------------------------------------------------------------------------

class TestSourceLint:
    def test_host_sync_positive(self):
        src = """
def forward(self, x):
    y = x.numpy()
    z = float(x)
    w = x.item()
    return y, z, w
"""
        rules = rules_of(lint_source(src, "f.py"))
        assert rules.count("host-sync") == 3

    def test_host_sync_clean(self):
        src = """
def forward(self, x):
    return (x * 2 + 1).reshape([-1])
"""
        assert lint_source(src, "f.py") == []

    def test_tensor_branch_positive(self):
        src = """
def forward(self, x):
    if x > 0:
        x = x * 2
    while x.sum() < 10:
        x = x + 1
    assert x.mean() > 0
    return x
"""
        assert rules_of(lint_source(src, "f.py")) == [
            "tensor-branch", "tensor-branch", "tensor-branch"]

    def test_tensor_branch_clean_static_predicates(self):
        # identity tests, self attrs, scalar-default kwargs, isinstance —
        # all host-static predicates that must NOT flag
        src = """
def forward(self, x, mask=None, use_cache=False):
    if mask is not None:
        x = x + mask
    if use_cache:
        x = x * 1
    if self.training:
        x = x * 2
    if isinstance(x, tuple):
        x = x[0]
    return x
"""
        assert lint_source(src, "f.py") == []

    def test_taint_propagates_through_assignment(self):
        src = """
def forward(self, x):
    y = x * 2
    z = y + 1
    if z > 0:
        z = z - 1
    return z
"""
        assert rules_of(lint_source(src, "f.py")) == ["tensor-branch"]

    def test_traced_print(self):
        src = """
def forward(self, x):
    print(x)
    return x
"""
        assert rules_of(lint_source(src, "f.py")) == ["traced-print"]

    def test_stdlib_random_positive(self):
        src = """
def forward(self, x):
    import random
    a = random.random()
    b = np.random.rand(3)
    c = numpy.random.randint(0, 2)
    return x + a + b + c
"""
        assert rules_of(lint_source(src, "f.py")) == ["stdlib-random"] * 3

    def test_stdlib_random_clean_framework_rng(self):
        src = """
def forward(self, x):
    noise = paddle.rand([4])      # rides the trace key: fine
    return x + noise
"""
        assert lint_source(src, "f.py") == []

    def test_shape_capture_positive(self):
        src = """
def forward(self, x):
    if x.shape[0] > 8:
        x = x * 2
    while len(x) > 4:
        x = x[:-1]
    return x
"""
        assert rules_of(lint_source(src, "f.py")) == [
            "shape-capture", "shape-capture"]

    def test_shape_capture_clean_static_uses(self):
        src = """
def forward(self, x):
    b = x.shape[0]
    for i in range(x.shape[1]):
        x = x + i
    return x.reshape([b, -1])
"""
        assert lint_source(src, "f.py") == []

    def test_lazy_sync_advisory_in_loop(self):
        """lazy-sync (ISSUE 9): a host sync inside a loop body gets the
        extra INFO advisory — each iteration would flush the lazy segment."""
        src = """
def forward(self, x):
    total = 0.0
    for i in range(10):
        total += x.item()
    return total
"""
        assert rules_of(lint_source(src, "f.py")) == ["host-sync", "lazy-sync"]

    def test_lazy_sync_not_fired_outside_loop(self):
        src = """
def forward(self, x):
    return x.numpy()
"""
        assert rules_of(lint_source(src, "f.py")) == ["host-sync"]

    def test_lazy_sync_loop_header_exempt_while_test_counted(self):
        """The For iterable is evaluated once (no advisory); a While test
        re-runs every iteration (advisory)."""
        src = """
def forward(self, x):
    for i in range(int(x.item())):
        pass
    while x.item() > 0:
        x = x - 1
    return x
"""
        fs = lint_source(src, "f.py")
        by_rule = {}
        for f in fs:
            by_rule.setdefault(f.rule, []).append(f.line)
        assert by_rule["lazy-sync"] == [5]

    def test_default_mode_scans_only_trace_destined(self):
        src = """
def helper(x):
    return x.numpy()

def forward(self, x):
    return x + 1
"""
        assert lint_source(src, "f.py") == []
        rules = rules_of(lint_source(src, "f.py", all_functions=True))
        assert rules == ["host-sync"]

    def test_decorated_function_is_trace_destined(self):
        src = """
@paddle.jit.to_static
def step(x):
    print(x)
    return x
"""
        assert rules_of(lint_source(src, "f.py")) == ["traced-print"]

    def test_nested_functions_are_in_region(self):
        src = """
def forward(self, x):
    def inner(v):
        return v.numpy()
    return inner(x)
"""
        assert rules_of(lint_source(src, "f.py")) == ["host-sync"]

    def test_suppression_same_line(self):
        src = """
def forward(self, x):
    y = x.numpy()  # tpu-lint: disable=host-sync
    z = x.numpy()
    return y, z
"""
        fs = lint_source(src, "f.py")
        assert rules_of(fs) == ["host-sync"] and fs[0].line == 4

    def test_suppression_file_wide_and_all(self):
        src = """
# tpu-lint: disable=host-sync
def forward(self, x):
    print(x)
    return x.numpy()
"""
        assert rules_of(lint_source(src, "f.py")) == ["traced-print"]
        src_all = src.replace("disable=host-sync", "disable=all")
        assert lint_source(src_all, "f.py") == []

    # -- buffer-retain advisory (ISSUE 10: HBM memory attribution) --

    def test_buffer_retain_eager_loop(self):
        """`self.last_loss = loss` in an --all-mode epoch loop pins the
        step's device buffer across iterations (defeats donation) —
        including through a plain-name rebind."""
        src = """
def run_epoch(self, loader):
    for batch in loader:
        loss = self.step(batch)
        self.last_loss = loss
"""
        assert rules_of(lint_source(src, "f.py",
                                    all_functions=True)) == ["buffer-retain"]

    def test_buffer_retain_traced_forward(self):
        src = """
def forward(self, x):
    for blk in range(3):
        x = x * 2
        self.h = x
    return x
"""
        assert rules_of(lint_source(src, "f.py")) == ["buffer-retain"]

    def test_buffer_retain_host_copies_exempt(self):
        """float(...)/np.asarray(...) copies are the recommended FIX —
        they hold host values, not device buffers."""
        src = """
def run_epoch(self, loader):
    for batch in loader:
        loss = self.step(batch)
        self.last = float(loss)
        self.curve = np.asarray(loss)
"""
        assert lint_source(src, "f.py", all_functions=True) == []

    def test_buffer_retain_outside_loop_exempt(self):
        src = """
def setup(self, x):
    self.template = paddle.zeros([4, 4])
"""
        assert lint_source(src, "f.py", all_functions=True) == []

    def test_buffer_retain_suppression(self):
        src = """
def run_epoch(self, loader):
    for batch in loader:
        loss = self.step(batch)
        self.last_loss = loss  # tpu-lint: disable=buffer-retain
"""
        assert lint_source(src, "f.py", all_functions=True) == []


# ---------------------------------------------------------------------------
# level 2: graph analysis
# ---------------------------------------------------------------------------

class TestGraphAnalysis:
    def test_dead_op_and_unused_var(self):
        import jax
        import jax.numpy as jnp

        def f(x, y):
            dead = jnp.sin(x) * 3.0   # noqa: F841 — the fixture hazard
            return x + 1.0

        j = jax.make_jaxpr(f)(jnp.ones(3), jnp.ones(3))
        fs = agraph.analyze_jaxpr(j, "f")
        assert "dead-op" in rules_of(fs)
        assert any(f.rule == "unused-var" and "#1" in f.message for f in fs)
        assert any("sin" in f.message for f in fs)

    def test_clean_program_has_no_findings(self):
        import jax
        import jax.numpy as jnp

        def f(x, y):
            return (x * y).sum()

        assert agraph.analyze_jaxpr(jax.make_jaxpr(f)(
            jnp.ones(3), jnp.ones(3)), "f") == []

    def test_dtype_widen(self):
        import jax
        import jax.numpy as jnp

        with jax.enable_x64():
            def f(x):
                return x.astype(jnp.float64) * 2.0

            j = jax.make_jaxpr(f)(jnp.ones(3, jnp.float32))

            def g(x):
                return x * 2.0

            j_clean = jax.make_jaxpr(g)(jnp.ones(3, jnp.float32))
        fs = agraph.analyze_jaxpr(j, "f")
        assert rules_of(fs) == ["dtype-widen"]
        assert "float64" in fs[0].message
        assert agraph.analyze_jaxpr(j_clean, "g") == []

    def test_host_callback(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            jax.debug.print("x={}", x)
            return x * 2

        fs = agraph.analyze_jaxpr(jax.make_jaxpr(f)(jnp.ones(3)), "f")
        assert "host-callback" in rules_of(fs)

    def test_analyze_program(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.static.program import Program

        def f(x):
            dead = jnp.cos(x)         # noqa: F841
            return x + 1

        prog = Program.from_callable(
            f, [jax.ShapeDtypeStruct((4,), jnp.float32)])
        assert "dead-op" in rules_of(agraph.analyze_program(prog))


# ---------------------------------------------------------------------------
# collective-ordering verification
# ---------------------------------------------------------------------------

def _mesh(axis="pp"):
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:8]).reshape(8), (axis,))


def _shmap(fn, mesh, **kw):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    return shard_map(fn, mesh=mesh, in_specs=P("pp"), out_specs=P("pp"),
                     **kw)


_PERM = [(i, (i + 1) % 8) for i in range(8)]


class TestCollectiveOrder:
    def test_sequence_extraction(self):
        import jax
        import jax.numpy as jnp

        def stage(x):
            x = jax.lax.psum(x, "pp")
            return jax.lax.ppermute(x, "pp", _PERM)

        seq = agraph.collective_sequence(_shmap(stage, _mesh()),
                                         jnp.ones((8, 4)))
        assert [c.op for c in seq] == ["psum", "ppermute"]
        assert seq[0].axis == "pp" and seq[0].dtype == "float32"

    def test_check_rep_does_not_change_signature(self):
        # psum is rewritten to psum2+pbroadcast under check_vma=True; the
        # signature must be invariant to that bookkeeping
        import jax
        import jax.numpy as jnp

        def stage(x):
            x = jax.lax.psum(x, "pp")
            return jax.lax.ppermute(x, "pp", _PERM)

        m = _mesh()
        x = jnp.ones((8, 4))
        a = agraph.collective_sequence(_shmap(stage, m), x)
        b = agraph.collective_sequence(_shmap(stage, m, check_vma=False), x)
        assert a == b

    def test_mismatch_names_first_divergence(self):
        # two 2-stage pipeline programs, deliberately skewed: rank1 swaps
        # the order of its first stage's collectives
        import jax
        import jax.numpy as jnp

        def r0_s0(x):
            x = jax.lax.psum(x, "pp")
            return jax.lax.ppermute(x, "pp", _PERM)

        def r1_s0(x):
            x = jax.lax.ppermute(x, "pp", _PERM)
            return jax.lax.psum(x, "pp")

        m = _mesh()
        x = jnp.ones((8, 4))
        fs = agraph.verify_collective_order(
            {"rank0": _shmap(r0_s0, m), "rank1": _shmap(r1_s0, m)},
            specs={"rank0": [x], "rank1": [x]})
        assert rules_of(fs) == ["collective-order"]
        msg = fs[0].message
        assert "#0" in msg and "psum" in msg and "ppermute" in msg
        assert "rank1" in msg

    def test_length_mismatch_detected(self):
        import jax
        import jax.numpy as jnp

        def long_stage(x):
            x = jax.lax.psum(x, "pp")
            return jax.lax.ppermute(x, "pp", _PERM)

        def short_stage(x):
            return jax.lax.psum(x, "pp")

        m = _mesh()
        x = jnp.ones((8, 4))
        fs = agraph.verify_collective_order(
            {"rank0": _shmap(long_stage, m), "rank1": _shmap(short_stage, m)},
            specs={"rank0": [x], "rank1": [x]})
        assert rules_of(fs) == ["collective-order"]
        assert "never reaches" in fs[0].message

    def test_matching_programs_clean(self):
        import jax
        import jax.numpy as jnp

        def stage(x):
            return jax.lax.psum(x, "pp")

        m = _mesh()
        x = jnp.ones((8, 4))
        assert agraph.verify_collective_order(
            {"rank0": _shmap(stage, m), "rank1": _shmap(stage, m)},
            specs={"rank0": [x], "rank1": [x]}) == []

    def test_precomputed_sequences_accepted(self):
        a = [agraph.CollectiveDesc("psum", "dp", (4,), "float32")]
        b = [agraph.CollectiveDesc("all_gather", "dp", (4,), "float32")]
        fs = agraph.verify_collective_order({"r0": a, "r1": b})
        assert rules_of(fs) == ["collective-order"]

    def test_spmd_train_step_signature(self):
        from paddle_tpu.parallel import (HybridCommunicateGroup,
                                         SPMDTrainStep)
        paddle.seed(0)
        hcg = HybridCommunicateGroup(hybrid_configs={"dp_degree": 8})
        model = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
        opt = paddle.optimizer.SGD(parameters=model.parameters(),
                                   learning_rate=0.1)
        step = SPMDTrainStep(model, nn.CrossEntropyLoss(), opt,
                             mesh=hcg.get_mesh(), donate=False)
        x = paddle.to_tensor(np.random.rand(16, 8).astype("float32"))
        y = paddle.to_tensor(np.random.randint(0, 4, (16,)))
        sig = step.collective_signature(x, y)
        assert isinstance(sig, list)
        # same-program signatures must verify clean rank-to-rank
        assert agraph.verify_collective_order({"r0": sig, "r1": sig}) == []


# ---------------------------------------------------------------------------
# pipeline/task-graph verification
# ---------------------------------------------------------------------------

class TestStageGraph:
    def test_chain_clean(self):
        import jax.numpy as jnp
        stages = [lambda x: x.reshape(4, 8),
                  lambda x: x @ jnp.ones((8, 2))]
        assert agraph.verify_stage_chain(stages, jnp.ones(32)) == []

    def test_chain_broken_edge_named(self):
        import jax.numpy as jnp
        stages = [lambda x: x.reshape(4, 8),
                  lambda x: x @ jnp.ones((5, 2))]
        fs = agraph.verify_stage_chain(stages, jnp.ones(32))
        assert rules_of(fs) == ["stage-graph"]
        assert "stage 1" in fs[0].message and "stage 0" in fs[0].message

    def test_fleet_executor_verify(self):
        import jax.numpy as jnp
        from paddle_tpu.distributed.fleet_executor import FleetExecutor
        good = FleetExecutor([lambda x: x * 2, lambda x: x.sum()])
        assert good.verify(jnp.ones(4)) == []
        bad = FleetExecutor([lambda x: x.reshape(2, 2),
                             lambda x: x @ jnp.ones((3, 3))])
        assert rules_of(bad.verify(jnp.ones(4))) == ["stage-graph"]

    def test_stage_assignment(self):
        fs = agraph.verify_stage_assignment({0: 0, 2: 1}, 3)
        assert rules_of(fs) == ["stage-graph"]
        assert "stage 1" in fs[0].message
        fs = agraph.verify_stage_assignment({0: 0, 1: 1}, 2, my_rank=0,
                                            my_stages=[0, 1])
        assert rules_of(fs) == ["stage-graph"]      # rank 0 hosting stage 1
        assert agraph.verify_stage_assignment(
            {0: 0, 1: 1}, 2, my_rank=1, my_stages=[1]) == []


# ---------------------------------------------------------------------------
# passes: dead_op_elim + lint
# ---------------------------------------------------------------------------

class TestPasses:
    def _prog(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.static.program import Program

        def f(x):
            dead = jnp.sin(x) * 2.0   # noqa: F841
            return (x + 1.0).sum()

        return Program.from_callable(
            f, [jax.ShapeDtypeStruct((4,), jnp.float32)])

    def test_dead_op_elim_removes_dead_eqns(self):
        import jax
        prog = self._prog()
        opt = prog.apply_pass("dead_op_elim")
        orig = [e.primitive.name
                for e in jax.make_jaxpr(prog._fn)(*prog._arg_specs).eqns]
        after = [e.primitive.name
                 for e in jax.make_jaxpr(opt._fn)(*opt._arg_specs).eqns]
        assert "sin" in orig and "sin" not in after
        assert len(after) < len(orig)

    def test_dead_op_elim_preserves_results(self):
        import jax.numpy as jnp
        prog = self._prog()
        opt = prog.apply_pass("dead_op_elim")
        x = jnp.arange(4.0)
        np.testing.assert_allclose(np.asarray(opt.run(x)),
                                   np.asarray(prog.run(x)), rtol=1e-6)

    def test_lint_pass_warns_and_attaches_findings(self):
        prog = self._prog()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            out = prog.apply_pass("lint")
        assert any("tpu-lint" in str(x.message) for x in w)
        assert "dead-op" in rules_of(out.lint_findings)

    def test_lint_pass_gate_raises(self):
        prog = self._prog()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with pytest.raises(ValueError, match="dead-op"):
                prog.apply_pass("lint", fail_on="warning")

    def test_passes_registered(self):
        from paddle_tpu.static.passes import list_passes
        assert {"lint", "dead_op_elim"} <= set(list_passes())


# ---------------------------------------------------------------------------
# CLI driver
# ---------------------------------------------------------------------------

HAZARD_SRC = """
def forward(self, x):
    print(x)
    return x.numpy()
"""

CLEAN_SRC = """
def forward(self, x):
    return x + 1
"""


class TestCLI:
    def test_exit_1_on_errors(self, tmp_path, capsys):
        p = tmp_path / "bad.py"
        p.write_text(HAZARD_SRC)
        assert lint_cli.main([str(p)]) == 1
        out = capsys.readouterr().out
        assert "host-sync" in out and "bad.py" in out

    def test_exit_0_on_clean(self, tmp_path):
        p = tmp_path / "ok.py"
        p.write_text(CLEAN_SRC)
        assert lint_cli.main([str(p)]) == 0

    def test_exit_2_on_missing_path(self, tmp_path):
        assert lint_cli.main([str(tmp_path / "nope.py")]) == 2

    def test_fail_on_never_and_warning(self, tmp_path):
        p = tmp_path / "warn.py"
        p.write_text("def forward(self, x):\n    print(x)\n    return x\n")
        assert lint_cli.main([str(p)]) == 0            # warning < error
        assert lint_cli.main([str(p), "--fail-on", "warning"]) == 1
        bad = tmp_path / "bad.py"
        bad.write_text(HAZARD_SRC)
        assert lint_cli.main([str(bad), "--fail-on", "never"]) == 0

    def test_json_output(self, tmp_path, capsys):
        p = tmp_path / "bad.py"
        p.write_text(HAZARD_SRC)
        rc = lint_cli.main([str(p), "--json", "--fail-on", "never"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0 and doc["version"] == 1 and doc["files"] == 1
        assert doc["counts"]["error"] == 1
        rules = {f["rule"] for f in doc["findings"]}
        assert rules == {"host-sync", "traced-print"}
        assert all({"path", "line", "severity", "message"} <=
                   set(f) for f in doc["findings"])

    def test_rules_filter(self, tmp_path, capsys):
        p = tmp_path / "bad.py"
        p.write_text(HAZARD_SRC)
        lint_cli.main([str(p), "--rules", "traced-print", "--json",
                       "--fail-on", "never"])
        doc = json.loads(capsys.readouterr().out)
        assert {f["rule"] for f in doc["findings"]} == {"traced-print"}
        lint_cli.main([str(p), "--disable", "host-sync", "--json",
                       "--fail-on", "never"])
        doc = json.loads(capsys.readouterr().out)
        assert {f["rule"] for f in doc["findings"]} == {"traced-print"}

    def test_directory_recursion_and_suppression(self, tmp_path, capsys):
        sub = tmp_path / "pkg"
        sub.mkdir()
        (sub / "a.py").write_text(
            "def forward(self, x):\n"
            "    return x.numpy()  # tpu-lint: disable=host-sync\n")
        (sub / "b.py").write_text(CLEAN_SRC)
        assert lint_cli.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "2 file(s)" in out

    def test_list_rules(self, capsys):
        assert lint_cli.main(["--list-rules", "x"]) == 0
        out = capsys.readouterr().out
        for rule in ("host-sync", "collective-order", "dead-op"):
            assert rule in out


# ---------------------------------------------------------------------------
# FLAGS_lint trace-time wiring + overhead guard
# ---------------------------------------------------------------------------

HAZARD_MODULE = """
import paddle_tpu as paddle
import paddle_tpu.nn as nn

@paddle.jit.to_static
def noisy(x):
    print("traced")
    return x * 2

class NoisyNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 2)

    def forward(self, x):
        print("step")
        return self.fc(x)
"""


def _load_module(tmp_path, name="lint_fixture"):
    import importlib.util
    p = tmp_path / f"{name}.py"
    p.write_text(HAZARD_MODULE)
    spec = importlib.util.spec_from_file_location(name, p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestTraceTimeLint:
    def test_to_static_warns_once_and_counts(self, tmp_path, linted):
        mod = _load_module(tmp_path)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            mod.noisy(paddle.ones([3]))
            mod.noisy(paddle.ones([5]))    # novel sig: no duplicate lint
        msgs = [str(x.message) for x in w if "tpu-lint" in str(x.message)]
        assert len(msgs) == 1 and "traced-print" in msgs[0]
        snap = monitor.snapshot()["counters"]
        assert snap.get("lint.findings") == 1
        assert snap.get("lint.files") == 1

    def test_train_step_lints_forward(self, tmp_path, linted):
        mod = _load_module(tmp_path, "lint_fixture_ts")
        model = mod.NoisyNet()
        opt = paddle.optimizer.SGD(parameters=model.parameters(),
                                   learning_rate=0.1)
        step = paddle.jit.TrainStep(
            model, lambda out, y: ((out - y) ** 2).mean(), opt)
        x = paddle.ones([2, 4])
        y = paddle.zeros([2, 2])
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            step(x, y)
        msgs = [str(m.message) for m in w if "tpu-lint" in str(m.message)]
        assert any("traced-print" in m for m in msgs)
        assert monitor.snapshot()["counters"].get("lint.findings", 0) >= 1

    def test_disabled_no_lint_no_counters(self, tmp_path):
        monitor.reset()
        analysis._reset_trace_cache()
        assert analysis._ENABLED is False
        mod = _load_module(tmp_path, "lint_fixture_off")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            mod.noisy(paddle.ones([3]))
        assert not [m for m in w if "tpu-lint" in str(m.message)]
        snap = monitor.snapshot()["counters"]
        assert "lint.findings" not in snap and "lint.files" not in snap

    def test_disabled_gate_is_one_attribute_check(self):
        assert analysis._ENABLED is False

        def gated():
            if analysis._ENABLED:
                analysis.lint_traced(gated)

        def baseline():
            pass

        n = 20000
        gated(), baseline()                 # warm
        t0 = time.perf_counter()
        for _ in range(n):
            gated()
        t_gate = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n):
            baseline()
        t_base = time.perf_counter() - t0
        # generous: anything near this bound means the disabled path grew
        # a lookup/allocation (same guard style as faults/monitor)
        assert t_gate < t_base + 0.05


# ---------------------------------------------------------------------------
# repo self-lint: shipped code must stay trace-clean (tier-1 CI gate)
# ---------------------------------------------------------------------------

class TestSelfLint:
    def test_shipped_packages_are_lint_clean(self):
        """A future PR introducing a trace hazard into shipped models/nn/
        ops fails here — run the FULL rule set (--all) like the CI recipe
        in README; intentional host syncs carry explicit suppressions."""
        findings, n_files = analysis.lint_paths(
            [os.path.join(PKG, "models"), os.path.join(PKG, "nn"),
             os.path.join(PKG, "ops"),
             # hot-path overlap plane (ISSUE 7): the prefetch feeder and
             # the bucketed reducer ride the same gate
             os.path.join(PKG, "io", "prefetch.py"),
             os.path.join(PKG, "parallel", "reducer.py"),
             # memory attribution plane (ISSUE 10): census seams must not
             # themselves retain per-step buffers or sync in hot loops
             os.path.join(PKG, "serving", "engine.py"),
             os.path.join(PKG, "guard", "supervisor.py"),
             os.path.join(PKG, "device", "__init__.py"),
             # executable substrate + persistent compile cache (ISSUE
             # 11): every dispatch regime rides these on the hot path
             os.path.join(PKG, "core", "executable.py"),
             os.path.join(PKG, "core", "compile_cache.py"),
             # request tracing + SLO plane (ISSUE 12): every request
             # crosses these — span bookkeeping must stay sync-free
             os.path.join(PKG, "obs", "trace.py"),
             os.path.join(PKG, "obs", "slo.py"),
             # fleet serving tier (ISSUE 13): every routed request
             # crosses the dispatch/scoring path
             os.path.join(PKG, "serving", "fleet.py"),
             # continuous-batching LLM plane (ISSUE 14): the decode loop
             # dispatches every step — no host syncs beyond the tokens
             os.path.join(PKG, "serving", "llm.py"),
             # PS durability + HA plane (ISSUE 15): every sequenced push
             # crosses the WAL commit path; the replication tail runs
             # beside training
             os.path.join(PKG, "distributed", "ps", "wal.py"),
             os.path.join(PKG, "distributed", "ps", "ha.py"),
             # fleet telemetry plane (ISSUE 16): the exporter's event()
             # rides the serving hot path; pushes run on their own thread
             os.path.join(PKG, "obs", "telemetry.py"),
             # elastic autoscaler (ISSUE 17): the sense→decide→act tick
             # runs beside serving every interval — it must stay
             # device-sync-free or the decision loop taxes the p99
             os.path.join(PKG, "serving", "autoscaler.py"),
             # online-learning plane (ISSUE 19): the delta tail runs
             # beside serving and every CTR lookup crosses the table
             os.path.join(PKG, "distributed", "ps", "delta.py"),
             os.path.join(PKG, "serving", "online.py")],
            all_functions=True)
        assert n_files > 25
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_shipped_model_programs_are_graph_clean(self):
        """Dead ops / f64 widenings in a shipped model's traced program
        (both modes — the BN running-stat fix keeps train mode clean)."""
        import jax
        from paddle_tpu.jit.functional import functional_call, split_state
        from paddle_tpu.models.lenet import LeNet

        for train in (False, True):
            model = LeNet()
            model.train() if train else model.eval()
            trainable, frozen = split_state(model)
            pn, bn = list(trainable), list(frozen)

            def pure(params, buffers, inputs):
                return functional_call(model, pn, params, bn, buffers,
                                       *inputs)

            j = jax.make_jaxpr(pure)(
                [trainable[n]._value for n in pn],
                [frozen[n]._value for n in bn],
                [paddle.rand([2, 1, 28, 28])._value])
            fs = [f for f in agraph.analyze_jaxpr(j, "lenet")
                  if f.rule != "unused-var"]
            assert fs == [], "\n".join(f.format() for f in fs)


# ---------------------------------------------------------------------------
# level 4: concurrency analysis (lock graph, blocking, thread registry)
# ---------------------------------------------------------------------------

INVERTED_SRC = """
import threading

class Pool:
    def __init__(self):
        self.a_lock = threading.Lock()
        self.b_lock = threading.Lock()

    def one(self):
        with self.a_lock:
            with self.b_lock:
                pass

    def two(self):
        with self.b_lock:
            with self.a_lock:
                pass
"""

CONSISTENT_SRC = """
import threading

class Pool:
    def __init__(self):
        self.a_lock = threading.Lock()
        self.b_lock = threading.Lock()

    def one(self):
        with self.a_lock:
            with self.b_lock:
                pass

    def two(self):
        with self.a_lock:
            with self.b_lock:
                pass
"""

BLOCKING_SRC = """
import threading
import time

_LOCK = threading.Lock()

def tick(q, sock, t):
    with _LOCK:
        time.sleep(0.2)
        q.get()
        sock.recv(1024)
        t.join()
"""

THREAD_SRC = """
import threading

def spawn():
    return threading.Thread(target=print, daemon=True)
"""


class TestConcurrencyLint:
    def _run(self, src):
        from paddle_tpu.analysis.concurrency import analyze_source
        return analyze_source(src, "fix.py")

    def test_lock_order_positive_names_both_sites(self):
        fs = self._run(INVERTED_SRC)
        assert rules_of(fs) == ["lock-order"]
        f = fs[0]
        # the finding sits at one inverting site and its message cites
        # the OTHER established site with file:line
        assert {"Pool.one", "Pool.two"} == {f.func} | {
            m.split(")")[0] for m in f.message.split("(in ")[1:]}
        assert "fix.py:" in f.message
        assert "Pool.a_lock" in f.message and "Pool.b_lock" in f.message
        assert "deadlock" in f.message

    def test_lock_order_clean_on_consistent_order(self):
        assert self._run(CONSISTENT_SRC) == []

    def test_lock_order_suppressed_at_either_site(self):
        src = INVERTED_SRC.replace(
            "        with self.a_lock:\n                pass",
            "        with self.a_lock:  # tpu-lint: disable=lock-order\n"
            "                pass")
        assert "disable=lock-order" in src
        assert self._run(src) == []

    def test_blocking_under_lock_positive(self):
        fs = self._run(BLOCKING_SRC)
        assert rules_of(fs) == ["blocking-under-lock"] * 4
        reasons = " | ".join(f.message for f in fs)
        assert "time.sleep(0.2)" in reasons
        assert "queue .get() with no timeout" in reasons
        assert "socket .recv()" in reasons
        assert ".join() with no timeout" in reasons
        assert all("'_LOCK'" in f.message for f in fs)

    def test_blocking_clean_when_bounded_or_outside(self):
        src = """
import threading
import time

_LOCK = threading.Lock()

def tick(q, t, counters):
    with _LOCK:
        time.sleep(0.001)          # under threshold
        q.get(timeout=1.0)         # bounded
        t.join(timeout=5.0)        # bounded
        counters.get()             # not queue-shaped: a dict/Counter get
    q.get()                        # blocking, but no lock held
"""
        assert self._run(src) == []

    def test_rpc_retry_under_lock(self):
        src = """
import threading

_LOCK = threading.Lock()

def push(chan):
    with _LOCK:
        return chan.call_with_retry(b"PUSH", b"")
"""
        fs = self._run(src)
        assert rules_of(fs) == ["blocking-under-lock"]
        assert "call_with_retry" in fs[0].message

    def test_unregistered_thread_positive_and_registered_clean(self):
        fs = self._run(THREAD_SRC)
        assert rules_of(fs) == ["unregistered-thread"]
        assert "syncwatch.Thread" in fs[0].message
        clean = THREAD_SRC.replace("threading.Thread",
                                   "_syncwatch.Thread")
        assert self._run(clean) == []

    def test_unregistered_thread_inline_suppression(self):
        src = THREAD_SRC.replace(
            "target=print, daemon=True)",
            "target=print, daemon=True)  "
            "# tpu-lint: disable=unregistered-thread")
        assert self._run(src) == []

    def test_acquire_release_tracked_like_with(self):
        src = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._mu = threading.Lock()

    def fwd(self):
        self._lock.acquire()
        with self._mu:
            pass
        self._lock.release()

    def rev(self):
        with self._mu:
            self._lock.acquire()
            self._lock.release()
"""
        fs = self._run(src)
        assert rules_of(fs) == ["lock-order"]

    def test_one_level_call_inlining_carries_held_set(self):
        src = """
import threading
import time

class C:
    def __init__(self):
        self._lock = threading.Lock()

    def outer(self):
        with self._lock:
            self.inner()

    def inner(self):
        time.sleep(1.0)
"""
        fs = self._run(src)
        assert rules_of(fs) == ["blocking-under-lock"]
        assert "(called holding C._lock)" in fs[0].func

    def test_rules_registered_and_listed(self, capsys):
        from paddle_tpu.analysis.base import RULES
        for rule in ("lock-order", "blocking-under-lock",
                     "unregistered-thread"):
            assert rule in RULES
        assert lint_cli.main(["--list-rules", "x"]) == 0
        out = capsys.readouterr().out
        assert "lock-order" in out and "unregistered-thread" in out

    def test_cli_reports_and_no_concurrency_disables(self, tmp_path,
                                                     capsys):
        p = tmp_path / "pool.py"
        p.write_text(INVERTED_SRC)
        assert lint_cli.main([str(p)]) == 1
        assert "lock-order" in capsys.readouterr().out
        assert lint_cli.main([str(p), "--no-concurrency"]) == 0

    def test_lazy_exports(self):
        assert analysis.analyze_concurrency is not None
        assert analysis.lock_graph is not None

    def test_concurrency_pass_attaches_findings(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.static.passes import list_passes
        from paddle_tpu.static.program import Program
        assert "concurrency" in list_passes()
        prog = Program.from_callable(
            lambda x: x + 1.0, [jax.ShapeDtypeStruct((4,), jnp.float32)])
        out = prog.apply_pass("concurrency", fail_on="error")
        assert out.concurrency_findings == []


class TestConcurrencySelfGate:
    def test_repo_lock_graph_is_cycle_free_and_lint_clean(self):
        """THE tier-1 gate (ISSUE 20): the shipped package's own static
        lock graph has no cycles and zero concurrency findings — a future
        PR nesting locks inconsistently, blocking under a lock, or
        spawning a raw thread fails HERE, before any soak can wedge."""
        from paddle_tpu.analysis.concurrency import (analyze_paths,
                                                     find_cycles)
        findings, n_files, sites = analyze_paths([PKG])
        assert n_files > 150
        assert findings == [], "\n".join(f.format() for f in findings)
        assert find_cycles(sites) == []
        # the graph is genuinely populated (the PS durability hierarchy),
        # so an AST regression that stops SEEING locks also fails
        assert ("PsServer._wal_lock", "PsServer._seq_lock") in sites
