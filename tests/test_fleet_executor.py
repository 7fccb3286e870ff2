"""FleetExecutor actor pipeline + DistModel distributed inference."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import DistModel, FleetExecutor


class TestFleetExecutor:
    def test_three_stage_pipeline_matches_composition(self):
        import jax
        import jax.numpy as jnp
        stages = [jax.jit(lambda x: x * 2.0),
                  jax.jit(lambda x: x + 1.0),
                  jax.jit(lambda x: jnp.sqrt(x))]
        fx = FleetExecutor(stages)
        micros = [np.full((4,), float(i)) for i in range(8)]
        outs = fx.run(micros)
        for i, o in enumerate(outs):
            np.testing.assert_allclose(np.asarray(o),
                                       np.sqrt(np.full((4,), i * 2.0) + 1.0),
                                       rtol=1e-6)

    def test_ordering_preserved_with_many_microbatches(self):
        fx = FleetExecutor([lambda x: x], max_inflight=1)
        outs = fx.run([np.array([i]) for i in range(32)])
        assert [int(o[0]) for o in outs] == list(range(32))

    def test_stage_error_fails_fast(self):
        def boom(x):
            raise ValueError("stage exploded")
        fx = FleetExecutor([lambda x: x, boom])
        with pytest.raises(RuntimeError, match="interceptor"):
            fx.run([np.zeros(2)], timeout=30)

    def test_empty_stages_rejected(self):
        with pytest.raises(ValueError):
            FleetExecutor([])


class TestDistModel:
    def test_sharded_regime_matches_single_device(self):
        import jax.numpy as jnp
        from paddle_tpu.parallel.topology import create_mesh
        mesh = create_mesh({"dp": 8})

        def program(x):
            return jnp.tanh(x) @ jnp.ones((16, 4), jnp.float32)

        x = np.random.default_rng(0).normal(size=(32, 16)).astype(np.float32)
        dm = DistModel(program=program, mesh=mesh, in_spec=("dp", None))
        out = dm.predict(x)
        np.testing.assert_allclose(out, np.tanh(x) @ np.ones((16, 4)),
                                   rtol=1e-5)

    def test_pipelined_regime(self):
        import jax
        stages = [jax.jit(lambda x: x * 3.0), jax.jit(lambda x: x - 1.0)]
        dm = DistModel(stages=stages)
        x = np.arange(16, dtype=np.float32).reshape(16, 1)
        out = dm.predict(x, n_micro=4)
        np.testing.assert_allclose(out, x * 3.0 - 1.0)

    def test_exactly_one_regime(self):
        with pytest.raises(ValueError):
            DistModel()
        with pytest.raises(ValueError):
            DistModel(program=lambda x: x, stages=[lambda x: x])


class TestCrossProcessFleetExecutor:
    """r5: Carrier/Interceptor loops spanning two REAL processes over the
    DistMessageBus (TCPStore rendezvous) — the reference runs the same
    topology over brpc (`fleet_executor/message_bus.cc`)."""

    def test_two_process_pipeline(self):
        import json
        import os
        import socket
        import subprocess
        import sys as _sys
        from paddle_tpu import _native
        if not _native.available():
            import pytest as _pytest
            _pytest.skip("no C++ toolchain for TCPStore")
        runner = os.path.join(os.path.dirname(__file__),
                              "fleet_exec_2proc_runner.py")
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "JAX_", "XLA_", "PALLAS_",
                                    "TPU_", "PYTHONPATH"))}
        procs = [subprocess.Popen(
            [_sys.executable, runner, str(r), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True) for r in range(2)]
        outs = {}
        for p in procs:
            try:
                out, err = p.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise AssertionError("fleet exec 2-proc runner timed out")
            assert p.returncode == 0, f"runner failed:\n{err[-2000:]}"
            rec = json.loads(out.strip().splitlines()[-1])
            outs[rec["rank"]] = rec["outs"]
        # stage0 (x*2) on rank 0, stage1 (+1) on rank 1: i -> 2i + 1
        assert outs[0] is None
        got = outs[1]
        assert got == [[2.0 * i + 1.0] * 2 for i in range(5)]
