"""Cross-process collective test: 2 real OS processes, C++ TCPStore
rendezvous, jax.distributed CPU backend, psum across processes.

Reference technique: `test_collective_base.py:32` `_run_cluster` — ranks as
subprocesses, stdout compared to the numpy expectation."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_two_process_allreduce_via_tcpstore():
    runner = os.path.join(os.path.dirname(__file__), "collective_2proc_runner.py")
    port = _free_port()
    # strip every accelerator hook: the runners must come up as pure-CPU
    # jax processes whose FIRST backend touch is jax.distributed.initialize
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_", "JAX_", "XLA_", "PALLAS_",
                                "TPU_", "PYTHONPATH"))}
    procs = [subprocess.Popen([sys.executable, runner, str(r), str(port)],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              env=env, text=True)
             for r in range(2)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("2-process collective runner timed out")
        assert p.returncode == 0, f"runner failed:\n{err[-2000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))

    for o in outs:
        assert o["n_proc"] == 2
        # psum of rank-local [1,4] blocks: (1+2) everywhere
        np.testing.assert_allclose(np.asarray(o["allreduce"]),
                                   np.full((1, 4), 3.0))
