"""Process-level distributed environment.

Reference parity: `python/paddle/distributed/parallel.py:79`
(init_parallel_env) + ParallelEnv, env vars PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ENDPOINTS set by the launcher.

TPU-first: one process per HOST (not per chip); in-process chips are
addressed by the mesh, cross-host via jax.distributed (coordination service
= the reference's TCPStore role; see paddle_tpu._native.tcpstore for the
C++ rendezvous used to exchange the coordinator address when no scheduler
provides one).
"""
from __future__ import annotations

import os

import jax

_INITIALIZED = [False]


def get_rank(group=None) -> int:
    env = os.environ.get("PADDLE_TRAINER_ID")
    if env is not None:
        return int(env)
    try:
        return jax.process_index()
    except Exception:
        return 0


def get_world_size(group=None) -> int:
    env = os.environ.get("PADDLE_TRAINERS_NUM")
    if env is not None:
        return int(env)
    try:
        return jax.process_count()
    except Exception:
        return 1


class ParallelEnv:
    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def local_rank(self):
        return int(os.environ.get("PADDLE_RANK_IN_NODE", 0))

    @property
    def dev_id(self):
        return self.local_rank

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:6170")

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else [self.current_endpoint]


def init_parallel_env(strategy=None):
    """Bring up cross-host coordination when endpoints are provided.

    Single-host (the common TPU-pod-slice-per-host case during tests) is a
    no-op: all chips are already visible to this process.
    """
    if _INITIALIZED[0]:
        return ParallelEnv()
    eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS")
    # world size/rank from env ONLY here: jax.process_count() would
    # initialize the XLA backend, after which initialize() is illegal
    nproc = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if eps and nproc > 1:
        if not jax.distributed.is_initialized():
            coordinator = eps.split(",")[0]
            jax.distributed.initialize(coordinator_address=coordinator,
                                       num_processes=nproc, process_id=rank)
    _INITIALIZED[0] = True
    return ParallelEnv()
