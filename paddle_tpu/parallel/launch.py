"""Distributed launcher: `python -m paddle_tpu.distributed.launch train.py`.

Reference parity: `python/paddle/distributed/fleet/launch.py:523` (launch →
launch_collective:380 → start_local_trainers with PADDLE_* env).

TPU-native process model: ONE process per HOST (chips inside a host are
addressed by the mesh, not by processes), so on a single host the launcher
simply execs the script with rank env set; multi-host launch sets the
coordinator address for jax.distributed.

`--nproc_per_node N` (N > 1) is for the CPU multi-process tests only: it
starts N worker processes on this host, which must run with
`JAX_PLATFORMS=cpu`. A chip belongs to one process at a time, so on a host
with chips every such worker would claim the same chip(s) and all but one
would fail or hang. On chips the sharded path is the single-controller SPMD
one — one process, a mesh over `jax.devices()` (`fleet.init`,
`SPMDTrainStep`; `chip_smoke.py --chips 4` runs it).
"""
from __future__ import annotations

import argparse
import os
import runpy
import subprocess
import sys


def _parse():
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--nnodes", type=int, default=int(os.environ.get("PADDLE_NNODES", 1)))
    p.add_argument("--node_rank", type=int, default=int(os.environ.get("PADDLE_NODE_RANK", 0)))
    p.add_argument("--master", default=os.environ.get("PADDLE_MASTER", "127.0.0.1:6170"))
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--devices", "--gpus", dest="devices", default=None)
    p.add_argument("--log_dir", default=None)
    p.add_argument("--run_mode", default=None,
                   help="collective (default) | ps | elastic")
    # PS mode (reference launch_ps, fleet/launch.py:416)
    p.add_argument("--server_num", type=int, default=0)
    p.add_argument("--worker_num", type=int, default=0)
    p.add_argument("--servers", default="", help="host:port list for PS")
    # elastic mode (reference launch_elastic, elastic/__init__.py:48)
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args()
    if args.run_mode is None:
        # mode autodetect (reference which_distributed_mode, launch.py:448)
        args.run_mode = "ps" if (args.server_num or args.servers
                                  or args.worker_num) else "collective"
    return args


def _launch_ps(args):
    """Server + trainer process gang (launch_ps role): servers get
    TRAINING_ROLE=PSERVER and a port; trainers get the endpoint list."""
    if args.servers:
        endpoints = [e for e in args.servers.split(",") if e]
    else:
        endpoints = [f"127.0.0.1:{8200 + i}" for i in range(args.server_num)]
    n_workers = args.worker_num or 1
    procs = []

    def spawn(role, rank, extra):
        env = dict(os.environ)
        env["TRAINING_ROLE"] = role
        env["PADDLE_PSERVERS_IP_PORT_LIST"] = ",".join(endpoints)
        env["PADDLE_TRAINERS_NUM"] = str(n_workers)
        env.update(extra)
        out = None
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            out = open(os.path.join(args.log_dir,
                                    f"{role.lower()}log.{rank}"), "w")
        return subprocess.Popen(
            [sys.executable, args.training_script] + args.training_script_args,
            env=env, stdout=out, stderr=subprocess.STDOUT if out else None)

    for i, ep in enumerate(endpoints):
        procs.append(spawn("PSERVER", i, {
            "PADDLE_PORT": ep.rsplit(":", 1)[1], "POD_IP": ep.rsplit(":", 1)[0]}))
    for r in range(n_workers):
        procs.append(spawn("TRAINER", r, {"PADDLE_TRAINER_ID": str(r)}))
    rc = 0
    # trainers finish -> kill servers (reference behavior)
    for p in procs[len(endpoints):]:
        rc |= p.wait()
    for p in procs[:len(endpoints)]:
        p.terminate()
    sys.exit(rc)


def launch():
    args = _parse()
    if args.run_mode == "ps":
        return _launch_ps(args)
    if args.run_mode == "elastic":
        from .elastic import launch_elastic
        res = launch_elastic(args.training_script,
                             args.training_script_args,
                             nprocs=max(args.nproc_per_node, 1),
                             max_restarts=args.max_restarts)
        sys.exit(0 if res.success else 1)
    base_env = dict(os.environ)
    base_env["PADDLE_MASTER"] = args.master
    base_env["PADDLE_TRAINERS_NUM"] = str(args.nnodes * args.nproc_per_node)

    if args.nproc_per_node == 1:
        os.environ.update(base_env)
        os.environ["PADDLE_TRAINER_ID"] = str(args.node_rank)
        os.environ["PADDLE_CURRENT_ENDPOINT"] = args.master if args.node_rank == 0 \
            else f"127.0.0.1:{6171 + args.node_rank}"
        sys.argv = [args.training_script] + args.training_script_args
        runpy.run_path(args.training_script, run_name="__main__")
        return

    # multi-process simulation (CPU only: see the module docstring)
    procs = []
    for local in range(args.nproc_per_node):
        rank = args.node_rank * args.nproc_per_node + local
        env = dict(base_env)
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_RANK_IN_NODE"] = str(local)
        env["PADDLE_CURRENT_ENDPOINT"] = f"127.0.0.1:{6171 + rank}"
        env["PADDLE_TRAINER_ENDPOINTS"] = ",".join(
            f"127.0.0.1:{6171 + r}" for r in range(args.nnodes * args.nproc_per_node))
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            out = open(os.path.join(args.log_dir, f"workerlog.{rank}"), "w")
        else:
            out = None
        procs.append(subprocess.Popen(
            [sys.executable, args.training_script] + args.training_script_args,
            env=env, stdout=out, stderr=subprocess.STDOUT if out else None))
    rc = 0
    for p in procs:
        rc |= p.wait()
    sys.exit(rc)


if __name__ == "__main__":
    launch()
