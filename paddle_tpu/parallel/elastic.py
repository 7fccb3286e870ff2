"""Elastic training: lease-based membership + gang relaunch.

Reference parity: `python/paddle/distributed/fleet/elastic/manager.py:130`
(ElasticManager: nodes register in etcd with TTL leases, watches trigger
membership changes, `manager.py:245-266`) and `elastic/__init__.py:48`
(launch_elastic: restart loop around the launcher). Env contract kept:
`PADDLE_ELASTIC_*`.

TPU-native redesign: etcd is replaced by the framework's own C++ TCPStore —
each node heartbeats a timestamp under `lease:{rank}`; staleness past the
TTL is the lease expiry; the single-host gang launcher kills and respawns
the whole gang on any member death (XLA SPMD jobs cannot run degraded, so
scale-in == restart with new membership, same as the reference's collective
mode).
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import List, Optional, Sequence

from .. import faults as _faults
from .. import monitor as _monitor
from ..utils import syncwatch as _syncwatch


class PrefixStore:
    """Namespace adapter so one TCPStore hosts many planes: every key a
    consumer writes (ElasticManager leases, join tickets, the PS HA
    primary record) lands under its own prefix. Grew up in the serving
    fleet; promoted here because the PS HA plane shares it."""

    def __init__(self, store, prefix: str):
        self._store = store
        self._prefix = prefix

    def set(self, key, value):
        return self._store.set(self._prefix + key, value)

    def get(self, key):
        return self._store.get(self._prefix + key)

    def add(self, key, amount):
        return self._store.add(self._prefix + key, amount)

    def wait(self, keys, timeout=None):
        return self._store.wait([self._prefix + k for k in keys], timeout)


class ElasticManager:
    """Lease-based membership over a TCPStore (manager.py:130 role)."""

    def __init__(self, store, rank: int, world_size: int,
                 lease_ttl: float = 10.0, heartbeat_interval: float = 2.0):
        self.store = store
        self.rank = rank
        self.world_size = world_size
        self.lease_ttl = lease_ttl
        self.heartbeat_interval = heartbeat_interval
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # on_rank_dead plane: callbacks fired once per lease-expiry
        # TRANSITION (a rank that heartbeats again re-arms), driven by a
        # dedicated watcher thread so callers don't have to poll
        # alive_ranks themselves
        self._dead_cbs: List = []
        self._known_dead: set = set()
        self._watch_stop = threading.Event()
        self._watch_thread: Optional[threading.Thread] = None

    # -- node side --
    def register(self):
        self._beat()
        self._thread = _syncwatch.Thread(target=self._run, daemon=True,
                                        name="elastic-heartbeat")
        self._thread.start()
        return self

    def _beat(self):
        if _faults._ENABLED:
            _faults.check("elastic.heartbeat")
        self.store.set(f"lease:{self.rank}", repr(time.time()))

    def _run(self):
        # a TRANSIENT store error (blip, injected fault) must not kill the
        # heartbeat thread — that would turn a one-interval hiccup into a
        # permanent lease expiry. Retry next interval; only give up once
        # the failures alone would have expired the lease anyway.
        misses = 0
        while not self._stop.wait(self.heartbeat_interval):
            try:
                self._beat()
                misses = 0
            except Exception:
                misses += 1
                if _monitor._ENABLED:
                    _monitor.count("elastic.heartbeat_errors")
                if misses * self.heartbeat_interval > self.lease_ttl * 3:
                    return  # store genuinely gone: lease is long expired

    def reclaim(self, rank: int) -> None:
        """Forcibly expire `rank`'s lease (the store has no delete: an
        empty value reads as expired). The autoscaler uses this to
        reclaim a corpse's lease after a SIGKILL mid-drain or a spawn
        that never came up — membership converges immediately instead of
        waiting out the TTL."""
        self.store.set(f"lease:{rank}", b"")

    def stop(self):
        self._stop.set()
        self._watch_stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=2)
            self._watch_thread = None

    # -- watcher side --
    def on_rank_dead(self, callback, interval: Optional[float] = None):
        """Register `callback(rank)` to fire ONCE per lease-expiry
        transition (the fleet router and tests react to replica death
        promptly instead of polling `alive_ranks`). Each newly expired
        lease also counts `elastic.lease_expired`. A rank whose lease
        recovers (rejoin) re-arms: a later expiry fires again. The first
        registration starts the `elastic-watcher` thread; `stop()` ends
        it."""
        self._dead_cbs.append(callback)
        if self._watch_thread is None:
            iv = interval if interval is not None else \
                min(1.0, self.heartbeat_interval)
            self._watch_stop.clear()
            self._watch_thread = _syncwatch.Thread(
                target=self._watch_loop, args=(iv,), daemon=True,
                name="elastic-watcher")
            self._watch_thread.start()
        return self

    def _watch_loop(self, interval: float) -> None:
        ever_alive: set = set()
        while not self._watch_stop.wait(interval):
            try:
                alive = set(self.alive_ranks())
            except Exception:
                continue  # transient store blip: check next interval
            ever_alive |= alive
            # only a rank that was OBSERVED alive can expire — a fleet
            # watching a sparse id space must not page for ids that never
            # registered
            dead = ever_alive - alive
            fresh = dead - self._known_dead
            self._known_dead = dead  # recovered ranks re-arm implicitly
            for r in sorted(fresh):
                if _monitor._ENABLED:
                    _monitor.count("elastic.lease_expired")
                for cb in list(self._dead_cbs):
                    try:
                        cb(r)
                    except Exception:
                        pass  # one bad callback must not kill the watcher
    def alive_ranks(self) -> List[int]:
        now = time.time()
        alive = []
        for r in range(self.world_size):
            try:
                ts = float(self.store.get(f"lease:{r}").decode())
            except (KeyError, ValueError):
                # missing OR undecodable (truncated/garbled write) lease ==
                # expired; a corrupt value must not crash the watcher
                # thread (same contract pending_joins already applies)
                continue
            if now - ts <= self.lease_ttl:
                alive.append(r)
        return alive

    def dead_ranks(self) -> List[int]:
        alive = set(self.alive_ranks())
        return [r for r in range(self.world_size) if r not in alive]

    def watch(self, interval: float = 1.0, max_wait: Optional[float] = None):
        """Block until membership shrinks; returns the dead ranks."""
        start = time.time()
        while True:
            dead = self.dead_ranks()
            if dead:
                return dead
            if max_wait is not None and time.time() - start > max_wait:
                return []
            time.sleep(interval)

    # -- scale-out (manager.py:215-266 world-size-change role) --
    def announce_join(self, node_id: str = "") -> int:
        """A NEW node announces itself to the gang's store; returns its
        join ticket. The controller absorbs pending tickets at the next
        re-rendezvous, growing the world size."""
        seq = self.store.add("elastic:join_seq", 1)
        self.store.set(f"elastic:join:{seq}",
                       f"{time.time()!r}:{node_id}")
        return seq

    def pending_joins(self, absorbed: int = 0) -> List[int]:
        """Join tickets newer than `absorbed` whose announcement is still
        fresh (within the lease TTL x 6 — joiners wait for the gang)."""
        try:
            # add(0) reads the counter (native add-counters live in their
            # own namespace; plain get can't see them)
            seq = int(self.store.add("elastic:join_seq", 0))
        except Exception:
            return []
        now = time.time()
        out = []
        for i in range(absorbed + 1, seq + 1):
            try:
                raw = self.store.get(f"elastic:join:{i}").decode()
                ts = float(raw.split(":", 1)[0])
            except (KeyError, ValueError):
                continue
            if now - ts <= self.lease_ttl * 6:
                out.append(i)
        return out

    def watch_membership(self, interval: float = 1.0,
                         max_wait: Optional[float] = None,
                         absorbed: int = 0):
        """Block until membership CHANGES either way:
        ('scale_in', dead_ranks) | ('scale_out', join_tickets) |
        ('steady', []) on timeout."""
        start = time.time()
        while True:
            dead = self.dead_ranks()
            if dead:
                return ("scale_in", dead)
            joins = self.pending_joins(absorbed)
            if joins:
                return ("scale_out", joins)
            if max_wait is not None and time.time() - start > max_wait:
                return ("steady", [])
            time.sleep(interval)


class ElasticResult:
    def __init__(self, restarts: int, returncodes: Sequence[int]):
        self.restarts = restarts
        self.returncodes = list(returncodes)

    @property
    def success(self) -> bool:
        return all(rc == 0 for rc in self.returncodes)


def launch_elastic(training_script: str, script_args: Sequence[str] = (),
                   nprocs: int = 2, max_restarts: int = 3,
                   poll_interval: float = 0.2, env: Optional[dict] = None,
                   timeout: float = 300.0, store=None,
                   max_np: Optional[int] = None) -> ElasticResult:
    """Gang launcher with relaunch + scale loop (elastic/__init__.py:48 +
    manager.py:215-266 world-size-change roles).

    Spawns `nprocs` ranks of `training_script`. Events:
    - a rank dying non-zero kills the gang and relaunches it (up to
      `max_restarts` times) — collective jobs restart as a unit;
    - with a `store`, a join announcement (ElasticManager.announce_join
      from a NEW node) triggers a re-rendezvous: the gang is killed and
      relaunched with world size grown by the pending joins (capped at
      `max_np`). Scale events do NOT consume the failure budget.
    Each (re)launch exports the CURRENT world size via
    PADDLE_TRAINERS_NUM/PADDLE_ELASTIC_NP, so AutoCheckpoint-driven
    scripts restore their snapshot and resume at the new membership.

    One worker process per rank ON THIS HOST: what the CPU multi-process
    tests need (`JAX_PLATFORMS=cpu` in `env`). A chip belongs to one
    process at a time, so on a host with chips the ranks would all claim
    the same chip(s); there the unit of restart is the one
    single-controller SPMD process that drives every chip of the host.
    """
    base_env = dict(os.environ if env is None else env)
    watcher = ElasticManager(store, rank=-1, world_size=0) if store else None
    absorbed = 0
    attempt = 0      # failure count (scale events don't advance it)
    launches = 0
    np_now = nprocs
    procs: List[subprocess.Popen] = []
    while attempt <= max_restarts:
        procs = []
        for r in range(np_now):
            e = dict(base_env)
            e.update({
                "PADDLE_TRAINER_ID": str(r),
                "PADDLE_TRAINERS_NUM": str(np_now),
                "PADDLE_ELASTIC_RESTART_COUNT": str(launches),
                "PADDLE_ELASTIC_NP": str(np_now),
            })
            procs.append(subprocess.Popen(
                [sys.executable, training_script, *map(str, script_args)],
                env=e))
        launches += 1
        deadline = time.time() + timeout
        outcome = "done"
        while True:
            rcs = [p.poll() for p in procs]
            if any(rc is not None and rc != 0 for rc in rcs):
                outcome = "failed"
                break
            if all(rc == 0 for rc in rcs):
                break
            if watcher is not None:
                joins = watcher.pending_joins(absorbed)
                # Partial absorption: grow whenever there is headroom at
                # all — the absorb slice below caps how many join.
                if joins and (max_np is None or np_now < max_np):
                    outcome = "scale_out"
                    break
            if time.time() > deadline:
                outcome = "failed"
                break
            time.sleep(poll_interval)
        if outcome == "done":
            return ElasticResult(attempt, [p.returncode for p in procs])
        for p in procs:  # kill the rest of the gang, then relaunch
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if outcome == "scale_out":
            joins = watcher.pending_joins(absorbed)
            take = joins if max_np is None else \
                joins[:max(0, max_np - np_now)]
            absorbed = max(take or [absorbed])
            np_now += len(take)
        else:
            attempt += 1
    return ElasticResult(max_restarts, [p.returncode for p in procs])
