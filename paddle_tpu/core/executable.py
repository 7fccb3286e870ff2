"""One executable substrate under all four dispatch regimes.

Reference parity: the reference extracted phi out of fluid so eager and
static-graph execution share ONE kernel library instead of two
(PAPER.md §1 rows 3/6) — the same move one level up. Before this module,
`jit/train_step.py` (TrainStep/SPMDTrainStep), `jit/to_static.py`,
`ops/lazy.py` segments, and `serving/engine.py` bucket warm-up each grew
a private copy of the build→cache→dispatch plumbing: a signature cache
(`_seen_sigs` / `_prog_sig` / `_SEG_CACHE` / `_dispatched_sigs`), retrace
accounting, donation policy, timeline booking, and the OOM-dump seam —
so every cross-cutting feature (the PR-10 memory census, and now the
persistent compile cache) paid a ×4 implementation tax. The substrate
here is what each regime parameterizes instead:

- `ExecutableLedger` — the signature cache + retrace accounting + LRU
  executable cache, one implementation. `note(sig)` answers "novel?" and
  books the retrace counters under the regime's kind string (counter
  names unchanged: `jit.<kind>.traces` / `.retraces`).
- `booking(kind)` — the timeline phase around a dispatch. Opens
  `device_compute`; if the regime reports `bk.compiled()` the phase is
  renamed to `trace_compile` in place (the `_Phase.name` late-rename
  trick), so a compile is attributed exactly where it happened. A
  booking that finds the calling thread ALREADY inside an open phase
  suppresses its own phase entirely — this closes the latent
  double-accounting seam where a lazy-segment flush nested inside a
  step's phase booked the same wall seconds twice and broke the
  phase-sum≈wall invariant. Monitor counters (`trace_compile`,
  `trace_compile.<kind>`) are still counted when nested — suppression is
  about wall-time attribution, not compile counting.
- `acquire(kind, jitted, args)` — the persistent-cache build step
  (core/compile_cache.py): key the canonical StableHLO, deserialize a
  prior process's AOT-serialized executable on hit (re-wrapped with the
  regime's declared donation), export+persist on miss. Cache off = one
  module-attribute check, zero overhead.
- `dispatch_guard(label, report)` — the OOM forensics seam: the
  `mem.alloc` fault drill site plus `obs.memory.maybe_dump_oom` on the
  way out of a failed dispatch.

The post-commit re-tag half of the lifecycle stays with the regime (only
it knows which arrays are params vs slots vs pool); the substrate's
`retag` hook exists so regimes declare it once at construction.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Iterable, List, Optional, Tuple

from .. import faults as _faults
from .. import monitor as _monitor
from .. import obs as _obs
from ..obs import memory as _mem
from . import compile_cache as _cc

__all__ = ["ExecutableLedger", "booking", "acquire", "dispatch_guard"]


class ExecutableLedger:
    """Signature ledger + optional LRU executable cache for one dispatch
    regime. Replaces TrainStep `_seen_sigs`, to_static `_seen_sigs` +
    `_prog_sig`, lazy `_SEG_CACHE`/`_SEG_SEEN`, and serving
    `_dispatched_sigs` with one thread-safe implementation.

    `note(sig)` is the novelty test + retrace bookkeeping; `get`/`put`
    manage cached callables (LRU when `cap` is set, `evictions` counted,
    `on_evict(sig, value)` fired outside nothing — callers use it to
    mirror eviction counters)."""

    def __init__(self, kind: str, cap: Optional[int] = None,
                 on_evict: Optional[Callable[[Any, Any], None]] = None):
        self.kind = kind
        self._lock = threading.RLock()
        self._seen: set = set()
        self._cache: "OrderedDict[Any, Any]" = OrderedDict()
        self._cap = cap
        self.on_evict = on_evict
        self.evictions = 0
        # the signature the regime's published program was built for
        # (to_static's old `_prog_sig` role)
        self.current_sig: Any = None

    # ---- novelty / retrace accounting ----
    def note(self, sig, detail=None, retrace: bool = True) -> bool:
        """Record `sig` as dispatched; True when it was novel (the call
        ahead pays trace+compile). Books monitor retrace counters under
        this ledger's kind — `detail` overrides the signature logged
        (lazy passes op-count + leaf signature)."""
        with self._lock:
            novel = sig not in self._seen
            first = not self._seen
            if novel:
                self._seen.add(sig)
        if novel and retrace and _monitor._ENABLED:
            _monitor.record_retrace(self.kind,
                                    sig if detail is None else detail,
                                    first=first)
        return novel

    def seen(self, sig) -> bool:
        with self._lock:
            return sig in self._seen

    def seen_sigs(self) -> set:
        with self._lock:
            return set(self._seen)

    # ---- cached callables (LRU) ----
    def get(self, sig):
        with self._lock:
            if sig not in self._cache:
                return None
            self._cache.move_to_end(sig)
            return self._cache[sig]

    def put(self, sig, value) -> None:
        evicted: List[Tuple[Any, Any]] = []
        with self._lock:
            self._cache[sig] = value
            self._cache.move_to_end(sig)
            if self._cap is not None:
                while len(self._cache) > max(1, int(self._cap)):
                    evicted.append(self._cache.popitem(last=False))
                    self.evictions += 1
        for esig, evalue in evicted:
            if self.on_evict is not None:
                self.on_evict(esig, evalue)

    def set_cap(self, cap: Optional[int]) -> None:
        with self._lock:
            self._cap = cap
        if cap is not None:
            # shrink immediately (watch_flag lowering the cap mid-run)
            self.put_noop()

    def put_noop(self) -> None:
        """Re-run the eviction sweep without inserting (cap shrink)."""
        evicted: List[Tuple[Any, Any]] = []
        with self._lock:
            if self._cap is not None:
                while len(self._cache) > max(1, int(self._cap)):
                    evicted.append(self._cache.popitem(last=False))
                    self.evictions += 1
        for esig, evalue in evicted:
            if self.on_evict is not None:
                self.on_evict(esig, evalue)

    def keys(self) -> list:
        with self._lock:
            return list(self._cache.keys())

    def items(self) -> list:
        with self._lock:
            return list(self._cache.items())

    def clear(self, seen: bool = True) -> None:
        with self._lock:
            self._cache.clear()
            if seen:
                self._seen.clear()
            self.current_sig = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)

    def __contains__(self, sig) -> bool:
        with self._lock:
            return sig in self._cache


class _Booking:
    """Timeline booking around one dispatch. Opens `device_compute`,
    renamed in place to `trace_compile` if the regime calls
    `compiled()`. Nested inside an already-open phase on this thread →
    no phase of its own (the enclosing phase owns the wall time; monitor
    compile counters still fire)."""

    __slots__ = ("kind", "did_compile", "_ctx")

    def __init__(self, kind: str):
        self.kind = kind
        self.did_compile = False
        self._ctx = None

    def __enter__(self):
        if _obs._TL_ENABLED and not _obs.in_phase():
            self._ctx = _obs.timeline().phase("device_compute")
            self._ctx.__enter__()
        return self

    def compiled(self) -> None:
        """The dispatch underway traced+compiled a novel program: rename
        the open phase and count it. This is THE compile counter — the
        zero-compile warm-start acceptance reads `trace_compile`."""
        if self.did_compile:
            return
        self.did_compile = True
        if self._ctx is not None:
            self._ctx.name = "trace_compile"
        if _monitor._ENABLED:
            _monitor.count("trace_compile")
            _monitor.count(f"trace_compile.{self.kind}")

    def __exit__(self, *exc):
        if self._ctx is not None:
            self._ctx.__exit__(*exc)
            self._ctx = None
        return False


def booking(kind: str) -> _Booking:
    return _Booking(kind)


class _DispatchGuard:
    """OOM forensics around one dispatch: the `mem.alloc` fault drill
    site on the way in, `maybe_dump_oom` (RESOURCE_EXHAUSTED dump) on
    the way out of a failure. `report` is a zero-arg lambda producing
    the executable memory breakdown — only called when dumping."""

    __slots__ = ("label", "report")

    def __init__(self, label: str, report: Optional[Callable] = None):
        self.label = label
        self.report = report

    def __enter__(self):
        if _faults._ENABLED:
            try:
                _faults.check("mem.alloc")
            except Exception as exc:
                # an __enter__ raise skips __exit__ — dump here so the
                # injected fault exercises the same forensics path
                _mem.maybe_dump_oom(exc, executable=self.label,
                                    report=self.report)
                raise
        return self

    def __exit__(self, etype, exc, tb):
        if exc is not None:
            _mem.maybe_dump_oom(exc, executable=self.label,
                                report=self.report)
        return False


def dispatch_guard(label: str, report: Optional[Callable] = None):
    return _DispatchGuard(label, report)


# ---- persistent-cache build step -------------------------------------------

def acquire(kind: str, jitted, args: Iterable[Any], donate: Tuple[int, ...] = (),
            label: str = "", mesh_shape=None):
    """Build step for a novel signature. With the persistent cache off
    (default) this is `(jitted, "fresh")` after one module-attribute
    check. With `FLAGS_compile_cache_dir` set: lower to StableHLO, key
    it, and either deserialize a prior process's serialized executable
    (source `"disk"` — the call is re-wrapped in `jax.jit` with the
    regime's declared `donate` argnums, preserving the `is_deleted()`
    donation guarantees) or export+persist this process's build for the
    next one (source `"fresh"`). Every failure path degrades to the
    fresh jitted callable — the cache can only ever save work.

    NOTE: programs the export path cannot serialize (host callbacks,
    closures over opaque out-trees) count `export_skips`."""
    if not _cc._DIR:
        return jitted, "fresh"
    import jax
    args = tuple(args)
    try:
        text = jitted.lower(*args).as_text()
        key = _cc.cache_key(text, mesh_shape=mesh_shape, extra=(kind,))
    except Exception as e:
        _cc.note_export_skip(f"lower: {type(e).__name__}: {e}")
        return jitted, "fresh"
    blob = _cc.lookup(key, mesh_shape=mesh_shape)
    if blob is not None:
        try:
            exp = jax.export.deserialize(blob)
            call = jax.jit(lambda *a: exp.call(*a),
                           donate_argnums=tuple(donate))
            if _monitor._ENABLED:
                _monitor.log_event("compile_cache.hit", kind=kind, key=key,
                                   label=label)
            return call, "disk"
        except Exception:
            _cc._fallback(key, "deserialize_failed")
    _cc.note_miss()
    try:
        exp = jax.export.export(jitted)(*args)
        _cc.store(key, exp.serialize(), kind=kind, label=label,
                  mesh_shape=mesh_shape)
    except Exception as e:
        _cc.note_export_skip(f"export: {type(e).__name__}: {e}")
    return jitted, "fresh"
