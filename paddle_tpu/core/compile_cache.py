"""Persistent on-disk executable cache — fleet warm start in seconds.

Reference parity: the reference keeps a program/executor cache so one
process never recompiles a ProgramDesc it already built
(`framework/executor_cache.h` role); at fleet scale the same waste happens
ACROSS processes — every serving replica re-warms its bucket ladder and
every preempted trainer re-traces its step, recompiling programs some
other process already compiled. This module is the cross-process half of
the `core/executable.py` substrate: novel builds are AOT-serialized via
the `jax.export` path `jit/save_load.py` already rides, keyed by

    sha256(canonical StableHLO text
           + topology fingerprint (device kind, device count, mesh shape)
           + jax version
           + relevant flags)

and persisted crash-atomically (`framework/sharded_io.atomic_write`, CRC
manifests, tmp+rename with per-writer tmp names so lock-free concurrent
writers are last-writer-wins). A second process with the same program and
topology deserializes instead of compiling; corrupt, stale-version, or
wrong-topology entries fall back to a fresh compile (`fallbacks` counter,
never an error). The disk footprint is a size-capped LRU
(`FLAGS_compile_cache_mb`), age-ranked by each entry's last-use stamp.

Hot-path contract (monitor/faults/obs regime): every build site checks
ONE module attribute (`_DIR`) and pays nothing else while the flag is
unset. Counters are plain module ints (`stats()`), mirrored to
`paddle_tpu.monitor` counters `compile_cache.*` when the monitor is on.

Fault drill site: `compile_cache.write` (torn/corrupt blob bytes — the
manifest CRC is of the INTENDED bytes, so a mangled write fails lookup
verification and falls back).
"""
from __future__ import annotations

import hashlib
import json
import os
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

from .. import faults as _faults
from .. import monitor as _monitor
from . import flags as _flags

__all__ = [
    "enabled", "cache_dir", "cache_key", "topology_fingerprint",
    "lookup", "store", "entries", "gc", "verify", "stats", "reset_stats",
    "warm_start_report", "place_jax_cache",
]

_SCHEMA = 1

# ---- gate (one module attribute on the disabled path) ----------------------
_DIR: str = str(_flags.flag("compile_cache_dir") or "")

# process-lifetime counters (monitor may be off; serving stats() and tests
# read these regardless)
hits: int = 0
misses: int = 0
fallbacks: int = 0
stores: int = 0
evictions: int = 0
export_skips: int = 0   # programs the export path cannot serialize


def _on_dir(value) -> None:
    global _DIR
    _DIR = str(value or "")
    if _DIR:
        place_jax_cache()


# JAX's own persistent compilation cache (the StableHLO→binary stage; on
# TPU the dominant cost — the export blob alone removes only the trace).
# Its directory is part of the cache key, so it must not move: one fixed,
# git-ignored path inside the checkout, unless the environment places it.
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
JAX_CACHE_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_jax_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory — the ONE rule `chip_smoke.py`, `bench.py` and this package
    share. Where `JAX_COMPILATION_CACHE_DIR` is set the cache lives there
    and no other is ever set; otherwise at `JAX_CACHE_DEFAULT`. Nothing
    unsets it: `FLAGS_compile_cache_dir` places the export-blob store
    above and only switches this on."""
    import jax
    want = os.environ.get(JAX_CACHE_ENV) or JAX_CACHE_DEFAULT
    # with the variable set before `import jax` this is already true
    if jax.config.jax_compilation_cache_dir != want:
        jax.config.update("jax_compilation_cache_dir", want)
    return want


_flags.watch_flag("compile_cache_dir", _on_dir)
if _DIR:
    place_jax_cache()


def enabled() -> bool:
    return bool(_DIR)


def cache_dir() -> str:
    return _DIR


def _count(name: str, delta: int = 1) -> None:
    if _monitor._ENABLED:
        _monitor.count(f"compile_cache.{name}", delta)


# ---- key anatomy -----------------------------------------------------------

def topology_fingerprint(mesh_shape=None) -> str:
    """Device kind × count (+ mesh axes) — an entry compiled for one
    topology must never be offered to another."""
    import jax
    devs = jax.devices()
    fp = f"{devs[0].device_kind}x{len(devs)}"
    if mesh_shape:
        fp += ";mesh=" + ",".join(f"{a}={n}" for a, n in
                                  (mesh_shape.items()
                                   if isinstance(mesh_shape, dict)
                                   else mesh_shape))
    return fp


def _canonicalize(text: str) -> str:
    """Strip location metadata and trailing whitespace so cosmetically
    different lowerings of the same program hash identically."""
    lines = []
    for ln in text.splitlines():
        if ln.lstrip().startswith("loc("):
            continue
        lines.append(ln.rstrip())
    return "\n".join(lines)


def _relevant_flags() -> str:
    vals = []
    for name in ("tpu_matmul_precision", "check_nan_inf"):
        vals.append(f"{name}={_flags.flag(name)}")
    return ";".join(vals)


def cache_key(stablehlo_text: str, mesh_shape=None,
              extra: Tuple[str, ...] = ()) -> str:
    import jax
    h = hashlib.sha256()
    h.update(_canonicalize(stablehlo_text).encode())
    h.update(b"\x00" + topology_fingerprint(mesh_shape).encode())
    h.update(b"\x00" + jax.__version__.encode())
    h.update(b"\x00" + _relevant_flags().encode())
    for item in extra:
        h.update(b"\x00" + str(item).encode())
    return h.hexdigest()[:40]


# ---- storage layout: <dir>/<key>.bin + <dir>/<key>.json --------------------

def _paths(key: str, dirname: Optional[str] = None) -> Tuple[str, str]:
    d = dirname or _DIR
    return os.path.join(d, key + ".bin"), os.path.join(d, key + ".json")


def _read_manifest(mpath: str) -> Optional[dict]:
    try:
        with open(mpath) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _write_manifest(mpath: str, manifest: dict) -> None:
    from ..framework.sharded_io import atomic_write
    atomic_write(mpath, json.dumps(manifest).encode(), unique_tmp=True)


def _prune(key: str, dirname: Optional[str] = None) -> None:
    for path in _paths(key, dirname):
        try:
            os.remove(path)
        except OSError:
            pass


def _fallback(key: str, why: str, dirname: Optional[str] = None,
              prune: bool = True) -> None:
    global fallbacks
    fallbacks += 1
    _count("fallbacks")
    if _monitor._ENABLED:
        _monitor.log_event("compile_cache.fallback", key=key, why=why)
    if prune:
        _prune(key, dirname)


def lookup(key: str, mesh_shape=None) -> Optional[bytes]:
    """Serialized executable bytes for `key`, or None (miss OR fallback).
    Verifies the manifest CRC and re-validates the recorded jax version /
    topology against the current process (defense in depth — they are in
    the key, but a copied or forged entry must still never load). A bad
    entry is pruned and counted as a fallback, never raised."""
    global hits
    import jax
    bpath, mpath = _paths(key)
    manifest = _read_manifest(mpath)
    if manifest is None:
        if os.path.exists(bpath):           # blob without commit record
            _fallback(key, "missing_manifest")
        return None
    try:
        with open(bpath, "rb") as f:
            blob = f.read()
    except OSError:
        _fallback(key, "missing_blob")
        return None
    if zlib.crc32(blob) & 0xFFFFFFFF != manifest.get("crc"):
        _fallback(key, "crc_mismatch")
        return None
    if manifest.get("jax_version") != jax.__version__:
        _fallback(key, "stale_jax_version")
        return None
    if manifest.get("topology") != topology_fingerprint(mesh_shape):
        _fallback(key, "wrong_topology")
        return None
    hits += 1
    _count("hits")
    # LRU stamp + hit count: lock-free last-writer-wins manifest rewrite
    manifest["hits"] = int(manifest.get("hits", 0)) + 1
    manifest["last_used"] = time.time()
    try:
        _write_manifest(mpath, manifest)
    except OSError:
        pass
    return blob


def store(key: str, blob: bytes, kind: str = "", label: str = "",
          mesh_shape=None) -> bool:
    """Persist one entry crash-atomically. The manifest CRC is computed
    over the INTENDED bytes before the `compile_cache.write` fault site
    can mangle them, so a torn write is caught by the next lookup. Never
    raises; a failed store just means the next process compiles fresh."""
    global stores
    import jax
    if not _DIR:
        return False
    bpath, mpath = _paths(key)
    manifest = {
        "schema": _SCHEMA,
        "key": key,
        "kind": kind,
        "label": label,
        "bytes": len(blob),
        "crc": zlib.crc32(blob) & 0xFFFFFFFF,
        "jax_version": jax.__version__,
        "topology": topology_fingerprint(mesh_shape),
        "created": time.time(),
        "last_used": time.time(),
        "hits": 0,
    }
    if _faults._ENABLED:
        blob = _faults.mangle("compile_cache.write", blob)
    try:
        from ..framework.sharded_io import atomic_write
        os.makedirs(_DIR, exist_ok=True)
        atomic_write(bpath, blob, unique_tmp=True)
        _write_manifest(mpath, manifest)
    except OSError:
        return False
    stores += 1
    _count("stores")
    _enforce_cap()
    return True


def note_miss() -> None:
    global misses
    misses += 1
    _count("misses")


def note_export_skip(why: str = "") -> None:
    global export_skips
    export_skips += 1
    _count("export_skips")
    if _monitor._ENABLED and why:
        _monitor.log_event("compile_cache.export_skip", why=why[:200])


# ---- listing / gc / verify (the monitor CLI's `cache` subcommand) ----------

def entries(dirname: Optional[str] = None) -> List[Dict[str, Any]]:
    """Manifest-backed listing of every committed entry, LRU first."""
    d = dirname or _DIR
    out: List[Dict[str, Any]] = []
    if not d or not os.path.isdir(d):
        return out
    for name in sorted(os.listdir(d)):
        if not name.endswith(".json"):
            continue
        manifest = _read_manifest(os.path.join(d, name))
        if not manifest or "key" not in manifest:
            continue
        bpath = os.path.join(d, manifest["key"] + ".bin")
        try:
            nbytes = os.path.getsize(bpath)
        except OSError:
            nbytes = -1   # torn entry: manifest without blob
        row = dict(manifest)
        row["disk_bytes"] = nbytes
        row["age_s"] = max(0.0, time.time() - float(
            manifest.get("created", 0.0)))
        out.append(row)
    out.sort(key=lambda r: float(r.get("last_used", 0.0)))
    return out


def total_bytes(dirname: Optional[str] = None) -> int:
    return sum(max(0, e["disk_bytes"]) + len(json.dumps(e))
               for e in entries(dirname))


def gc(dirname: Optional[str] = None,
       cap_mb: Optional[float] = None) -> List[str]:
    """Evict least-recently-used entries until the directory fits the cap
    (`FLAGS_compile_cache_mb`). Returns evicted keys."""
    global evictions
    d = dirname or _DIR
    cap = float(_flags.flag("compile_cache_mb")) if cap_mb is None \
        else float(cap_mb)
    cap_bytes = int(cap * 1024 * 1024)
    rows = entries(d)
    used = sum(max(0, r["disk_bytes"]) for r in rows)
    evicted: List[str] = []
    for row in rows:                       # LRU first
        if used <= cap_bytes:
            break
        _prune(row["key"], d)
        used -= max(0, row["disk_bytes"])
        evicted.append(row["key"])
    if evicted:
        evictions += len(evicted)
        _count("evictions", len(evicted))
    return evicted


def _enforce_cap() -> None:
    try:
        gc()
    except Exception:
        pass


def verify(dirname: Optional[str] = None,
           prune: bool = True) -> Tuple[int, List[str]]:
    """CRC-check every entry; optionally prune corrupt/torn ones.
    Returns (ok_count, bad_keys)."""
    d = dirname or _DIR
    ok, bad = 0, []
    for row in entries(d):
        bpath, _ = _paths(row["key"], d)
        try:
            with open(bpath, "rb") as f:
                blob = f.read()
            good = zlib.crc32(blob) & 0xFFFFFFFF == row.get("crc")
        except OSError:
            good = False
        if good:
            ok += 1
        else:
            bad.append(row["key"])
            if prune:
                _prune(row["key"], d)
    return ok, bad


# ---- stats -----------------------------------------------------------------

def stats() -> Dict[str, int]:
    """Process-lifetime cache activity (plain ints — valid with the
    monitor off; `ServingEngine.stats()` embeds this dict)."""
    return {"hits": hits, "misses": misses, "fallbacks": fallbacks,
            "stores": stores, "evictions": evictions,
            "export_skips": export_skips}


def reset_stats() -> None:
    global hits, misses, fallbacks, stores, evictions, export_skips
    hits = misses = fallbacks = stores = evictions = export_skips = 0


def warm_start_report() -> Dict[str, Any]:
    """One-call warm-start verdict for a freshly spawned process: cache
    activity plus the `trace_compile` ledger counter (core/executable.py
    counts every traced build there). `warm` is the autoscaler's
    acceptance bit — the process served with ZERO traced compiles and at
    least one cache hit, i.e. scale-out actually exploited the
    persistent cache instead of paying cold compiles."""
    compiles = 0
    if _monitor._ENABLED:
        compiles = int(
            _monitor.snapshot()["counters"].get("trace_compile", 0))
    s = stats()
    return {"enabled": enabled(), "dir": cache_dir(),
            "trace_compile": compiles,
            "warm": bool(enabled() and compiles == 0 and s["hits"] > 0),
            **s}
