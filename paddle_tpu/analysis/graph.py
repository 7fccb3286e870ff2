"""tpu-lint level 2: graph analysis over traced jaxprs / static Programs.

Reference parity: the analysis half of the IR-pass framework
(`paddle/fluid/framework/ir/` graph walks; `static/passes.py` mirrors the
rewrite half). The traced jaxpr is the SSA graph here: dead-op liveness,
implicit dtype widenings, host callbacks, and — the headline rule —
collective-ordering verification: extract each rank's/pipeline stage's
STATIC sequence of collectives (op, axis, shape, dtype) and prove the
sequences match, naming the first divergence instead of letting the pod
deadlock at runtime.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence

import numpy as np

from .base import Finding

__all__ = ["iter_eqns", "live_eqn_mask", "dead_eqns",
           "analyze_jaxpr", "analyze_program",
           "CollectiveDesc", "collective_sequence", "verify_collective_order",
           "verify_stage_chain", "verify_stage_assignment"]

# jax primitives that are cross-device collectives: a rank that reaches one
# of these blocks until every peer on the axis reaches the SAME one. The
# names are the installed jax's (`pmean` traces as psum + div, and
# `psum_scatter` as reduce_scatter). Under `check_vma=True` shard_map
# emits psum_invariant / all_gather_invariant for the same wire ops; its
# companions pvary / pbroadcast are replication-accounting markers that
# lower to nothing, so they are deliberately NOT collectives here —
# otherwise the same program would sign differently under check_vma=True
# vs False.
COLLECTIVE_PRIMS = {
    "psum", "psum_invariant", "pmax", "pmin", "ppermute",
    "all_gather", "all_gather_invariant", "all_to_all", "ragged_all_to_all",
    "reduce_scatter", "pgather",
}
_CANONICAL_OP = {"psum_invariant": "psum",
                 "all_gather_invariant": "all_gather"}

# primitives that re-enter the host from inside the compiled program
HOST_CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "callback", "debug_callback",
    "debug_print", "host_callback_call", "outside_call",
}


def _sub_jaxprs(params: Mapping[str, Any]):
    """Jaxprs nested in an eqn's params (cond branches, scan/while bodies,
    pjit/shard_map/remat jaxprs) — `static/passes.py` uses the same shape."""
    for v in params.values():
        for c in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(c, "jaxpr"):          # ClosedJaxpr
                yield c.jaxpr
            elif hasattr(c, "eqns"):         # plain Jaxpr
                yield c


def iter_eqns(jaxpr) -> Iterable:
    """Every eqn in program order, recursing into nested regions (pjit,
    shard_map, scan/while/cond bodies — bodies yield their eqns once)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _as_jaxpr(obj, specs: Optional[Sequence] = None):
    """Normalize callable/Program/(Closed)Jaxpr to a plain Jaxpr."""
    import jax
    if hasattr(obj, "jaxpr"):                     # ClosedJaxpr
        return obj.jaxpr
    if hasattr(obj, "eqns"):                      # plain Jaxpr
        return obj
    if hasattr(obj, "_fn") and hasattr(obj, "_arg_specs"):   # static.Program
        return jax.make_jaxpr(obj._fn)(*obj._arg_specs).jaxpr
    if callable(obj):
        if specs is None:
            raise ValueError("collective/graph analysis of a callable needs "
                             "example args or ShapeDtypeStructs (specs)")
        return jax.make_jaxpr(obj)(*specs).jaxpr
    raise TypeError(f"cannot analyze {type(obj).__name__}")


# ---- liveness (dead-op / unused-var) ---------------------------------------

def live_eqn_mask(jaxpr) -> List[bool]:
    """Per-eqn liveness at this jaxpr level: an eqn is live when any of its
    outputs feeds a live eqn or a program output, or it carries effects
    (donation/io/debug). Nested bodies are treated atomically."""
    live_vars = {id(v) for v in jaxpr.outvars}
    mask = [False] * len(jaxpr.eqns)
    for i in range(len(jaxpr.eqns) - 1, -1, -1):
        eqn = jaxpr.eqns[i]
        effectful = bool(getattr(eqn, "effects", ()))
        if effectful or any(id(v) in live_vars for v in eqn.outvars):
            mask[i] = True
            for v in eqn.invars:
                live_vars.add(id(v))
    return mask


def dead_eqns(jaxpr) -> Iterable:
    """Dead eqns at every nesting level: a locally-dead eqn (value never
    reaches its own jaxpr's outputs) is globally dead no matter how the
    enclosing program uses that jaxpr — so pjit/shard_map/remat wrappers
    (e.g. a to_static capture, which is ONE pjit eqn at top level) are
    descended through. Eqns inside an already-dead region are skipped:
    the region itself is the finding."""
    mask = live_eqn_mask(jaxpr)
    for eqn, live in zip(jaxpr.eqns, mask):
        if not live:
            yield eqn
        else:
            for sub in _sub_jaxprs(eqn.params):
                yield from dead_eqns(sub)


def analyze_jaxpr(jaxpr, path: str = "<program>",
                  func: str = "") -> List[Finding]:
    """dead-op / unused-var / dtype-widen / host-callback over one traced
    program. `jaxpr` may be a Jaxpr, ClosedJaxpr, static.Program, or a
    callable (then pass specs via analyze_program/collective helpers)."""
    jaxpr = _as_jaxpr(jaxpr)
    findings: List[Finding] = []
    mask = live_eqn_mask(jaxpr)

    used = set()
    for eqn, live in zip(jaxpr.eqns, mask):
        if live:
            used.update(id(v) for v in eqn.invars)
    used.update(id(v) for v in jaxpr.outvars)

    for eqn in dead_eqns(jaxpr):
        findings.append(Finding(
            "dead-op",
            f"dead op '{eqn.primitive.name}': its results are never "
            "used by any program output", path=path, func=func))

    for i, v in enumerate(jaxpr.invars):
        if id(v) not in used:
            findings.append(Finding(
                "unused-var",
                f"program input #{i} ({v.aval.str_short()}) is consumed by "
                "no live op", path=path, func=func))

    def _wide(dt) -> bool:
        try:
            d = np.dtype(dt)
        except TypeError:
            return False        # extension dtypes (PRNG keys) are never wide
        return d in (np.dtype("float64"), np.dtype("complex128"))

    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in HOST_CALLBACK_PRIMS:
            findings.append(Finding(
                "host-callback",
                f"host callback '{prim}' inside the compiled program — a "
                "device->host round trip every step", path=path, func=func))
        in_dts = [v.aval.dtype for v in eqn.invars
                  if hasattr(v.aval, "dtype")]
        out_dts = [v.aval.dtype for v in eqn.outvars
                   if hasattr(v.aval, "dtype")]
        if out_dts and any(_wide(d) for d in out_dts) \
                and in_dts and not any(_wide(d) for d in in_dts):
            findings.append(Finding(
                "dtype-widen",
                f"'{prim}' widens {in_dts[0]} -> "
                f"{next(d for d in out_dts if _wide(d))} (float64 is "
                "emulated on TPU)", path=path, func=func))
    return findings


def analyze_program(program, path: Optional[str] = None) -> List[Finding]:
    """Graph rules over a `static.Program` (traces its captured fn)."""
    return analyze_jaxpr(program, path=path or f"<Program {program.name}>",
                         func=program.name)


# ---- collective-ordering verification --------------------------------------

class CollectiveDesc:
    """One collective in a rank's static sequence: what must match across
    peers for the op to complete instead of deadlocking."""

    __slots__ = ("op", "axis", "shape", "dtype", "perm")

    def __init__(self, op: str, axis, shape, dtype, perm=None):
        self.op = op
        self.axis = axis
        self.shape = tuple(shape)
        self.dtype = str(dtype)
        self.perm = tuple(perm) if perm is not None else None

    def __eq__(self, other):
        return isinstance(other, CollectiveDesc) and \
            (self.op, self.axis, self.shape, self.dtype, self.perm) == \
            (other.op, other.axis, other.shape, other.dtype, other.perm)

    def __hash__(self):
        return hash((self.op, self.axis, self.shape, self.dtype, self.perm))

    def __repr__(self):
        shp = ",".join(str(s) for s in self.shape)
        return f"{self.op}(axis={self.axis}, {self.dtype}[{shp}])"


def _axis_of(params: Mapping[str, Any]):
    ax = params.get("axis_name", params.get("axes"))
    if isinstance(ax, (tuple, list)):
        return ax[0] if len(ax) == 1 else tuple(ax)
    return ax


def collective_sequence(obj, *specs) -> List[CollectiveDesc]:
    """The static, ordered collective sequence of a program. `obj` may be a
    (Closed)Jaxpr, static.Program, callable (+ example args/specs), or an
    already-extracted sequence (returned as-is). Collectives inside
    scan/while/cond bodies appear once, in body order — peers trace the
    same structure, so the comparison stays sound."""
    if isinstance(obj, (list, tuple)) and \
            all(isinstance(c, CollectiveDesc) for c in obj):
        return list(obj)
    jaxpr = _as_jaxpr(obj, specs if specs else None)
    seq: List[CollectiveDesc] = []
    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim not in COLLECTIVE_PRIMS:
            continue
        avals = [v.aval for v in eqn.invars if hasattr(v.aval, "shape")]
        shape = avals[0].shape if avals else ()
        dtype = avals[0].dtype if avals else ""
        seq.append(CollectiveDesc(_CANONICAL_OP.get(prim, prim),
                                  _axis_of(eqn.params), shape, dtype,
                                  perm=eqn.params.get("perm")))
    return seq


def verify_collective_order(programs: Mapping[str, Any],
                            specs: Optional[Mapping[str, Sequence]] = None
                            ) -> List[Finding]:
    """Prove every rank's/stage's static collective sequence matches the
    first entry's (the reference rank). Values may be sequences from
    `collective_sequence`, Programs, jaxprs, or callables (give per-name
    example args via `specs`). Returns findings naming the FIRST
    divergence — the exact op the pod would deadlock on."""
    names = list(programs)
    if len(names) < 2:
        return []
    seqs: Dict[str, List[CollectiveDesc]] = {}
    for n in names:
        sp = (specs or {}).get(n, ())
        seqs[n] = collective_sequence(programs[n], *sp)
    ref_name, ref = names[0], seqs[names[0]]
    findings: List[Finding] = []
    for n in names[1:]:
        seq = seqs[n]
        for i, (a, b) in enumerate(zip(ref, seq)):
            if a != b:
                findings.append(Finding(
                    "collective-order",
                    f"{n} diverges from {ref_name} at collective #{i}: "
                    f"{ref_name} issues {a!r}, {n} issues {b!r} — the pod "
                    "deadlocks here at runtime", func=n))
                break
        else:
            if len(ref) != len(seq):
                short, long_ = (n, ref_name) if len(seq) < len(ref) \
                    else (ref_name, n)
                i = min(len(ref), len(seq))
                extra = (ref if len(ref) > len(seq) else seq)[i]
                findings.append(Finding(
                    "collective-order",
                    f"{n} issues {len(seq)} collectives, {ref_name} issues "
                    f"{len(ref)}: {short} never reaches {long_}'s "
                    f"collective #{i} ({extra!r}) — peers block there "
                    "forever", func=n))
    return findings


# ---- pipeline/task-graph verification --------------------------------------

def verify_stage_chain(stages: Sequence, sample) -> List[Finding]:
    """Prove each pipeline stage's output can feed the next stage by
    abstract evaluation (no FLOPs): names the first broken edge instead of
    letting the fleet executor hang mid-drain. `sample` is a stage-0
    example input (array or ShapeDtypeStruct)."""
    import jax

    findings: List[Finding] = []
    x = sample
    for i, stage in enumerate(stages):
        try:
            x = jax.eval_shape(stage, x)
        except Exception as e:
            src = "microbatch input" if i == 0 else f"stage {i - 1} output"
            shp = jax.tree_util.tree_map(
                lambda a: getattr(a, "shape", None), x)
            findings.append(Finding(
                "stage-graph",
                f"stage {i} cannot consume {src} {shp}: "
                f"{type(e).__name__}: {e}", func=f"stage{i}"))
            return findings
    return findings


def verify_stage_assignment(stage_owner: Mapping[int, int], n_stages: int,
                            my_rank: Optional[int] = None,
                            my_stages: Optional[Iterable[int]] = None
                            ) -> List[Finding]:
    """Fleet-executor task-graph ownership check: every stage 0..n-1 must
    have an owner, and a rank must only host stages it owns — a stage with
    no owner is a pipeline that never drains."""
    findings: List[Finding] = []
    for s in range(n_stages):
        if s not in stage_owner:
            findings.append(Finding(
                "stage-graph",
                f"stage {s} has no owning rank: microbatches reaching it "
                "are never consumed", func=f"stage{s}"))
    for s in stage_owner:
        if not (0 <= s < n_stages):
            findings.append(Finding(
                "stage-graph",
                f"stage_owner maps nonexistent stage {s} "
                f"(n_stages={n_stages})", func=f"stage{s}"))
    if my_rank is not None and my_stages is not None:
        for s in my_stages:
            owner = stage_owner.get(s)
            if owner is not None and owner != my_rank:
                findings.append(Finding(
                    "stage-graph",
                    f"rank {my_rank} hosts stage {s} but stage_owner maps "
                    f"it to rank {owner}: both ranks will consume its "
                    "messages", func=f"stage{s}"))
    return findings
