"""Seeded RNG management.

Reference parity: `paddle/fluid/framework/generator.cc` / `phi/core/generator.h`
(global + per-device Philox generators, `paddle.seed`). TPU-first design: a
stateful key-splitting `Generator` over `jax.random` (threefry/rbg), so eager
ops draw fresh keys while jitted programs take keys as explicit inputs.
"""
from __future__ import annotations

import functools
import threading

import jax


class TraceKeyError(RuntimeError):
    """A stateful key draw was attempted inside a jax trace with no trace
    key pushed. Mutating the global generator under a trace would leak a
    tracer into host state; callers must hoist `next_key()` out of traced
    fns (or push a trace key). The eager dispatch cache treats this as a
    bailout signal and reruns the op uncached (core/autograd.py)."""


class Generator:
    """Stateful wrapper over a jax PRNG key; `next_key()` splits off fresh keys."""

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        with getattr(self, "_lock", threading.Lock()):
            self._seed = int(seed)
            # Key creation is LAZY: materializing a PRNG key initializes the
            # XLA backend, which must not happen at import time (it would
            # forbid a later jax.distributed.initialize in multi-process
            # bring-up).
            self._key = None
            self._count = 0
            self._pool = []
        return self

    def initial_seed(self) -> int:
        return self._seed

    _POOL = 16

    @staticmethod
    @functools.lru_cache(maxsize=1)
    def _refill_fn(n):
        # ONE jitted executable producing n sequential split(k, 2) draws —
        # bitwise the same stream as n individual next_key calls (the
        # chain advances split[0], hands out split[1]), amortizing the
        # per-draw device dispatch to 1/n
        def chain(k):
            def body(c, _):
                c2, out = jax.random.split(c)
                return c2, out
            return jax.lax.scan(body, k, None, length=n)
        return jax.jit(chain)

    def _refill(self):
        cur = self._key if self._key is not None \
            else jax.random.key(self._seed)
        new_key, pool = Generator._refill_fn(self._POOL)(cur)
        if isinstance(new_key, jax.core.Tracer):
            # a jit trace would capture the split and leak a tracer
            # into host state (note: nothing is committed before this
            # raise — a lazily-created key may itself be a tracer);
            # vjp-linearize replays (recompute) keep concrete keys
            # concrete and pass through here
            raise TraceKeyError(
                "Generator.next_key() called inside a jax trace — draw "
                "the key before tracing (or push a trace key for replay)")
        self._key = new_key
        self._pool = list(pool)

    @staticmethod
    def _trace_mode() -> str:
        """"clean" (no trace: pool OK), "staging" (jit/pjit: must raise),
        or "unknown" (linearize/other/probe failure: fall back to the
        pre-pool BEHAVIORAL path — split once and inspect the result — so
        a jax upgrade that moves `jax.core.trace_ctx` degrades to the old
        per-draw safety, never to silently baking a key constant)."""
        try:
            ctx = jax.core.trace_ctx
            if ctx.is_top_level():
                return "clean"
            if type(ctx.trace).__name__ == "DynamicJaxprTrace":
                return "staging"
        except AttributeError:
            pass
        return "unknown"

    def next_key(self, n: int = 1):
        # keys are drawn from a small pre-split POOL: one device-side
        # split serves 16 draws. A per-draw split costs one dispatch —
        # with two captured static programs per eager step that was ~20%
        # of the whole step on the old remote device (r4; not re-measured
        # on the local chip). get_state snapshots the pool so restore
        # stays EXACT.
        mode = self._trace_mode()
        if mode == "staging":
            # the pre-pool code raised on EVERY staged-trace draw (the
            # split produced a tracer); a warm pool must not weaken that
            # to a 1-in-16 intermittent — a concrete key baked into a
            # traced program would replay the same randomness every call
            raise TraceKeyError(
                "Generator.next_key() called inside a jax trace — draw "
                "the key before tracing (or push a trace key for replay)")
        if mode == "unknown":
            # behavioral pre-pool path: per-draw split whose RESULT tells
            # us whether this trace stages (tracer -> raise) or replays
            # concretely (linearize recompute -> serve). The pool stream
            # is preserved: these draws consume pool slots first.
            with self._lock:
                keys = []
                for _ in range(n):
                    if self._pool:
                        keys.append(self._pool.pop(0))
                        continue
                    cur = self._key if self._key is not None \
                        else jax.random.key(self._seed)
                    new_key, k = jax.random.split(cur)
                    if isinstance(new_key, jax.core.Tracer):
                        raise TraceKeyError(
                            "Generator.next_key() called inside a jax "
                            "trace — draw the key before tracing (or push "
                            "a trace key for replay)")
                    self._key = new_key
                    keys.append(k)
                self._count += n
            return keys[0] if n == 1 else keys
        with self._lock:
            keys = []
            for _ in range(n):
                if not self._pool:
                    self._refill()
                keys.append(self._pool.pop(0))
            self._count += n
        return keys[0] if n == 1 else keys

    def get_state(self):
        """(seed, count, raw key data, pooled key data) — the raw key +
        remaining pool make restore EXACT: replaying `count` draws can't
        reproduce a stream whose draws had mixed granularity
        (split(k, n+1) != n sequential split(k, 2))."""
        import numpy as np
        with self._lock:  # consistent (count, key, pool) snapshot
            kd = None if self._key is None else \
                np.asarray(jax.random.key_data(self._key))
            pool = tuple(np.asarray(jax.random.key_data(k))
                         for k in getattr(self, "_pool", ()))
            return (self._seed, self._count, kd, pool)

    def set_state(self, state):
        if len(state) == 2:  # legacy (seed, count) form: replay draws
            seed, count = state
            self.manual_seed(seed)
            if count:
                self.next_key(count)
            return
        seed, count, kd = state[0], state[1], state[2]
        pool = state[3] if len(state) > 3 else ()
        with self._lock:
            self._seed = int(seed)
            self._count = int(count)
            self._key = None if kd is None else \
                jax.random.wrap_key_data(jax.numpy.asarray(kd))
            self._pool = [jax.random.wrap_key_data(jax.numpy.asarray(p))
                          for p in pool]


_DEFAULT = Generator(0)

# Trace-time key stack: when a jitted/static program is being traced,
# `jit` pushes a traced key here so stateful eager RNG entry points
# (dropout etc.) split from the *traced* key instead of baking a constant.
_TRACE_KEYS = []


def push_trace_key(key):
    _TRACE_KEYS.append(key)


def pop_trace_key():
    return _TRACE_KEYS.pop()


def in_trace() -> bool:
    return bool(_TRACE_KEYS)


def seed(s: int) -> Generator:
    """paddle.seed parity: reseed the global generator."""
    _DEFAULT.manual_seed(s)
    return _DEFAULT


def default_generator() -> Generator:
    return _DEFAULT


def next_key(n: int = 1):
    if _TRACE_KEYS:
        import jax
        k = _TRACE_KEYS[-1]
        _TRACE_KEYS[-1], *keys = jax.random.split(k, n + 1)
        return keys[0] if n == 1 else keys
    return _DEFAULT.next_key(n)


def get_rng_state():
    return _DEFAULT.get_state()


def set_rng_state(state):
    _DEFAULT.set_state(state)
