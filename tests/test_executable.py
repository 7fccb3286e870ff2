"""The shared executable substrate (paddle_tpu.core.executable).

Acceptance properties (ISSUE 11): ONE ledger implementation carries the
signature cache + retrace accounting + LRU executable cache for all four
dispatch regimes (grep-enforced: no private copies remain anywhere else
in the package); `booking()` books trace_compile/device_compute wall
time exactly once even when dispatches nest (the double-accounting
seam), while monitor compile counters still fire when nested; `acquire`
degrades to the fresh jitted callable on every failure path and serves
bit-identical executables from disk on a warm key.
"""
import os
import re

import pytest

from paddle_tpu import monitor, obs
from paddle_tpu.core import compile_cache as cc
from paddle_tpu.core import executable as exe
from paddle_tpu.core import flags as _flags

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "paddle_tpu")


@pytest.fixture
def with_monitor():
    _flags.set_flags({"monitor": True})
    monitor.reset()
    yield
    monitor.reset()
    _flags.set_flags({"monitor": False})


@pytest.fixture
def with_timeline():
    _flags.set_flags({"obs_timeline": True})
    obs.reset()
    yield
    _flags.set_flags({"obs_timeline": False})
    obs.reset()


# ---- ledger -----------------------------------------------------------------

class TestLedger:
    def test_note_novelty_and_first(self, with_monitor):
        led = exe.ExecutableLedger("unit")
        assert led.note(("a",)) is True          # first trace
        assert led.note(("a",)) is False         # steady state
        assert led.note(("b",)) is True          # retrace
        c = monitor.snapshot()["counters"]
        assert c.get("jit.unit.traces") == 1
        assert c.get("jit.unit.retraces") == 1
        assert led.seen(("a",)) and led.seen(("b",))
        assert led.seen_sigs() == {("a",), ("b",)}

    def test_note_retrace_false_skips_counters(self, with_monitor):
        led = exe.ExecutableLedger("unit")
        assert led.note("s", retrace=False) is True
        c = monitor.snapshot()["counters"]
        assert "jit.unit.traces" not in c

    def test_lru_cap_evicts_oldest_with_hook(self):
        evicted = []
        led = exe.ExecutableLedger("unit", cap=2,
                                   on_evict=lambda s, v: evicted.append(s))
        led.put("a", 1)
        led.put("b", 2)
        assert led.get("a") == 1                 # touch: a is now MRU
        led.put("c", 3)
        assert evicted == ["b"] and led.evictions == 1
        assert "b" not in led and led.keys() == ["a", "c"]

    def test_set_cap_shrinks_immediately(self):
        led = exe.ExecutableLedger("unit", cap=4)
        for i in range(4):
            led.put(i, i)
        led.set_cap(1)
        assert len(led) == 1 and led.evictions == 3

    def test_clear_and_current_sig(self):
        led = exe.ExecutableLedger("unit")
        led.note("s")
        led.put("s", 1)
        led.current_sig = "s"
        led.clear()
        assert len(led) == 0 and not led.seen("s")
        assert led.current_sig is None

    def test_no_private_signature_caches_remain(self):
        """Grep gate for the refactor: the four private implementations
        (`_seen_sigs`, `_prog_sig`, `_SEG_CACHE`, `_dispatched_sigs`)
        must not reappear anywhere in the package — the substrate is the
        only home for this plumbing. Comments/docstrings may mention the
        history; code may not."""
        pat = re.compile(r"_seen_sigs|_prog_sig\b|_SEG_CACHE"
                         r"|_dispatched_sigs")
        offenders = []
        for root, _dirs, files in os.walk(PKG):
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(root, name)
                if path.endswith(os.path.join("core", "executable.py")):
                    continue   # its docstring documents the replacement
                with open(path) as f:
                    for lineno, line in enumerate(f, 1):
                        code = line.split("#", 1)[0]
                        if pat.search(code):
                            offenders.append(f"{path}:{lineno}")
        assert not offenders, \
            f"private signature caches resurfaced: {offenders}"


# ---- booking ----------------------------------------------------------------

class TestBooking:
    def test_compiled_renames_phase_and_counts(self, with_monitor,
                                               with_timeline):
        tl = obs.timeline()
        with tl.step_record():
            with exe.booking("unit") as bk:
                bk.compiled()
        rec = tl.records()[-1]
        assert "trace_compile" in rec["phases"]
        assert "device_compute" not in rec["phases"]
        c = monitor.snapshot()["counters"]
        assert c.get("trace_compile") == 1
        assert c.get("trace_compile.unit") == 1

    def test_steady_state_books_device_compute(self, with_timeline):
        tl = obs.timeline()
        with tl.step_record():
            with exe.booking("unit"):
                pass
        assert "device_compute" in tl.records()[-1]["phases"]

    def test_nested_booking_books_wall_time_once(self, with_monitor,
                                                 with_timeline):
        """THE double-accounting regression: a dispatch nested inside an
        already-open phase (lazy flush inside a step, to_static inside a
        serving booking) must NOT book the same wall seconds twice —
        phase-sum would exceed wall. Compile COUNTERS still fire for the
        nested dispatch; only the wall attribution is suppressed."""
        tl = obs.timeline()
        with tl.step_record():
            with exe.booking("outer") as b1:
                with exe.booking("inner") as b2:
                    b2.compiled()
                assert b2._ctx is None           # suppressed: no phase
                assert b1._ctx is not None
        rec = tl.records()[-1]
        assert sum(rec["phases"].values()) <= rec["wall"] * 1.02
        # outer did not claim the compile: its phase stays compute
        assert "device_compute" in rec["phases"]
        c = monitor.snapshot()["counters"]
        assert c.get("trace_compile.inner") == 1  # counter still fired

    def test_booking_is_inert_with_timeline_off(self, with_monitor):
        with exe.booking("unit") as bk:
            bk.compiled()
        assert bk._ctx is None
        assert monitor.snapshot()["counters"].get("trace_compile") == 1


# ---- acquire ----------------------------------------------------------------

class TestAcquire:
    def test_cache_off_is_passthrough(self):
        import jax.numpy as jnp
        import jax
        f = jax.jit(lambda a: a * 2.0)
        call, source = exe.acquire("unit", f, (jnp.ones((4,)),))
        assert call is f and source == "fresh"

    def test_fresh_store_then_disk_hit(self, tmp_path):
        import jax
        import jax.numpy as jnp
        import numpy as np
        _flags.set_flags({"compile_cache_dir": str(tmp_path / "cc")})
        cc.reset_stats()
        try:
            f = jax.jit(lambda a: a * 3.0 + 1.0)
            args = (jnp.ones((4,)),)
            call1, src1 = exe.acquire("unit", f, args)
            assert src1 == "fresh" and cc.stores == 1 and cc.misses == 1
            call2, src2 = exe.acquire("unit", f, args)
            assert src2 == "disk" and cc.hits == 1
            np.testing.assert_array_equal(np.asarray(call1(*args)),
                                          np.asarray(call2(*args)))
        finally:
            _flags.set_flags({"compile_cache_dir": ""})
            cc.reset_stats()

    def test_unserializable_program_degrades_to_fresh(self, tmp_path):
        import jax
        import jax.numpy as jnp
        _flags.set_flags({"compile_cache_dir": str(tmp_path / "cc")})
        cc.reset_stats()
        try:
            # a host callback cannot ride jax.export (typed PRNG keys,
            # this test's old subject, have since jax 0.9): acquire must
            # skip persistence and hand back the working fresh callable
            import numpy as np
            f = jax.jit(lambda a: jax.pure_callback(
                np.sin, jax.ShapeDtypeStruct(a.shape, a.dtype), a))
            args = (jnp.ones((3,)),)
            call, source = exe.acquire("unit", f, args)
            assert source == "fresh"
            assert call(*args).shape == (3,)
            assert cc.export_skips >= 1 and cc.fallbacks == 0
        finally:
            _flags.set_flags({"compile_cache_dir": ""})
            cc.reset_stats()


# ---- where JAX's own persistent cache goes ---------------------------------

class TestJaxCachePlacement:
    """`FLAGS_compile_cache_dir` places the export-blob store; JAX's own
    compilation cache follows ONE rule (compile_cache.place_jax_cache):
    the directory `JAX_COMPILATION_CACHE_DIR` names, else one fixed path
    in the checkout — and nothing ever unsets it."""

    @pytest.fixture(autouse=True)
    def _restore_jax_config(self):
        import jax
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_dir_survives_setting_and_clearing_the_flag(
            self, tmp_path, monkeypatch):
        import jax
        given = str(tmp_path / "given")
        monkeypatch.setenv(cc.JAX_CACHE_ENV, given)
        jax.config.update("jax_compilation_cache_dir", given)  # as at import
        _flags.set_flags({"compile_cache_dir": str(tmp_path / "blobs")})
        try:
            assert jax.config.jax_compilation_cache_dir == given
        finally:
            _flags.set_flags({"compile_cache_dir": ""})
        assert jax.config.jax_compilation_cache_dir == given
        assert not (tmp_path / "blobs" / "xla").exists()

    def test_env_set_after_import_is_still_honoured(self, tmp_path,
                                                    monkeypatch):
        import jax
        given = str(tmp_path / "late")
        monkeypatch.setenv(cc.JAX_CACHE_ENV, given)
        assert cc.place_jax_cache() == given
        assert jax.config.jax_compilation_cache_dir == given

    def test_without_env_the_fixed_checkout_path(self, tmp_path,
                                                 monkeypatch):
        import jax
        monkeypatch.delenv(cc.JAX_CACHE_ENV, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.JAX_CACHE_DEFAULT == os.path.join(repo, ".jax_cache")
        _flags.set_flags({"compile_cache_dir": str(tmp_path / "blobs")})
        try:
            assert jax.config.jax_compilation_cache_dir == \
                cc.JAX_CACHE_DEFAULT
        finally:
            _flags.set_flags({"compile_cache_dir": ""})
        # clearing the flag turns the blob store off, not JAX's cache
        assert not cc.enabled()
        assert jax.config.jax_compilation_cache_dir == cc.JAX_CACHE_DEFAULT

    def test_no_cache_path_is_made_from_a_pid_a_time_or_a_temp_name(self):
        import inspect
        src = inspect.getsource(cc.place_jax_cache)
        for word in ("mkdtemp", "getpid", "time.", "tempfile"):
            assert word not in src
