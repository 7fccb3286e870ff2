"""Reader `jax_monitoring`: what the harness's `CompileWatch` counted
through `jax.monitoring` during set-up.

args: `field`: "compile_s" (sum of backend compile-or-load seconds),
"cache_hits", "cache_misses", "lowerings" or "backend_compiles".
"""
from __future__ import annotations


def read(evidence, field):
    setup = evidence.get("setup")
    if not setup or field not in setup:
        return None
    return setup[field]
