"""Persistent XLA compilation cache.

Every entry point — CLI runs, the benchmark, chip_smoke.py, the
comparison scripts — compiles the same federated round program, and a
cold compile is a large part of a short run. JAX's persistent cache
keys on (HLO, compile options, platform version, cache path), so a
shared on-disk cache at a FIXED path turns repeat compiles into a load.

The reference has no analog (eager torch does not compile); this is
TPU-runtime scope.
"""
from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set the operator
    placed the cache and no directory is set in code (jax reads the
    variable itself); otherwise the cache is ``<repo>/.jax_cache``,
    fixed — the path is part of the cache key, so a directory that
    moves never hits. Safe to call more than once and before or after
    backend init."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache everything that took noticeable compile time; tiny
    # programs aren't worth the disk round-trip
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # an executable carries the scope names (``fed.*``) and source lines
    # of the program it was compiled from, and a profile shows those.
    # JAX's default key leaves them out, so a program would load what an
    # older one compiled and its trace would read by stale stage names,
    # or by none (seen on the chip: the scope-less parent's trace showed
    # this tree's scopes, and this tree loading the parent's would have
    # read no stage at all). With them in the key a profile is always
    # this program's own; the first run after an edit that moves a line
    # on the round program's call stack, or after a move of the
    # checkout, compiles where the default would load (PERF.md,
    # section 6, has what that costs).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


def jit_cache_size(jitted) -> int | None:
    """Number of compiled executables held by a ``jax.jit``-wrapped
    callable — the compilation-side twin of the trace-event counter in
    ``utils.tracing``: trace events count Python re-entries, this
    counts distinct (shape, dtype, static-arg) specializations that
    survived to an executable.  A hot path that is healthy shows
    exactly 1 of each.  Returns None when jax's private probe is
    unavailable (the sentinel then relies on trace counts alone)."""
    try:
        return int(jitted._cache_size())
    except Exception:
        return None
