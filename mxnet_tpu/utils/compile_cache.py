"""Where jax's persistent compilation cache lives.

One rule for every entry point that compiles for the device
(``chip_smoke.py``, ``benchmark/chip/run.py``, ``mx.serve``'s warm
pool): ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache, and jax
reads the variable itself — nothing in the program sets a directory
over it.  When it is not set, the cache is ``<checkout>/.jax_cache``.
The path is part of the cache's key, so a directory that moves from run
to run never hits: no caller derives one from a temporary name.
"""
from __future__ import annotations

import contextlib
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def placed_from_outside():
    """True when ``JAX_COMPILATION_CACHE_DIR`` names the cache."""
    return bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))


def place_compile_cache():
    """Give this process its persistent compile cache; returns the
    directory in force."""
    import jax
    if not placed_from_outside():
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def cache_dir_in_force():
    """The persistent cache's directory as jax has it now, or None when
    no cache is placed: where what belongs beside the compiled programs
    (``parallel.TrainStep``'s recomputation plan) is kept."""
    import jax
    return jax.config.jax_compilation_cache_dir or None


@contextlib.contextmanager
def stable_locations():
    """Inside, what jax lowers names for each op the source line that
    made it and none of the Python call stack above that line (names
    and scopes stay as they are).  A Pallas kernel's serialized body
    carries its ops' locations, and jax keeps a kernel's trace from the
    first time it met the kernel, so with ten frames of call stack in
    them one step lowers to other bytes, and misses the persistent
    cache, depending on what the process lowered before it (PR 33: the
    planned step lowered after the step it was planned from, against the
    planned step alone at the next start)."""
    import jax
    name = "jax_traceback_in_locations_limit"
    was = getattr(jax.config, name)
    jax.config.update(name, 1)
    try:
        yield
    finally:
        jax.config.update(name, was)


def cache_entries(cache_dir):
    """Files under ``cache_dir`` (0 when it does not exist yet) — the
    count whose change across a set of compiles says how many of them
    missed."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _, _, files in os.walk(cache_dir))
