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


def cache_entries(cache_dir):
    """Files under ``cache_dir`` (0 when it does not exist yet) — the
    count whose change across a set of compiles says how many of them
    missed."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _, _, files in os.walk(cache_dir))
