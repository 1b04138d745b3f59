"""Where JAX keeps its persistent compilation cache.

Every entry point calls :func:`use_compile_cache` before its first
compile, so separate processes on one machine (a build, then the
server that loads what it built) share compiled programs. The cache
key includes the directory, so the directory must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and no other directory is set here),
else the fixed ``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import os

from .env import env_str

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory :func:`use_compile_cache` points JAX at (no JAX
    import: launchers that must stay off the backend can print it)."""
    return env_str("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`
    and return it."""
    if env_str("JAX_COMPILATION_CACHE_DIR"):
        return cache_dir()
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
