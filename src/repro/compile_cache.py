"""JAX's persistent compilation cache, switched on by the entry points.

Compiling the epoch scan takes tens of seconds at deployment shapes; with
the cache on, a second process with the same programs reads them back.
Entry points call ``enable_compile_cache`` from their ``main``; importing
this module changes nothing.
"""

from __future__ import annotations

import os

import jax


def enable_compile_cache(default_dir: str) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and no other
    directory is set here.  Otherwise the cache lives at ``default_dir``,
    which callers give as a fixed path (never one made from a temporary
    name, a process id or the time: a directory that moves never hits).
    Every compile is cached, however short: the many small programs of a
    smoke run add up.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.abspath(default_dir)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
