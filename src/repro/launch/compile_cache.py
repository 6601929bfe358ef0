"""JAX's persistent compilation cache, placed from outside the program.

A 32-layer model's prefill and decode programs take tens of seconds to compile,
and every fresh process on the chip would compile them again.  JAX keys a
persistent cache entry on the program and its compile options and finds it
again only in the same directory, so the directory must be a fixed path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: repository root (``src/repro/launch/`` is three levels below it)
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and nothing else is set.  Otherwise the cache lives in
    ``<checkout>/.jax_cache``, which ``.gitignore`` lists.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
