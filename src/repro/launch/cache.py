"""Persistent XLA compilation cache for the launchers and the chip smoke run.

Called from entry points only, never on import or in tests: a full-width
model compiles for tens of seconds, and a fixed cache path lets the next
process on the same checkout reuse the programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/cache.py -> <checkout>/.jax_cache
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to jax, which reads it
    itself; otherwise the cache goes to ``.jax_cache/`` at the root of the
    checkout. The path is fixed because it is part of the cache key.

    The key takes in the programs' metadata too: the named scopes a profile
    reads live there, and JAX's default key, which strips it, would hand a
    program the executable of the same program compiled under other names.
    Source files in the metadata lose their directories, so that the key
    does not change with the checkout's path.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
