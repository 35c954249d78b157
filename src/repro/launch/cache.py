"""JAX's persistent compilation cache at one fixed place.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets no directory.  Otherwise the cache lives in `.jax_cache/` at the
root of the checkout (git-ignored): a fixed path, because the path is part
of the cache's key and a directory that moves never hits.

The key holds each operation's `op_name`, which carries the step's phase
scopes: by default JAX strips that metadata from the key, and a program
whose operations differ from a cached one only in their scopes would load
the cached executable and report its names to a profile.  Source locations
are left out of the metadata, so the key does not depend on where the
checkout lies.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
