"""The entry points of the step's four phases, and of the two Pallas kernels
outside the join engine.

Each phase has one implementation.  `map_count` is the only one with a
platform fork: it runs its Pallas kernel on a TPU and its bit-identical
vectorized-XLA twin everywhere else, decided from `jax.default_backend()`
when it is called, never when this module is imported.  `TPU_IMPL` records
what each phase runs on a TPU.  `hash_partition` and `segment_histogram`
run natively on a TPU and in interpret mode elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import (hash_partition as _hp, join_probe as _jp, map_pack as _mp,
               scatter_pack as _sp, segment_histogram as _sh)

# What each phase of the step runs on a TPU.
TPU_IMPL = {
    "map_count": "kernel",
    "join_probe": "xla",
    "scatter_pack": "xla",
    "expand_rows": "xla",
}


def on_tpu() -> bool:
    """True when the default backend compiles Pallas kernels natively."""
    return jax.default_backend() == "tpu"


def hash_partition(keys: jnp.ndarray, seed: int, nbuckets: int,
                   block: int = _hp.DEFAULT_BLOCK):
    """(bucket_ids, histogram) — see kernels/hash_partition.py."""
    return _hp.hash_partition(keys, seed=seed, nbuckets=nbuckets, block=block,
                              interpret=not on_tpu())


def segment_histogram(values: jnp.ndarray, n_bins: int,
                      block: int = _sh.DEFAULT_BLOCK):
    """Bounded-domain histogram — see kernels/segment_histogram.py."""
    return _sh.segment_histogram(values, n_bins=n_bins, block=block,
                                 interpret=not on_tpu())


def map_count(rows: jnp.ndarray, routes, k: int, n_src: int):
    """The count pass: (n_src, k) routed copies — see kernels/map_pack.py."""
    if on_tpu():
        return _mp.map_count(rows, routes=routes, k=k, n_src=n_src)
    return _mp.map_count_host(rows, routes=routes, k=k, n_src=n_src)


def scatter_pack(rows: jnp.ndarray, routes, ptable: jnp.ndarray, k: int,
                 n_dev: int, cap: int):
    """The map: route, fold, sort assemble — see kernels/scatter_pack.py."""
    return _sp.scatter_pack_host(rows, ptable, routes=routes, k=k,
                                 n_dev=n_dev, cap=cap)


def join_probe(lk: jnp.ndarray, l_valid: jnp.ndarray, rk: jnp.ndarray,
               r_valid: jnp.ndarray):
    """The reduce's probe (counts, lo, perm) — see kernels/join_probe.py."""
    return _jp.join_probe_host(lk, l_valid, rk, r_valid)


def expand_rows(left: jnp.ndarray, right: jnp.ndarray, counts: jnp.ndarray,
                lo: jnp.ndarray, perm: jnp.ndarray, cap: int):
    """The reduce's prefix-sum expansion — see kernels/scatter_pack.py."""
    return _sp.expand_rows_host(left, right, counts, lo, perm, cap=cap)
