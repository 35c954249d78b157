"""Public jit'd wrappers for the Pallas kernels.

Each main-path phase of the executor has one implementation per backend,
fixed in `TPU_IMPL`: on TPU either its Pallas kernel or its bit-identical
vectorized-XLA twin, everywhere else the twin.  The kernels of the phases
that run the twin on TPU stay for the interpret-mode tests and for racing
them against their twins; the reason the v5e compiler refuses each is noted
in the table.  The remaining wrappers serve oracle and benchmark paths:
native on TPU, interpret mode elsewhere.  The backend is asked when a phase
is called, never when this module is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import (build_probe, bucket_pack as _bp, hash_partition as _hp,
               join_probe as _jp, map_pack as _mp, route_cells as _rc,
               scatter_pack as _sp, segment_histogram as _sh)

# How each main-path phase runs on TPU.
TPU_IMPL = {
    "map_count": "kernel",
    # The `join_hash` and `build_table` kernels compile, but their histogram
    # table holds at most 2^MAX_BITS buckets, so at millions of right rows
    # each bucket chains dozens of distinct keys and the probe pays one
    # resolution round per link (84 rounds, 79 s for a 4M-row probe on a
    # v5e).  The twin partitions on a 30-bit hash: O(1) rounds.
    "join_probe": "twin",
    # Stores each row with an in-kernel `dynamic_slice`, which the TPU
    # lowering lacks, and keeps the whole (n_dev·cap+1, w+1) shuffle buffer
    # resident in VMEM.
    "scatter_pack": "twin",
    # Contracts int32 one-hots, which Mosaic's matmul refuses, and keeps
    # both join sides resident in VMEM.
    "expand_rows": "twin",
}


def on_tpu() -> bool:
    """True when the default backend compiles Pallas kernels natively."""
    return jax.default_backend() == "tpu"


def native(phase: str) -> bool:
    """Whether `phase` runs its Pallas kernel here (see `TPU_IMPL`)."""
    return TPU_IMPL[phase] == "kernel" and on_tpu()


def hash_partition(keys: jnp.ndarray, seed: int, nbuckets: int,
                   block: int = _hp.DEFAULT_BLOCK):
    """(bucket_ids, histogram) — see kernels/hash_partition.py."""
    return _hp.hash_partition(keys, seed=seed, nbuckets=nbuckets, block=block,
                              interpret=not on_tpu())


def match_counts(probe: jnp.ndarray, build: jnp.ndarray,
                 probe_block: int = build_probe.DEFAULT_PROBE_BLOCK,
                 build_block: int = build_probe.DEFAULT_BUILD_BLOCK):
    """Per-probe match counts — see kernels/build_probe.py."""
    return build_probe.match_counts(probe, build, probe_block=probe_block,
                                    build_block=build_block, interpret=not on_tpu())


def first_match(probe: jnp.ndarray, build: jnp.ndarray,
                probe_block: int = build_probe.DEFAULT_PROBE_BLOCK,
                build_block: int = build_probe.DEFAULT_BUILD_BLOCK):
    """First matching build index per probe (or -1) — see kernels/build_probe.py."""
    return build_probe.first_match(probe, build, probe_block=probe_block,
                                   build_block=build_block, interpret=not on_tpu())


def segment_scan(keys: jnp.ndarray,
                 block: int = build_probe.DEFAULT_SCAN_BLOCK):
    """(seg_ids, run_start) over sorted keys — see kernels/build_probe.py."""
    return build_probe.segment_scan(keys, block=block, interpret=not on_tpu())


def run_lengths(keys: jnp.ndarray,
                block: int = build_probe.DEFAULT_SCAN_BLOCK):
    """(seg_ids, run_start, run_length) — see kernels/build_probe.py."""
    return build_probe.run_lengths(keys, block=block, interpret=not on_tpu())


def segment_histogram(values: jnp.ndarray, n_bins: int,
                      block: int = _sh.DEFAULT_BLOCK):
    """Bounded-domain histogram — see kernels/segment_histogram.py."""
    return _sh.segment_histogram(values, n_bins=n_bins, block=block,
                                 interpret=not on_tpu())


def route_cells(rows, recipe, block: int = _rc.DEFAULT_BLOCK):
    """Fused map-phase routing — see kernels/route_cells.py."""
    return _rc.route_cells(rows, recipe=recipe, block=block,
                           interpret=not on_tpu())


def fold_cells(dest, table, block: int = _rc.DEFAULT_BLOCK):
    """Logical->physical placement lookup — see kernels/route_cells.py."""
    return _rc.fold_cells(dest, table, block=block, interpret=not on_tpu())


def map_pack(rows: jnp.ndarray, routes, ptable: jnp.ndarray, k: int,
             n_dev: int, cap: int):
    """Fused map phase (route -> fold -> pack) — see kernels/map_pack.py.
    `scatter_pack` superseded it on the main path; this is always the
    megakernel's vectorized-XLA twin, as its per-copy 1-D tiles are not the
    multiples of 1024 the TPU needs.  Interpret-mode kernel validation
    lives in the tests."""
    return _mp.map_pack_host(rows, ptable, routes=routes, k=k, n_dev=n_dev,
                             cap=cap)


def scatter_pack(rows: jnp.ndarray, routes, ptable: jnp.ndarray, k: int,
                 n_dev: int, cap: int):
    """Fused map phase with scatter assembly — see kernels/scatter_pack.py."""
    if native("scatter_pack"):
        return _sp.scatter_pack(rows, ptable, routes=routes, k=k,
                                n_dev=n_dev, cap=cap)
    return _sp.scatter_pack_host(rows, ptable, routes=routes, k=k,
                                 n_dev=n_dev, cap=cap)


def expand_rows(left: jnp.ndarray, right: jnp.ndarray, counts: jnp.ndarray,
                lo: jnp.ndarray, perm: jnp.ndarray, cap: int):
    """Prefix-sum expansion of a probe result — see kernels/scatter_pack.py."""
    if native("expand_rows"):
        return _sp.expand_rows(left, right, counts, lo, perm, cap=cap)
    return _sp.expand_rows_host(left, right, counts, lo, perm, cap=cap)


def map_count(rows: jnp.ndarray, routes, k: int, n_src: int):
    """Scatter-free counting mode of the megakernel — see kernels/map_pack.py."""
    if native("map_count"):
        return _mp.map_count(rows, routes=routes, k=k, n_src=n_src)
    return _mp.map_count_host(rows, routes=routes, k=k, n_src=n_src)


def join_hash(keys: jnp.ndarray, valid: jnp.ndarray, n_bits: int):
    """Fused multi-column bucket hash, `join_probe`'s left leg — see
    kernels/join_probe.py."""
    if native("join_probe"):
        return _jp.join_hash(keys, valid, n_bits=n_bits)
    return _jp.join_hash_host(keys, valid, n_bits=n_bits)


def build_table(keys: jnp.ndarray, valid: jnp.ndarray, n_bits: int):
    """Hash + carried-histogram rank in one pass, `join_probe`'s right leg —
    see kernels/join_probe.py."""
    if native("join_probe"):
        return _jp.build_table(keys, valid, n_bits=n_bits)
    return _jp.build_table_host(keys, valid, n_bits=n_bits)


def join_probe(lk: jnp.ndarray, l_valid: jnp.ndarray, rk: jnp.ndarray,
               r_valid: jnp.ndarray, n_bits: int | None = None):
    """Reduce-phase radix hash join (counts, lo, perm) — see
    kernels/join_probe.py."""
    if native("join_probe"):
        return _jp.join_probe(lk, l_valid, rk, r_valid, n_bits=n_bits)
    return _jp.join_probe_host(lk, l_valid, rk, r_valid, n_bits=n_bits)


def bucket_pack(dest: jnp.ndarray, rows: jnp.ndarray, k: int, cap: int):
    """Radix shuffle pack into (k, cap, w) — see kernels/bucket_pack.py.

    Off-TPU this routes to the kernel's vectorized-XLA twin (not interpret
    mode): bit-identical, and the radix formulation is the production hot
    path there too.  Interpret-mode kernel validation lives in the tests.
    """
    if not on_tpu():
        return _bp.bucket_pack_host(dest, rows, k=k, cap=cap)
    return _bp.bucket_pack(dest, rows, k=k, cap=cap)
