"""CI structural-lowering guard (scripts/ci.sh) — the no-gather contract.

The scatter-assemble map phase and the gather-free reduce expansion exist to
keep XLA `gather` ops out of the shuffle-buffer assembly and the prefix-sum
expansion (kernels/scatter_pack.py).  A refactor that quietly reintroduces a
gather — advanced indexing with a traced index array is all it takes — would
pass every bit-exactness test while regressing the lowering this PR's perf
rests on.  This script asserts the contract STRUCTURALLY, by lowering the
actual functions and counting opcodes with `launch.hlo_analysis.count_ops`
(which parses fusion bodies, so a fused gather still counts):

  * `_scatter_assemble_host`  -> zero `gather` ops (the host-twin assemble,
    one stable sort on the destination);
  * `scatter_pack_host`       -> zero `scatter` ops: the sort gives each
    copy its stable rank by position, so neither the rank pass's
    scatter-add nor the row scatter it replaced may come back; teeth:
    `bucket_pack_host`, which still ranks and scatters, counts >= 1;
  * `scatter_pack` interpret  -> zero `gather` ops (the kernel body lowers
    its dynamic stores to dynamic-update-slice, never gather);
  * `expand_rows` interpret   -> zero `gather` ops (one-hot contraction);
  * teeth: the superseded `_assemble_tagged` and the `expand_rows_host`
    indexing twin must BOTH count >= 1 gather on the same inputs — proving
    the counter can see a gather in this very pipeline (a parser that
    returns 0 for everything fails here, not silently);
  * `expand_rows_host`        -> zero `while` ops: it finds each slot's left
    row by a merge sort (`join_probe.sorted_locate`), and a binary search
    (`jnp.searchsorted`'s default) would lower to a loop of gathers, the
    reduce's largest cost on a v5e chip.

Exit 1 on any violation.  Usage:  python scripts/check_hlo.py
"""
from __future__ import annotations

import functools
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "src"))


def _lower_text(fn, *args, **static) -> str:
    import jax
    return (jax.jit(functools.partial(fn, **static)).lower(*args)
            .compile().as_text())


def main() -> int:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.bucket_pack import bucket_pack_host
    from repro.kernels.map_pack import _assemble_tagged
    from repro.kernels.scatter_pack import (_scatter_assemble_host,
                                            expand_rows, expand_rows_host,
                                            scatter_pack, scatter_pack_host)
    from repro.launch.hlo_analysis import count_ops

    rng = np.random.default_rng(17)
    failures: list[str] = []

    def gate(name: str, text: str, want_zero: bool, op: str = "gather"
             ) -> None:
        n = count_ops(text, op)
        ok = (n == 0) if want_zero else (n >= 1)
        print(f"  {name}: {n} {op} ops "
              f"({'want 0' if want_zero else 'teeth, want >= 1'})"
              f"{'' if ok else '  <-- FAIL'}")
        if not ok:
            failures.append(name)

    # --- map-phase assemble: sort vs the superseded gather ---------------
    n, w, fanout, n_dev, cap = 64, 3, 2, 4, 16
    m = n * fanout
    rows = jnp.asarray(rng.integers(0, 99, (n, w)), jnp.int32)
    tag = jnp.asarray(rng.integers(0, 32, (m,)), jnp.int32)
    d = jnp.asarray(rng.integers(0, n_dev, (m,)), jnp.int32)
    rank = jnp.asarray(rng.integers(0, cap, (m,)), jnp.int32)
    hist = jnp.asarray(rng.integers(0, cap, (n_dev,)), jnp.int32)
    gate("sort assemble (_scatter_assemble_host)",
         _lower_text(_scatter_assemble_host, rows, tag, d,
                     n_dev=n_dev, cap=cap, fanout=fanout), want_zero=True)
    gate("old gather assemble (_assemble_tagged)",
         _lower_text(_assemble_tagged, rows, tag, d, rank, hist,
                     n_dev=n_dev, cap=cap, fanout=fanout), want_zero=False)

    # --- map-phase host twin: no scatter; teeth: rank-and-scatter pack ---
    routes = ((((0, 12345, 4, 1),), (0,), 0, (), ()),)
    ptable = jnp.asarray(np.arange(4, dtype=np.int32) % n_dev)
    gate("scatter_pack_host twin",
         _lower_text(scatter_pack_host, rows, ptable, routes=routes, k=4,
                     n_dev=n_dev, cap=cap), want_zero=True, op="scatter")
    gate("rank-and-scatter pack (bucket_pack_host)",
         _lower_text(bucket_pack_host, d, jnp.repeat(rows, fanout, axis=0),
                     k=n_dev, cap=cap),
         want_zero=False, op="scatter")

    # --- map-phase megakernel body (interpret-mode lowering) -------------
    gate("scatter_pack kernel (interpret)",
         _lower_text(scatter_pack, rows, ptable, routes=routes, k=4,
                     n_dev=n_dev, cap=cap, interpret=True), want_zero=True)

    # --- reduce-phase expansion: one-hot kernel vs the indexing twin -----
    n_l, n_r, cap_out = 24, 16, 64
    left = jnp.asarray(rng.integers(0, 9, (n_l, 3)), jnp.int32)
    right = jnp.asarray(rng.integers(0, 9, (n_r, 4)), jnp.int32)
    counts = jnp.asarray(rng.integers(0, 3, (n_l,)), jnp.int32)
    lo = jnp.asarray(rng.integers(0, n_r, (n_l,)), jnp.int32)
    perm = jnp.asarray(rng.permutation(n_r), jnp.int32)
    gate("expand_rows kernel (interpret)",
         _lower_text(expand_rows, left, right, counts, lo, perm,
                     cap=cap_out, interpret=True), want_zero=True)
    host = _lower_text(expand_rows_host, left, right, counts, lo, perm,
                       cap=cap_out)
    gate("expand_rows_host twin", host, want_zero=False)
    gate("expand_rows_host slot search", host, want_zero=True, op="while")

    if failures:
        print(f"HLO GUARD FAILED: {', '.join(failures)}", file=sys.stderr)
        return 1
    print("HLO guard passed: assemble/expansion paths lower with zero "
          "XLA gathers, the map's host twin with zero scatters (and the "
          "counter's teeth bite); the host expansion has no search loop.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
