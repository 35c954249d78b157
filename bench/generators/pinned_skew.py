"""Generator `pinned_skew`: one hot value, 0, per join attribute over a
uniform tail.

A copy of the program's generator of the same name, kept here so that the
yardstick does not move when the program's generators change.  It reads the
configuration's `heavy_share` (the heavy relation's rows over the rows of
value 0) and `light_rows`, and each tenant's `heavy`: the relation that
holds each join attribute's heavy hitter.
"""
from __future__ import annotations

from typing import Mapping

import numpy as np

from bench.gen import Relations, join_attributes, relations

KEYS = {"config": {"heavy_share", "light_rows"}, "tenant": {"heavy"}}


def pinned_skew_batch(relations: Relations, n: int, heavy: Mapping[str, str],
                      hh_rows: int, light_rows: int, tail_domain: int,
                      seed) -> dict[str, np.ndarray]:
    """One request's relations, `n` int32 rows each, heavy hitters pinned.

    For every join attribute `a`, relation `heavy[a]` holds `hh_rows` rows of
    value 0 and every other relation holding `a` holds `light_rows` of them,
    on disjoint rows of a relation.  Every other row of a join column takes
    a tail value in 1..tail_domain, each as often as any other, through a
    seeded bijection of the tail; non-join columns cycle.  The seed also
    permutes the rows."""
    join = set(join_attributes(relations))
    rng = np.random.default_rng(seed)
    out = {}
    for name, attrs in relations:
        n_zero = {a: (hh_rows if heavy.get(a) == name else light_rows)
                  for a in attrs if a in join}
        if sum(n_zero.values()) > n:
            raise ValueError(f"{name}: zero blocks exceed n={n}")
        cols, start = [], 0
        for a in attrs:
            if a not in join:
                cols.append(np.arange(n, dtype=np.int64) % max(tail_domain, 1))
                continue
            tail = 1 + rng.permutation(tail_domain)[
                np.arange(n - n_zero[a]) % tail_domain]
            cols.append(np.roll(np.concatenate(
                [np.zeros(n_zero[a], np.int64), tail]), start))
            start += n_zero[a]
        out[name] = np.stack(cols, axis=1)[rng.permutation(n)].astype(np.int32)
    return out


def tenant_data(config: Mapping, tenant: str, rows: int, data_seed
                ) -> dict[str, np.ndarray]:
    """The rows of one request of `tenant` at `rows` per relation."""
    t = config["tenants"][tenant]
    hh = rows // int(config["heavy_share"])
    return pinned_skew_batch(relations(config, tenant), rows, t["heavy"], hh,
                             int(config["light_rows"]), rows - hh, data_seed)
