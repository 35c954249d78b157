"""Request streams of the benchmark's cells, made from the seed.

A configuration (`configs/<name>.json`) fixes the deployment: its tenants,
each a query with the relation that holds each join attribute's heavy
hitter, and the rows per relation.  A traffic mix (`traffic/<name>.json`)
fixes the pool of requests one closed-loop client sends and which tenant
sends each.  One generator reads both, so a new deployment or pool is a new
data file and no code; a key or value it does not know is refused.

Every seed gets the same work: the same tenants and sizes, in an order the
seed draws, and the seed draws each request's rows.  The rows come from `pinned_skew_batch`,
a copy of the program's generator of the same name, kept here so that the
yardstick does not move when the program's generators change.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Mapping, Sequence

import numpy as np

Relations = Sequence[tuple[str, Sequence[str]]]


def attributes(relations: Relations) -> tuple[str, ...]:
    """Attributes in order of first appearance: the result's columns."""
    seen: dict[str, None] = {}
    for _, attrs in relations:
        for a in attrs:
            seen.setdefault(a, None)
    return tuple(seen)


def join_attributes(relations: Relations) -> tuple[str, ...]:
    """Attributes that two or more relations hold."""
    return tuple(a for a in attributes(relations)
                 if sum(a in attrs for _, attrs in relations) >= 2)


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


@dataclass(frozen=True)
class RequestSpec:
    """One request of a stream: who sends it, how large, and the seed of
    its rows."""
    index: int
    tenant: str
    rows: int
    data_seed: tuple[int, ...]


def _shares(shares: Mapping[str, float], n: int) -> list[str]:
    """Exactly `n` tenant names in the given shares (largest remainder)."""
    names = list(shares)
    total = float(sum(shares.values()))
    want = [shares[t] / total * n for t in names]
    counts = [int(math.floor(w)) for w in want]
    order = sorted(range(len(names)), key=lambda i: counts[i] - want[i])
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return [t for t, c in zip(names, counts) for _ in range(c)]


# Every key a traffic file may hold, by loop.  A key or value the generator
# does not know is an error, never silently ignored.
TRAFFIC_KEYS = {"closed": {"loop", "clients", "pool", "tenant_shares", "why"}}


def check_traffic(traffic: Mapping, config: Mapping) -> None:
    """Refuse a traffic mix that this generator cannot make as written."""
    loop = traffic.get("loop")
    if loop not in TRAFFIC_KEYS:
        raise ValueError(f"unknown loop {loop!r}; the generator makes "
                         f"{sorted(TRAFFIC_KEYS)}")
    unknown = set(traffic) - TRAFFIC_KEYS[loop]
    if unknown:
        raise ValueError(f"a {loop} loop takes no {sorted(unknown)}")
    if traffic.get("clients", 1) != 1:
        raise ValueError("a closed loop has one client")
    pool = traffic.get("pool")
    if not isinstance(pool, int) or pool < 1:
        raise ValueError(f"pool must be a whole number >= 1, not {pool!r}")
    shares = traffic.get("tenant_shares", {})
    if set(shares) - set(config["tenants"]):
        raise ValueError(f"traffic names tenants "
                         f"{sorted(set(shares) - set(config['tenants']))} "
                         f"that the configuration lacks")
    if not isinstance(config["rows"], int) or config["rows"] < 1:
        raise ValueError(f"rows must be a whole number >= 1, not "
                         f"{config['rows']!r}")


def request_specs(config: Mapping, traffic: Mapping, seed: int
                  ) -> list[RequestSpec]:
    """The pool of one run: `pool` requests at the configuration's rows,
    each tenant's in its exact share, in an order drawn from the seed.  The
    one client sends them round robin, each after the last one returned."""
    check_traffic(traffic, config)
    shares = traffic.get("tenant_shares") or {t: 1.0 for t in config["tenants"]}
    rng = np.random.default_rng([seed, 0])
    n = traffic["pool"]
    tenants = list(rng.permutation(_shares(shares, n)))
    return [RequestSpec(i, str(tenants[i]), config["rows"], (seed, 1, i))
            for i in range(n)]


def tenant_data(config: Mapping, tenant: str, rows: int, data_seed
                ) -> dict[str, np.ndarray]:
    """The rows of one request of `tenant` at `rows` per relation."""
    t = config["tenants"][tenant]
    hh = rows // int(config["heavy_share"])
    return pinned_skew_batch(relations(config, tenant), rows, t["heavy"], hh,
                             int(config["light_rows"]), rows - hh, data_seed)


def relations(config: Mapping, tenant: str) -> list[tuple[str, tuple]]:
    """[(relation, attributes)] of a tenant's query, in cascade order."""
    return [(name, tuple(attrs))
            for name, attrs in config["tenants"][tenant]["relations"]]
