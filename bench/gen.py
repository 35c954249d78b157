"""Request streams of the benchmark's cells, made from the seed.

A configuration (`configs/<name>.json`) fixes the deployment: its tenants,
each a query in cascade order, the rows per relation, and the generator
that makes a request's rows, `generators/<generator>.py`, found by name.
A traffic mix (`traffic/<name>.json`) fixes the pool of requests one
closed-loop client sends and which tenant sends each.  So a new deployment
or pool is new data files, and a new kind of data one new generator file;
a key or value that neither this module nor the generator reads is
refused.

Every seed gets the same work: the same tenants and sizes, in an order the
seed draws, and the seed draws each request's rows.

A generator module exposes `KEYS`, `{"config": {...}, "tenant": {...}}`,
the configuration and tenant keys it reads beyond `CONFIG_KEYS` and
`TENANT_KEYS`, and `tenant_data(config, tenant, rows, data_seed)`, which
returns each relation of the tenant's query as an int32 array of shape
(its rows, its width), the same for the same arguments.
"""
from __future__ import annotations

import functools
import importlib.util
import math
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Mapping, Sequence

import numpy as np

Relations = Sequence[tuple[str, Sequence[str]]]

BENCH = Path(__file__).resolve().parent


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


# Every key a configuration and a tenant may hold besides its generator's
# `KEYS`: those this module and the harness read, and the notes that say
# where the deployment comes from and what it assumes.
CONFIG_KEYS = {"name", "source", "deployment", "guarantee", "generator",
               "rehearsal", "tenants", "rows", "k_per_chip", "chips",
               "out_capacity", "reduced", "assumed",
               "output_rows_per_request"}
TENANT_KEYS = {"relations"}


def generator(name: str, bench: Path = BENCH) -> ModuleType:
    """The module `generators/<name>.py`; an unknown name is an error,
    never a default."""
    path = bench / "generators" / f"{name}.py"
    if not isinstance(name, str) or not path.is_file():
        have = sorted(p.stem for p in (bench / "generators").glob("*.py"))
        raise ValueError(f"unknown generator {name!r}; have {have}")
    return load_module(path)


@functools.cache
def load_module(path: Path) -> ModuleType:
    """The Python file at `path` as a module of its own, loaded once: a
    generator or a metric's reader, found by name."""
    name = f"bench_{path.parent.name}_{path.stem}"
    mod_spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def check_config(config: Mapping, bench: Path = BENCH) -> None:
    """Refuse a configuration that names no known generator, or holds a
    configuration, tenant or rehearsal key that neither the common set nor
    its generator's `KEYS` names."""
    keys = generator(config.get("generator"), bench).KEYS
    known = CONFIG_KEYS | set(keys["config"])
    if set(config) - known:
        raise ValueError(f"the configuration's keys "
                         f"{sorted(set(config) - known)} are read by neither "
                         f"the harness nor generator {config['generator']!r}")
    rehearsal = config.get("rehearsal")
    if not isinstance(rehearsal, dict) or set(rehearsal) - known:
        raise ValueError(f"rehearsal must map configuration keys to their "
                         f"values for the CPU rehearsal, not {rehearsal!r}")
    for name, t in config["tenants"].items():
        unknown = set(t) - TENANT_KEYS - set(keys["tenant"])
        if unknown:
            raise ValueError(f"tenant {name!r}: the keys {sorted(unknown)} "
                             f"are read by neither the harness nor generator "
                             f"{config['generator']!r}")


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
    """The rows of one request of `tenant` at `rows`, from the generator
    the configuration names."""
    return generator(config["generator"]).tenant_data(config, tenant, rows,
                                                      data_seed)


def relations(config: Mapping, tenant: str) -> list[tuple[str, tuple]]:
    """[(relation, attributes)] of a tenant's query, in cascade order."""
    return [(name, tuple(attrs))
            for name, attrs in config["tenants"][tenant]["relations"]]
