"""The request streams: byte-identical for one seed, the same work with
other rows for another seed."""
import hashlib
from collections import Counter

import numpy as np
import pytest

from bench import check, gen
from bench import run as harness

SEED = 2**31 + 12345


def _digest(config, traffic, seed):
    h = hashlib.sha256()
    for s in gen.request_specs(config, traffic, seed):
        h.update(repr(s).encode())
        if s.index < 3:
            for name, arr in gen.tenant_data(config, s.tenant, min(s.rows,
                                                                  4096),
                                             s.data_seed).items():
                h.update(name.encode() + arr.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", ["paper3-batch"])
def test_stream_is_byte_identical_for_one_seed(cell):
    _, _, config, traffic = harness.load_cell(cell)
    assert _digest(config, traffic, SEED) == _digest(config, traffic, SEED)
    assert _digest(config, traffic, SEED) != _digest(config, traffic,
                                                     SEED + 1)


def test_seeds_share_the_work():
    """Another seed draws other rows for the same tenants and sizes, in
    exact shares."""
    _, _, config, traffic = harness.load_cell("paper3-batch")
    traffic = {**traffic, "pool": 7}
    config = {**config, "tenants": {**config["tenants"],
                                    "other": config["tenants"]["paper3"]}}
    shares = {"paper3": 5.0, "other": 2.0}
    a = gen.request_specs(config, {**traffic, "tenant_shares": shares}, 1)
    b = gen.request_specs(config, {**traffic, "tenant_shares": shares}, 2)
    assert len(a) == len(b) == 7
    assert Counter((s.tenant, s.rows) for s in a) == Counter(
        (s.tenant, s.rows) for s in b) == Counter(
        {("paper3", config["rows"]): 5, ("other", config["rows"]): 2})
    assert [s.data_seed for s in a] != [s.data_seed for s in b]


def test_pinned_batch_pins_the_heavy_hitters():
    _, _, config, _ = harness.load_cell("paper3-batch")
    n = 4096
    for tenant, t in config["tenants"].items():
        data = gen.tenant_data(config, tenant, n, (7, 1, 0))
        rels = dict(gen.relations(config, tenant))
        for attr in gen.join_attributes(gen.relations(config, tenant)):
            for name, attrs in rels.items():
                if attr not in attrs:
                    continue
                zeros = int((data[name][:, attrs.index(attr)] == 0).sum())
                want = n // 4 if t["heavy"][attr] == name else 4
                assert zeros == want, (tenant, name, attr)
        assert all(a.dtype == np.int32 and len(a) == n
                   for a in data.values())


# `paper3-batch`'s stream and its first request's reference, as read before
# its generator moved into `generators/`: a change of harness that moves
# either changes what the cell measures.
PAPER3_DIGEST = ("5b45a31af196f34657816a32fc5bf9bd"
                 "1d44774708cf6cdf149708f5b7526a5e")
PAPER3_FIRST = (6291448, (212160149, 1031203454))


def test_paper3_batch_reads_the_same_bytes_and_fingerprints():
    _, _, config, traffic = harness.load_cell("paper3-batch")
    assert _digest(config, traffic, SEED) == PAPER3_DIGEST
    s = gen.request_specs(config, traffic, SEED)[0]
    ref = check.Reference(gen.relations(config, s.tenant),
                          gen.tenant_data(config, s.tenant, s.rows,
                                          s.data_seed),
                          (SEED, 2, s.index))
    assert (ref.n, ref.fingerprints) == PAPER3_FIRST
