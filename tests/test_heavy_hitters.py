"""Misra-Gries sketch: the N/m guarantee under updates, batches, and merges.

The classical contract — for every value v,
    true_count(v) - N/m  <=  estimate(v)  <=  true_count(v)
with N the total weight seen — must survive every composition the adaptive
loop performs: per-row `update`, weighted `update_counts` batches, and
arbitrary `merge` trees over shard sketches (`_reduce_counters` carries the
error argument; see its docstring).  Deterministic seeded cases run always;
the hypothesis versions widen the search when hypothesis is installed.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import MisraGries, exact_heavy_hitters, two_way
from repro.core.heavy_hitters import _reduce_counters


def _exact_counts(stream):
    vals, cnts = np.unique(np.asarray(stream), return_counts=True)
    return dict(zip(vals.tolist(), cnts.tolist()))


def _check_guarantee(sk: MisraGries, truth: dict, n: int):
    assert sk.n_seen == n
    assert len(sk.counters) <= sk.m
    for v, c in truth.items():
        est = sk.estimate(v)
        assert est <= c, f"over-count: {v}: {est} > {c}"
        assert est >= c - n / sk.m, f"under-count beyond N/m: {v}"
    for v, c in sk.counters.items():
        assert c > 0
        assert v in truth, f"phantom counter {v}"


# ---------------------------------------------------------------------------
# _reduce_counters: the merge-tie fix.
# ---------------------------------------------------------------------------

def test_reduce_counters_handles_ties_at_cut():
    # 6 counters, 4 of them tied exactly at the (m+1)-th largest value: the
    # single-round reduction `{c : c > cut}` keeps {10, 9} only — fine — but
    # shift the tie so the cut would leave MORE than m survivors and the loop
    # must keep going.
    cs = {i: 5 for i in range(10)}                    # all equal
    out = _reduce_counters(dict(cs), 3)
    assert len(out) <= 3
    cs = {0: 10, 1: 10, 2: 10, 3: 10, 4: 10, 5: 1}
    out = _reduce_counters(dict(cs), 2)
    assert len(out) <= 2


def test_reduce_counters_noop_when_small():
    cs = {1: 5, 2: 3}
    assert _reduce_counters(dict(cs), 4) == cs


def test_merge_never_exceeds_m_on_adversarial_ties():
    # Two sketches whose counter multisets tie everywhere: the pre-fix cut
    # logic could keep > m survivors when counts tie at the cut.
    m = 4
    a, b = MisraGries(m), MisraGries(m)
    for v in range(m):
        a.counters[v] = 7
        b.counters[v + m] = 7          # disjoint values, equal counts
    a.n_seen = b.n_seen = 7 * m
    merged = a.merge(b)
    assert len(merged.counters) <= m
    assert merged.n_seen == 14 * m


# ---------------------------------------------------------------------------
# Deterministic guarantee checks (always run).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,m,domain,n", [(0, 8, 50, 2000),
                                             (1, 16, 10, 500),
                                             (2, 5, 200, 3000)])
def test_update_guarantee_zipf(seed, m, domain, n):
    rng = np.random.default_rng(seed)
    stream = rng.zipf(1.5, size=n) % domain
    sk = MisraGries(m)
    sk.update(stream)
    _check_guarantee(sk, _exact_counts(stream), n)


@pytest.mark.parametrize("seed,m", [(3, 8), (4, 24)])
def test_update_counts_matches_expanded_stream_guarantee(seed, m):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 40, size=30)
    cnts = rng.integers(0, 50, size=30)            # zeros must be skipped
    sk = MisraGries(m)
    sk.update_counts(vals, cnts)
    stream = np.repeat(vals, cnts)
    _check_guarantee(sk, _exact_counts(stream), int(cnts.sum()))


def test_update_counts_ignores_nonpositive():
    sk = MisraGries(4)
    sk.update_counts([1, 2, 3], [5, 0, -7])
    assert sk.n_seen == 5
    assert sk.counters == {1: 5}


@pytest.mark.parametrize("seed,m,shards", [(5, 8, 2), (6, 12, 5), (7, 6, 8)])
def test_merge_tree_guarantee(seed, m, shards):
    """Arbitrary left-deep merge tree over shard sketches keeps the N/m
    guarantee with N the TOTAL weight, and agrees with a single-stream
    sketch up to the (two-sided) guarantee."""
    rng = np.random.default_rng(seed)
    streams = [rng.zipf(1.3, size=int(rng.integers(100, 800))) % 60
               for _ in range(shards)]
    merged = MisraGries(m)
    for s in streams:
        shard = MisraGries(m)
        shard.update(s)
        merged = merged.merge(shard)
    full = np.concatenate(streams)
    truth = _exact_counts(full)
    n = len(full)
    _check_guarantee(merged, truth, n)
    single = MisraGries(m)
    single.update(full)
    for v in set(truth):
        assert abs(merged.estimate(v) - single.estimate(v)) <= n / m


def test_merge_keeps_weaker_guarantee():
    a, b = MisraGries(16), MisraGries(4)
    a.update([1] * 10)
    b.update([2] * 10)
    assert a.merge(b).m == 4


@pytest.mark.parametrize("seed", [8, 9, 10])
def test_no_false_negatives_vs_exact_on_zipf(seed):
    """`heavy_hitters` must contain every exact HH: error < N/m strictly, so
    a value with true count >= frac*N keeps estimate > frac*N - N/m."""
    rng = np.random.default_rng(seed)
    q = two_way()
    k, n = 16, 4000
    col_r = rng.zipf(1.6, size=n) % 100
    col_s = rng.zipf(1.2, size=n) % 100
    data = {"R": np.stack([rng.integers(0, 50, n), col_r], axis=1),
            "S": np.stack([col_s, rng.integers(0, 50, n)], axis=1)}
    exact = exact_heavy_hitters(data, q, k, max_hh_per_attr=10_000)
    m = 4 * k                        # m > k: the candidate floor stays < frac*N
    for col in (col_r, col_s):
        sk = MisraGries(m)
        sk.update(col)
        cand = set(sk.heavy_hitters(n, 1.0 / k))
        truth = {int(v) for v, c in _exact_counts(col).items() if c >= n / k}
        assert truth <= cand, f"false negatives: {truth - cand}"
    # and the per-attr union covers the planner's exact set
    union = set()
    for col in (col_r, col_s):
        sk = MisraGries(m)
        sk.update(col)
        union |= set(sk.heavy_hitters(n, 1.0 / k))
    assert set(exact.values("B")) <= union


def test_certain_heavy_hitters_no_false_positives():
    rng = np.random.default_rng(11)
    stream = rng.zipf(1.5, size=3000) % 40
    sk = MisraGries(6)               # deliberately lossy
    sk.update(stream)
    truth = _exact_counts(stream)
    frac = 1.0 / 8
    for v in sk.certain_heavy_hitters(frac):
        assert truth[v] > frac * len(stream), f"{v} not a true HH"


# ---------------------------------------------------------------------------
# Property versions (run when hypothesis is installed, skip otherwise).
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(data=st.lists(st.integers(0, 30), min_size=1, max_size=500),
       m=st.integers(1, 20))
def test_prop_update_guarantee(data, m):
    sk = MisraGries(m)
    sk.update(data)
    _check_guarantee(sk, _exact_counts(data), len(data))


@settings(max_examples=50, deadline=None)
@given(chunks=st.lists(st.lists(st.integers(0, 20), min_size=0, max_size=80),
                       min_size=1, max_size=6),
       m=st.integers(1, 12))
def test_prop_merge_tree_guarantee(chunks, m):
    merged = MisraGries(m)
    full = []
    for ch in chunks:
        shard = MisraGries(m)
        shard.update(ch)
        merged = merged.merge(shard)
        full.extend(ch)
    if not full:
        assert merged.counters == {}
        return
    _check_guarantee(merged, _exact_counts(full), len(full))
    single = MisraGries(m)
    single.update(full)
    for v in set(full):
        assert abs(merged.estimate(v) - single.estimate(v)) <= len(full) / m


@settings(max_examples=50, deadline=None)
@given(vals=st.lists(st.integers(0, 25), min_size=1, max_size=40),
       m=st.integers(1, 10))
def test_prop_update_counts_guarantee(vals, m):
    cnts = [(v % 7) for v in vals]               # deterministic weights
    sk = MisraGries(m)
    sk.update_counts(vals, cnts)
    stream = np.repeat(vals, cnts)
    if len(stream) == 0:
        assert sk.counters == {} and sk.n_seen == 0
        return
    _check_guarantee(sk, _exact_counts(stream), len(stream))
