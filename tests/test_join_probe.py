"""join_probe radix hash join vs its oracles — every path, forced collisions.

The reduce-phase hash join has three implementations that must agree: the
Pallas kernels (interpret mode here, compiled on TPU), their vectorized-XLA
host twins (the non-TPU hot path, including the packed-word fused build),
and the dead-simple oracles in kernels/ref.py.  The semantic contract is the
expanded match list — per left row, its matching right rows in ARRIVAL order
(`join_probe_ref`) — reproduced through the executor's prefix-sum expansion
gather from (counts, lo, perm).  Coverage: tiny-hash-bits tables where every
partition sees colliding distinct keys (the key-verified chaining path),
duplicates-heavy zipf keys, fanout > 1 match recipes, invalid rows on both
sides, all-invalid sides, and empty left sides.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import join_probe as jp
from repro.kernels import ops as kops
from repro.kernels.ref import (build_table_ref, join_hash_ref, join_probe_ref)


def _zipf_keys(rng, n, w, domain, alpha=1.4):
    """Duplicates-heavy keys: zipf-ranked values make a few keys dominate."""
    ranks = np.arange(1, domain + 1, dtype=np.float64)
    prob = ranks ** (-alpha)
    prob /= prob.sum()
    return rng.choice(domain, size=(n, w), p=prob).astype(np.int32)


def _expand(counts, lo, perm, n_l, n_r, cap):
    """The executor's static-shape expansion gather, as numpy."""
    counts, lo, perm = np.asarray(counts), np.asarray(lo), np.asarray(perm)
    off = np.cumsum(counts) - counts
    n_match = counts.sum()
    t = np.arange(cap)
    li = np.clip(np.searchsorted(off, t, side="right") - 1, 0, max(n_l - 1, 0))
    ri = perm[np.clip(lo[li] + t - off[li], 0, max(n_r - 1, 0))]
    return li, ri, t < n_match


def _all_paths(lk, lv, rk, rv, n_bits):
    lk, rk = jnp.asarray(lk, jnp.int32), jnp.asarray(rk, jnp.int32)
    lv, rv = jnp.asarray(lv), jnp.asarray(rv)
    bits = n_bits or jp.default_bits(rk.shape[0])
    return {
        "kernel": jp.join_probe(lk, lv, rk, rv, n_bits=n_bits,
                                interpret=True),
        "host": jp.join_probe_host(lk, lv, rk, rv, n_bits=n_bits),
        "ref": jp.probe_tables(lk, join_hash_ref(lk, lv, bits), rk,
                               *build_table_ref(rk, rv, bits), bits),
        "ops": kops.join_probe(lk, lv, rk, rv, n_bits),
    }


def _assert_matches_ref(lk, lv, rk, rv, n_bits, cap=None):
    """Every path's expanded (li, ri, valid) equals the dense oracle's."""
    n_l, n_r = len(lk), len(rk)
    cap = cap or max(4, 2 * n_l * max(n_r, 1))
    li_o, ri_o, v_o = (np.asarray(x) for x in join_probe_ref(
        jnp.asarray(lk, jnp.int32), jnp.asarray(lv),
        jnp.asarray(rk, jnp.int32), jnp.asarray(rv), cap))
    for name, (counts, lo, perm) in _all_paths(lk, lv, rk, rv,
                                               n_bits).items():
        assert sorted(np.asarray(perm).tolist()) == list(range(n_r)), \
            f"path={name}: perm is not a permutation"
        li, ri, v = _expand(counts, lo, perm, n_l, n_r, cap)
        np.testing.assert_array_equal(v, v_o, err_msg=f"path={name}")
        np.testing.assert_array_equal(li[v], li_o[v_o], err_msg=f"path={name}")
        np.testing.assert_array_equal(ri[v], ri_o[v_o], err_msg=f"path={name}")
    return int(v_o.sum())


@pytest.mark.parametrize("n_bits", [None, 1, 2, 6])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_probe_matches_dense_oracle_randomized(seed, n_bits):
    """Random keys + invalid rows; n_bits=1 puts EVERY distinct key in one of
    two buckets — deep key-verified chains, still exact."""
    rng = np.random.default_rng(seed)
    n_l, n_r, w = 57, 83, 2
    lk = rng.integers(0, 9, (n_l, w))
    rk = rng.integers(0, 9, (n_r, w))
    lv = rng.random(n_l) > 0.25
    rv = rng.random(n_r) > 0.25
    matches = _assert_matches_ref(lk, lv, rk, rv, n_bits)
    assert matches > 0                          # the recipe really joins


@pytest.mark.parametrize("n_bits", [1, 3, None])
def test_probe_zipf_duplicates_fanout(n_bits):
    """Duplicates-heavy zipf keys: hot keys give fanout >> 1 per left row and
    huge buckets; arrival order within each match list is the contract."""
    rng = np.random.default_rng(7)
    lk = _zipf_keys(rng, 64, 2, 20)
    rk = _zipf_keys(rng, 200, 2, 20)
    lv = np.ones(64, bool)
    rv = np.ones(200, bool)
    matches = _assert_matches_ref(lk, lv, rk, rv, n_bits, cap=1 << 15)
    assert matches > 200                        # genuinely fanout > 1


def test_probe_forced_collisions_distinct_keys():
    """One bucket, all-distinct keys: the chain must peel one key per round
    and still resolve every key exactly (the adversarial tiny-bits case)."""
    n = 37
    lk = np.stack([np.arange(n), np.arange(n)], axis=1)
    rk = np.stack([np.arange(n)[::-1], np.arange(n)[::-1]], axis=1)
    ones = np.ones(n, bool)
    matches = _assert_matches_ref(lk, ones, rk, ones, 1)
    assert matches == n                          # every key matched once


def test_probe_all_invalid_sides():
    rng = np.random.default_rng(3)
    lk = rng.integers(0, 5, (20, 2))
    rk = rng.integers(0, 5, (30, 2))
    ones_l, ones_r = np.ones(20, bool), np.ones(30, bool)
    zeros_l, zeros_r = np.zeros(20, bool), np.zeros(30, bool)
    assert _assert_matches_ref(lk, ones_l, rk, zeros_r, 2) == 0
    assert _assert_matches_ref(lk, zeros_l, rk, ones_r, 2) == 0
    assert _assert_matches_ref(lk, zeros_l, rk, zeros_r, 2) == 0


def test_probe_empty_left():
    rk = np.arange(12).reshape(6, 2)
    counts, lo, perm = jp.join_probe_host(
        jnp.zeros((0, 2), jnp.int32), jnp.zeros((0,), bool),
        jnp.asarray(rk, jnp.int32), jnp.ones(6, bool), n_bits=3)
    assert counts.shape == (0,) and lo.shape == (0,)
    assert sorted(np.asarray(perm).tolist()) == list(range(6))


_SENTINEL = 1 << jp.HOST_BITS            # `join_probe_host`'s invalid hash
_I32MAX = np.iinfo(np.int32).max


def _random_hashes(seed):
    rng = np.random.default_rng(seed)
    rid = np.sort(np.concatenate([rng.integers(0, 40, 150),
                                  np.full(25, _SENTINEL)]))
    return rid, rng.integers(-2, 45, 301)


# (sorted right hashes `rid`, left hashes `hw_l`) as `join_probe_host` sees
# them: invalid right rows carry the sentinel and sort last.
_LOCATE_CASES = {
    "ties_and_runs": ([0, 0, 3, 3, 3, 7, 7, 9], [3, 0, 7, 9, 5, 3, 3, -1, 0]),
    "past_the_end": ([1, 2, 2, 4], [4, 5, 1 << 29, _SENTINEL, _I32MAX, 0]),
    "all_invalid": ([_SENTINEL] * 6, [0, 17, _SENTINEL - 1, _SENTINEL]),
    "sentinel_tail": ([2, 5, 5, _SENTINEL, _SENTINEL],
                      [5, 2, 3, _SENTINEL - 1, 6]),
    "one_left_row": ([1, 4, 4, 8], [4]),
    "one_left_row_missing": ([1, 4, 4, 8], [5]),
    "one_right_row": ([4], [3, 4, 5, 4]),
    "random": _random_hashes(0),
}


@pytest.mark.parametrize("case", sorted(_LOCATE_CASES))
def test_sorted_locate_matches_searchsorted(case):
    """`join_probe_host` locates each left row's partition by a merge sort
    (`sorted_locate`): position and next right hash as `np.searchsorted`
    and an index past the end give them."""
    rid, hw_l = (np.asarray(x, np.int32) for x in _LOCATE_CASES[case])
    for side in ("left", "right"):
        pos, nxt = jp.sorted_locate(jnp.asarray(rid), jnp.asarray(hw_l),
                                    side=side)
        want = np.searchsorted(rid, hw_l, side=side)
        np.testing.assert_array_equal(np.asarray(pos), want, err_msg=side)
        np.testing.assert_array_equal(np.asarray(nxt),
                                      np.append(rid, _I32MAX)[want],
                                      err_msg=side)


@pytest.mark.parametrize("n_bits", [1, 4, 8])
@pytest.mark.parametrize("m", [0, 1, 63, 257])          # ragged, off-block
def test_join_hash_and_build_table_bit_identity(m, n_bits):
    """The kernel legs themselves: bucket ids, stable within-bucket ranks,
    and histograms bit-identical across kernel / host twin / ref."""
    rng = np.random.default_rng(m * 10 + n_bits)
    keys = jnp.asarray(rng.integers(0, 1 << 20, (m, 3)), jnp.int32)
    valid = jnp.asarray(rng.random(m) > 0.2)
    h_ref = np.asarray(join_hash_ref(keys, valid, n_bits))
    for name, h in [
            ("kernel", jp.join_hash(keys, valid, n_bits=n_bits,
                                    interpret=True)),
            ("host", jp.join_hash_host(keys, valid, n_bits=n_bits)),
            ("ops", kops.join_hash(keys, valid, n_bits))]:
        np.testing.assert_array_equal(np.asarray(h), h_ref,
                                      err_msg=f"path={name}")
    b_ref, r_ref, hist_ref = (np.asarray(x) for x in
                              build_table_ref(keys, valid, n_bits))
    for name, (b, r, hist) in [
            ("kernel", jp.build_table(keys, valid, n_bits=n_bits,
                                      interpret=True)),
            ("host", jp.build_table_host(keys, valid, n_bits=n_bits)),
            ("ops", kops.build_table(keys, valid, n_bits))]:
        np.testing.assert_array_equal(np.asarray(b), b_ref,
                                      err_msg=f"path={name}")
        np.testing.assert_array_equal(np.asarray(r), r_ref,
                                      err_msg=f"path={name}")
        np.testing.assert_array_equal(np.asarray(hist), hist_ref,
                                      err_msg=f"path={name}")


@pytest.mark.parametrize("n_bits", [2, 3, 7, 11])
@pytest.mark.parametrize("m", [0, 1, 257])
def test_build_table_multi_pass_bit_identical(m, n_bits):
    """The factored (recursion-on-high-bits) build must be BIT-identical to
    the single-pass one-hot build at every width — forced both ways, below
    and above the SINGLE_PASS_BITS dispatch point."""
    rng = np.random.default_rng(m + n_bits)
    keys = jnp.asarray(rng.integers(0, 1 << 20, (m, 2)), jnp.int32)
    valid = jnp.asarray(rng.random(m) > 0.2)
    single = jp.build_table(keys, valid, n_bits=n_bits, multi_pass=False,
                            interpret=True)
    multi = jp.build_table(keys, valid, n_bits=n_bits, multi_pass=True,
                           interpret=True)
    host = jp.build_table_host(keys, valid, n_bits=n_bits)
    for s, g, h, tag in zip(single, multi, host, ("bucket", "rank", "hist")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(s),
                                      err_msg=f"{tag} n_bits={n_bits}")
        np.testing.assert_array_equal(np.asarray(g), np.asarray(h),
                                      err_msg=f"{tag} n_bits={n_bits} host")


def test_build_table_multi_pass_auto_dispatch_lifts_bucket_cap():
    """n_bits > SINGLE_PASS_BITS auto-dispatches the factored kernel; the
    probe built on it stays exact (the lifted ~2^14-bucket cap in action)."""
    assert jp.SINGLE_PASS_BITS < 14
    rng = np.random.default_rng(5)
    n_l, n_r = 40, 120
    bits = jp.SINGLE_PASS_BITS + 2
    lk = rng.integers(0, 50, (n_l, 2))
    rk = rng.integers(0, 50, (n_r, 2))
    ones_l, ones_r = np.ones(n_l, bool), np.ones(n_r, bool)
    matches = _assert_matches_ref(lk, ones_l, rk, ones_r, bits)
    assert matches > 0


def test_hash_partition_multi_pass_bit_identical():
    """nbuckets past MAX_ONEHOT_BUCKETS takes the factored histogram kernel:
    ids and histogram must match the single-pass formula exactly (including
    the pad-correction on bucket 0)."""
    from repro.kernels import hash_partition as hp
    from repro.kernels.ref import MULT
    rng = np.random.default_rng(9)
    seed = 0x9E3779B1
    for nb in (hp.MAX_ONEHOT_BUCKETS * 2, hp.MAX_ONEHOT_BUCKETS * 4):
        keys = rng.integers(0, 1 << 31, size=1537).astype(np.int32)
        ids, hist = kops.hash_partition(jnp.asarray(keys), seed, nb)
        shift = 32 - (nb.bit_length() - 1)
        want = ((keys.astype(np.uint32) * np.uint32(seed))
                * np.uint32(MULT)) >> np.uint32(shift)
        np.testing.assert_array_equal(np.asarray(ids), want.astype(np.int32))
        np.testing.assert_array_equal(np.asarray(hist),
                                      np.bincount(want, minlength=nb))
        assert int(np.asarray(hist).sum()) == len(keys)  # pad correction


def test_default_bits_table_sizing():
    assert jp.default_bits(8) == 4               # ~2·n buckets
    assert jp.default_bits(16384) == 15
    assert jp.default_bits(1 << 20) == jp.MAX_BITS
    for n in (0, 1, 2, 100):
        assert 1 <= jp.default_bits(n) <= jp.MAX_BITS
