"""The map's sort assemble vs an argsort pack — arbitrary bucket counts.

`_scatter_assemble_host` builds the shuffle buffer with one stable sort on
the destination; it must be bit-identical to the plain argsort pack written
here (bucket contents in arrival order, the first `cap` of each kept), and
to the one-hot reference `bucket_pack_ref`.  Each row is one copy
(fanout 1) whose tag is its own arrival index, appended as the buffer's last
column.  Coverage: bucket counts from 1 through 256, ragged m including
m = 0, all-invalid destinations, and capacity overflow.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st
from repro.kernels.ref import bucket_pack_ref, bucket_rank_ref
from repro.kernels.scatter_pack import _scatter_assemble_host

KS = (1, 7, 32, 33, 128, 256)


def argsort_pack(dest, rows, k, cap):
    """(buf (k, cap, w), overflow): a stable argsort of the destinations,
    each row's place in its bucket its position after the bucket start;
    dest < 0 and places past cap are dropped."""
    dest, rows = np.asarray(dest), np.asarray(rows)
    big = np.where(dest < 0, k, dest)
    order = np.argsort(big, kind="stable")
    sd, sr = big[order], rows[order]
    pos = np.arange(len(sd)) - np.searchsorted(sd, sd, side="left")
    buf = np.full((k, cap, rows.shape[1]), -1, np.int32)
    keep = (sd < k) & (pos < cap)
    buf[sd[keep], pos[keep]] = sr[keep]
    return buf, int(((pos >= cap) & (sd < k)).sum())


def assert_matches_oracle(dest, rows, k, cap):
    """The sort assemble and the one-hot ref vs the argsort pack."""
    dest = np.asarray(dest, np.int32)
    rows = np.asarray(rows, np.int32)
    tagged = np.concatenate(
        [rows, np.arange(len(dest), dtype=np.int32)[:, None]], axis=1)
    buf_o, over_o = argsort_pack(dest, tagged, k, cap)
    d = jnp.asarray(np.where(dest < 0, k, dest), jnp.int32)
    paths = {
        "sort": _scatter_assemble_host(jnp.asarray(rows), jnp.arange(
            len(dest), dtype=jnp.int32), d, k, cap, 1),
        "ref": bucket_pack_ref(jnp.asarray(dest), jnp.asarray(tagged), k,
                               cap),
    }
    for name, (buf, over) in paths.items():
        np.testing.assert_array_equal(np.asarray(buf), buf_o,
                                      err_msg=f"path={name} k={k}")
        assert int(over) == over_o, f"path={name} k={k}"
    return buf_o[..., :-1], over_o


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("m", [0, 1, 63, 257])          # ragged, off-block
def test_pack_matches_oracle_random(k, m):
    rng = np.random.default_rng(m * 1000 + k)
    dest = rng.integers(-1, k, size=m)                  # includes invalid -1
    rows = rng.integers(0, 10_000, size=(m, 3))
    cap = max(2, (2 * m) // max(k, 1) or 2)
    assert_matches_oracle(dest, rows, k, cap)


@pytest.mark.parametrize("k", KS)
def test_pack_all_invalid(k):
    m = 70
    buf, over = assert_matches_oracle(
        np.full(m, -1), np.zeros((m, 2)), k, cap=4)
    assert over == 0
    assert (buf == -1).all()


@pytest.mark.parametrize("k", [7, 33, 256])
def test_pack_overflow_counts_and_keeps_arrival_order(k):
    cap = 5
    dest = np.concatenate([np.full(cap + 4, k - 1), np.full(3, 0)])
    rows = np.arange(len(dest) * 2).reshape(-1, 2)
    buf, over = assert_matches_oracle(dest, rows, k, cap)
    assert over == 4                                    # 4 rows beyond cap
    assert (buf[k - 1] == rows[:cap]).all()             # first cap, in order
    assert (buf[0][:3] == rows[cap + 4:]).all()         # other bucket intact
    assert (buf[0][3:] == -1).all()


@pytest.mark.parametrize("k", [1, 128])
def test_pack_exact_capacity_no_overflow(k):
    cap = 6
    dest = np.repeat(np.arange(k), cap)
    rows = np.arange(k * cap * 2).reshape(-1, 2)
    buf, over = assert_matches_oracle(dest, rows, k, cap)
    assert over == 0
    assert (buf != -1).all()


def test_rank_ref_is_stable_prefix_count():
    dest = np.array([2, 0, 2, 2, 1, 0, 5, -1, 2], np.int32)
    rank, hist = bucket_rank_ref(jnp.asarray(dest), 4)
    np.testing.assert_array_equal(np.asarray(rank)[:7], [0, 0, 1, 2, 0, 1, 0])
    np.testing.assert_array_equal(np.asarray(hist), [2, 1, 4, 0])


@settings(max_examples=20, deadline=None)
@given(
    st.integers(min_value=0, max_value=400),            # m (ragged, incl. 0)
    st.sampled_from(KS),                                # k
    st.integers(min_value=1, max_value=12),             # cap (forces overflow)
    st.integers(min_value=0, max_value=2**31 - 1),      # seed
)
def test_pack_property_bit_identical_to_argsort(m, k, cap, seed):
    """Property: the sort assemble == argsort pack for any (m, k, cap)."""
    rng = np.random.default_rng(seed)
    dest = rng.integers(-1, k, size=m)
    rows = rng.integers(0, 2**20, size=(m, 4))
    assert_matches_oracle(dest, rows, k, cap)
