"""Radix shuffle pack vs the argsort oracle — every path, arbitrary k.

`bucket_pack` has three implementations that must be bit-identical to the
superseded argsort pack (`core.executor._pack_buckets_argsort`, kept solely as
this oracle): the Pallas kernel (interpret mode here, compiled on TPU), its
vectorized-XLA host twin (the non-TPU hot path), and the dead-simple one-hot
jnp reference.  Coverage: k from 1 through 256 (the old pack dispatched to a
full argsort past k = 32 — these sizes straddle that deleted cliff), ragged m
including m = 0, all-invalid destinations, and capacity overflow.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st
from repro.core.executor import _pack_buckets_argsort
from repro.kernels import bucket_pack as bp
from repro.kernels import ops as kops
from repro.kernels.ref import bucket_pack_ref, bucket_rank_ref

KS = (1, 7, 32, 33, 128, 256)


def _all_paths(dest, rows, k, cap):
    """(name, (buf, overflow)) for every bucket_pack implementation."""
    return {
        "kernel": bp.bucket_pack(dest, rows, k=k, cap=cap, interpret=True),
        "host": bp.bucket_pack_host(dest, rows, k=k, cap=cap),
        "ref": bucket_pack_ref(dest, rows, k, cap),
        "ops": kops.bucket_pack(dest, rows, k, cap),
    }


def _assert_matches_oracle(dest, rows, k, cap):
    dest = jnp.asarray(dest, jnp.int32)
    rows = jnp.asarray(rows, jnp.int32)
    buf_o, over_o = _pack_buckets_argsort(dest, rows, k, cap)
    buf_o, over_o = np.asarray(buf_o), int(over_o)
    for name, (buf, over) in _all_paths(dest, rows, k, cap).items():
        np.testing.assert_array_equal(np.asarray(buf), buf_o,
                                      err_msg=f"path={name} k={k}")
        assert int(over) == over_o, f"path={name} k={k}"
    return buf_o, over_o


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("m", [0, 1, 63, 257])          # ragged, off-block
def test_pack_matches_oracle_random(k, m):
    rng = np.random.default_rng(m * 1000 + k)
    dest = rng.integers(-1, k, size=m)                  # includes invalid -1
    rows = rng.integers(0, 10_000, size=(m, 3))
    cap = max(2, (2 * m) // max(k, 1) or 2)
    _assert_matches_oracle(dest, rows, k, cap)


@pytest.mark.parametrize("k", KS)
def test_pack_all_invalid(k):
    m = 70
    buf, over = _assert_matches_oracle(
        np.full(m, -1), np.zeros((m, 2)), k, cap=4)
    assert over == 0
    assert (buf == -1).all()


@pytest.mark.parametrize("k", [7, 33, 256])
def test_pack_overflow_counts_and_keeps_arrival_order(k):
    cap = 5
    dest = np.concatenate([np.full(cap + 4, k - 1), np.full(3, 0)])
    rows = np.arange(len(dest) * 2).reshape(-1, 2)
    buf, over = _assert_matches_oracle(dest, rows, k, cap)
    assert over == 4                                    # 4 rows beyond cap
    assert (buf[k - 1] == rows[:cap]).all()             # first cap, in order
    assert (buf[0][:3] == rows[cap + 4:]).all()         # other bucket intact
    assert (buf[0][3:] == -1).all()


@pytest.mark.parametrize("k", [1, 128])
def test_pack_exact_capacity_no_overflow(k):
    cap = 6
    dest = np.repeat(np.arange(k), cap)
    rows = np.arange(k * cap * 2).reshape(-1, 2)
    buf, over = _assert_matches_oracle(dest, rows, k, cap)
    assert over == 0
    assert (buf != -1).all()


def test_rank_ref_is_stable_prefix_count():
    dest = np.array([2, 0, 2, 2, 1, 0, 5, -1, 2], np.int32)
    rank, hist = bucket_rank_ref(jnp.asarray(dest), 4)
    np.testing.assert_array_equal(np.asarray(rank)[:7], [0, 0, 1, 2, 0, 1, 0])
    np.testing.assert_array_equal(np.asarray(hist), [2, 1, 4, 0])


def test_kernel_rank_matches_ref_across_blocks():
    """Block boundaries must not break the carried histogram."""
    rng = np.random.default_rng(0)
    m, k = 700, 33
    dest = jnp.asarray(rng.integers(-1, k, m), jnp.int32)
    r_ref, h_ref = bucket_rank_ref(dest, k)
    for block in (32, 256, 1024):                       # m < , ≈ , > block
        r_k, h_k = bp.bucket_rank(dest, k=k, block=block, interpret=True)
        r_h, h_h = bp.bucket_rank_host(dest, k=k, block=block)
        valid = np.asarray(dest) >= 0
        np.testing.assert_array_equal(np.asarray(r_k)[valid],
                                      np.asarray(r_ref)[valid])
        np.testing.assert_array_equal(np.asarray(r_h)[valid],
                                      np.asarray(r_ref)[valid])
        np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_ref))
        np.testing.assert_array_equal(np.asarray(h_h), np.asarray(h_ref))


@settings(max_examples=20, deadline=None)
@given(
    st.integers(min_value=0, max_value=400),            # m (ragged, incl. 0)
    st.sampled_from(KS),                                # k
    st.integers(min_value=1, max_value=12),             # cap (forces overflow)
    st.integers(min_value=0, max_value=2**31 - 1),      # seed
)
def test_pack_property_bit_identical_to_argsort(m, k, cap, seed):
    """Property: every path == argsort oracle for arbitrary (m, k, cap)."""
    rng = np.random.default_rng(seed)
    dest = rng.integers(-1, k, size=m)
    rows = rng.integers(0, 2**20, size=(m, 4))
    _assert_matches_oracle(dest, rows, k, cap)
