"""Distributed executor vs numpy reference join — 8 virtual devices."""
import numpy as np
import pytest
import jax

from repro.core import (canonical, plan_no_skew, plan_skew_join,
                        reference_join, running_example, two_way)
from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
from repro.data import skewed_join_dataset

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def _mesh():
    from jax.sharding import AxisType
    return jax.make_mesh((8,), ("cells",), axis_types=(AxisType.Auto,))


def _run(query, data, cfg=None, skew=True, **plan_kw):
    plan = (plan_skew_join if skew else plan_no_skew)(query, data, 8, **plan_kw)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=cfg or ExecutorConfig(out_capacity=65536))
    got = ex.result_rows(data)
    expect = reference_join(query, data)
    np.testing.assert_array_equal(canonical(got), expect)
    return plan, ex


def test_two_way_uniform():
    q = two_way()
    data = skewed_join_dataset(q, 400, 50, seed=1)
    _run(q, data)


def test_two_way_skewed_one_hh():
    q = two_way()
    data = skewed_join_dataset(q, 600, 40, skew={"B": 1.9}, seed=2)
    plan, _ = _run(q, data)
    assert plan.hhs.total() >= 1     # the skew really exercised the HH path


def test_two_way_extreme_skew_all_same_key():
    """Every tuple shares one join value — the pure Example 1.2 regime."""
    q = two_way()
    rng = np.random.default_rng(3)
    data = {
        "R": np.stack([rng.integers(0, 100, 300), np.full(300, 7)], axis=1),
        "S": np.stack([np.full(80, 7), rng.integers(0, 100, 80)], axis=1),
    }
    cfg = ExecutorConfig(out_capacity=300 * 80 + 64)
    plan, ex = _run(q, data, cfg=cfg)
    # The HH residual must dominate the plan and split both sides.
    hh_res = [rp for rp in plan.residuals if not rp.residual.combo.is_ordinary()]
    assert hh_res and hh_res[0].k_i > 1


def test_three_way_running_example():
    q = running_example()
    data = skewed_join_dataset(q, 100, 50, skew={"B": 1.5, "C": 1.2}, seed=4)
    _run(q, data, cfg=ExecutorConfig(out_capacity=32768), max_hh_per_attr=3)


def test_no_skew_plan_also_correct():
    q = two_way()
    data = skewed_join_dataset(q, 500, 64, seed=5)
    _run(q, data, skew=False)


def test_overflow_detection():
    """Tiny capacity must be detected, not silently wrong."""
    q = two_way()
    data = skewed_join_dataset(q, 600, 10, skew={"B": 1.9}, seed=6)
    plan = plan_skew_join(q, data, 8)
    ex = ShardedJoinExecutor(plan, _mesh(), config=ExecutorConfig(out_capacity=4))
    with pytest.raises(RuntimeError, match="capacity overflow"):
        ex.result_rows(data)


def test_shuffle_balance_metric():
    """Received-tuple counts per device are balanced under skew."""
    q = two_way()
    data = skewed_join_dataset(q, 2000, 100, skew={"B": 1.8}, seed=8)
    plan = plan_skew_join(q, data, 8)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=65536))
    res = ex.run(data)
    recv = res["recv_counts"].astype(float)
    used = recv[recv > 0]
    assert used.max() <= 5.0 * max(used.mean(), 1.0)


def test_four_relation_chain_join():
    """Chain query R(A,B) ⋈ S(B,C) ⋈ T(C,D) ⋈ U(D,E) with skew on B and D."""
    from repro.core import JoinQuery, Relation
    q = JoinQuery((Relation("R", ("A", "B")), Relation("S", ("B", "C")),
                   Relation("T", ("C", "D")), Relation("U", ("D", "E"))))
    data = skewed_join_dataset(q, 80, 40, skew={"B": 1.5, "D": 1.4}, seed=9)
    _run(q, data, cfg=ExecutorConfig(out_capacity=32768), max_hh_per_attr=2)


def test_no_heavy_hitters_degenerates_to_plain_shares():
    """Uniform data: the plan must be a single ordinary residual."""
    q = two_way()
    data = skewed_join_dataset(q, 400, 4000, seed=10)   # huge domain, no HH
    plan = plan_skew_join(q, data, 8)
    assert len(plan.residuals) == 1
    assert plan.residuals[0].residual.combo.is_ordinary()
    _run(q, data)


def test_empty_relation():
    q = two_way()
    data = {"R": np.zeros((0, 2), np.int64),
            "S": np.stack([np.arange(50), np.arange(50)], axis=1)}
    plan = plan_skew_join(q, data, 8)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=64))
    rows = ex.result_rows(data)
    assert len(rows) == 0


def _argsort_pack(dest, rows, k, cap):
    """(buf, overflow): bucket contents in arrival order by a stable numpy
    argsort, places past cap and dest < 0 dropped."""
    big = np.where(dest < 0, k, dest)
    order = np.argsort(big, kind="stable")
    sd, sr = big[order], rows[order]
    pos = np.arange(len(sd)) - np.searchsorted(sd, sd, side="left")
    buf = np.full((k, cap, rows.shape[1]), -1, np.int32)
    keep = (sd < k) & (pos < cap)
    buf[sd[keep], pos[keep]] = sr[keep]
    return buf, int(((pos >= cap) & (sd < k)).sum())


def _assert_pack_equal(dest, rows, k, cap):
    """The map's sort assemble (`_scatter_assemble_host`, one copy per row,
    the row's arrival index as its tag) vs the argsort pack."""
    import jax.numpy as jnp
    from repro.kernels.scatter_pack import _scatter_assemble_host
    dest = np.asarray(dest, np.int32)
    rows = np.asarray(rows, np.int32)
    arrival = np.arange(len(dest), dtype=np.int32)
    buf_ref, over_ref = _argsort_pack(
        dest, np.concatenate([rows, arrival[:, None]], axis=1), k, cap)
    buf, over = _scatter_assemble_host(
        jnp.asarray(rows), jnp.asarray(arrival),
        jnp.asarray(np.where(dest < 0, k, dest), jnp.int32), k, cap, 1)
    np.testing.assert_array_equal(np.asarray(buf), buf_ref)
    assert int(over) == over_ref
    return buf_ref[..., :-1], over_ref


@pytest.mark.parametrize("k", [8, 64])          # spans the old pack's k=32
@pytest.mark.parametrize("seed", [0, 1, 2])     # one-hot/argsort dispatch cliff
def test_pack_buckets_matches_argsort_randomized(seed, k):
    rng = np.random.default_rng(seed)
    m, cap, w = 257, 16, 3
    dest = rng.integers(-1, k, size=m)          # includes invalid -1
    rows = rng.integers(0, 1000, size=(m, w))
    _assert_pack_equal(dest, rows, k, cap)


def test_pack_buckets_all_invalid():
    m, k, cap, w = 64, 8, 4, 2
    buf, over = _assert_pack_equal(np.full(m, -1), np.zeros((m, w)), k, cap)
    assert over == 0
    assert (buf == -1).all()


def test_pack_buckets_exact_capacity():
    k, cap, w = 4, 8, 2
    dest = np.repeat(np.arange(k), cap)         # every bucket exactly full
    rows = np.arange(k * cap * w).reshape(-1, w)
    buf, over = _assert_pack_equal(dest, rows, k, cap)
    assert over == 0
    assert (buf != -1).all()


def test_pack_buckets_overflow():
    k, cap = 4, 8
    dest = np.concatenate([np.full(cap + 3, 1), np.full(2, 2)])
    rows = np.arange(len(dest) * 2).reshape(-1, 2)
    buf, over = _assert_pack_equal(dest, rows, k, cap)
    assert over == 3                            # 3 rows beyond bucket 1's cap
    assert (buf[1] == rows[:cap]).all()         # first cap rows kept, in order


@pytest.mark.parametrize("n_dev", [8, 2])       # identity, and folded
def test_route_relation_matches_numpy_router(n_dev):
    """The map (`scatter_pack_host`) vs the plan's numpy routing oracle.

    Compares the multiset of (phys dest, logical cell, row values) routed
    copies in the shuffle buffer — the map sorts copies by device while the
    oracle is route-major, so order is not part of the contract.  Runs
    without a mesh.
    """
    import jax.numpy as jnp
    from repro.core.executor import _build_routes, _route_specs
    from repro.core.placement import lpt_placement
    from repro.kernels.scatter_pack import scatter_pack_host
    q = two_way()
    data = skewed_join_dataset(q, 400, 30, skew={"B": 1.6}, seed=12)
    plan = plan_skew_join(q, data, 8)
    routes = _build_routes(plan)
    place = lpt_placement(np.asarray(plan.cell_loads(data), float), n_dev)
    for rel in q.relations:
        rows = np.asarray(data[rel.name], np.int32)
        buf, over = scatter_pack_host(
            jnp.asarray(rows), jnp.asarray(place.table),
            routes=_route_specs(routes[rel.name]), k=plan.k, n_dev=n_dev,
            cap=len(rows) * plan.k)
        assert int(over) == 0
        buf = np.asarray(buf)
        dev = np.broadcast_to(np.arange(n_dev)[:, None], buf.shape[:2])
        valid = buf[..., -1] >= 0
        tagged, dev = buf[valid], dev[valid]
        # (device, wrapped logical cell, row values) of every copy; the
        # hidden logical-cell tag must agree with the device it reached.
        cell = tagged[:, -1] % plan.k
        assert (place.device_of(cell) == dev).all()
        got = np.concatenate([dev[:, None], cell[:, None], tagged[:, :-1]],
                             axis=1)
        ridx, odest = plan.route_relation(rel.name, rows)
        expect = np.concatenate([place.device_of(odest)[:, None],
                                 odest[:, None], rows[ridx]], axis=1)
        np.testing.assert_array_equal(canonical(got), canonical(expect))


def _assert_local_join_parity(frags, q, caps):
    """Bit-parity of `_local_join` with the dense ground oracle."""
    from repro.core.executor import _local_join, _local_join_dense
    for cap in caps:
        out_d, val_d, ov_d = _local_join_dense(frags, q, cap)
        out, val, ov = _local_join(frags, q, cap)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_d),
                                      err_msg=f"cap={cap}")
        np.testing.assert_array_equal(np.asarray(val), np.asarray(val_d),
                                      err_msg=f"cap={cap}")
        assert int(ov) == int(ov_d), f"cap={cap}"


def _query(shape):
    """A query of each shape the reduce cascades over, in a cascade order in
    which every relation joins the prefix."""
    from repro.core import JoinQuery, Relation
    if shape == "two_way":
        return two_way()
    if shape == "three_way":
        return running_example()
    if shape == "chain4":
        return JoinQuery((Relation("R", ("A", "B")), Relation("S", ("B", "C")),
                          Relation("T", ("C", "D")), Relation("U", ("D", "E"))))
    return JoinQuery((Relation("F", ("A", "B", "C")), Relation("R", ("A", "X")),
                      Relation("S", ("B", "Y")), Relation("T", ("C", "Z"))))


@pytest.mark.parametrize("shape", ["two_way", "three_way", "chain4", "star"])
@pytest.mark.parametrize("seed", [0, 1])
def test_local_join_probes_match_dense(shape, seed):
    """The hash-join cascade is bit-identical to the dense-matrix oracle on
    every query shape, at an overflowing and a slack capacity."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    q = _query(shape)
    frags = {}
    for rel, n in zip(q.relations, (60, 90, 40, 50)):
        w = len(rel.attrs)
        rows = rng.integers(0, 8, size=(n, w + 1)).astype(np.int32)
        rows[:, -1] = rng.integers(0, 3, size=n)          # logical cell ids
        rows[rng.random(n) < 0.25] = -1                   # invalid rows
        frags[rel.name] = jnp.asarray(rows)
    _assert_local_join_parity(frags, q, caps=(16, 4096))  # overflow + slack


def test_local_join_cap1_fragments():
    """Degenerate cap-1 fragments: one row per relation, cap_out down to 1."""
    import jax.numpy as jnp
    q = two_way()
    match = {"R": jnp.asarray([[5, 7, 0]], jnp.int32),
             "S": jnp.asarray([[7, 9, 0]], jnp.int32)}
    nomatch = {"R": jnp.asarray([[5, 7, 0]], jnp.int32),
               "S": jnp.asarray([[8, 9, 0]], jnp.int32)}
    _assert_local_join_parity(match, q, caps=(1, 4))
    _assert_local_join_parity(nomatch, q, caps=(1, 4))


def test_local_join_all_invalid_right():
    """An all-invalid right fragment must produce zero matches (the probe's
    absent-partition masking edge)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    q = two_way()
    frags = {
        "R": jnp.asarray(rng.integers(0, 4, (30, 3)), jnp.int32),
        "S": jnp.asarray(np.full((20, 3), -1), jnp.int32),
    }
    _assert_local_join_parity(frags, q, caps=(8, 128))
    from repro.core.executor import _local_join
    _, valid, over = _local_join(frags, q, 128)
    assert int(np.asarray(valid).sum()) == 0 and int(over) == 0


def test_local_join_all_invalid_accumulator_mid_cascade():
    """Disjoint R/S keys make step 1 emit zero rows; step 2 then joins an
    ALL-INVALID accumulator against a live T fragment — the cascade must
    agree with the dense oracle bit for bit (and emit nothing)."""
    import jax.numpy as jnp
    from repro.core import running_example
    rng = np.random.default_rng(6)
    q = running_example()
    frags = {}
    for rel, n, lo_v in [("R", 25, 0), ("S", 35, 50), ("T", 15, 0)]:
        w = len(q.relation(rel).attrs)
        rows = rng.integers(lo_v, lo_v + 8, size=(n, w + 1)).astype(np.int32)
        rows[:, -1] = 0                                   # one logical cell
        frags[rel] = jnp.asarray(rows)
    _assert_local_join_parity(frags, q, caps=(4, 256))
    from repro.core.executor import _local_join
    _, valid, over = _local_join(frags, q, 256)
    assert int(np.asarray(valid).sum()) == 0 and int(over) == 0


@pytest.mark.parametrize("n_cells", [1, 3])
def test_stable_argsort_locks_arrival_order(n_cells):
    """Regression lock for the stable sorts.

    `_local_join` enumerates right-side matches through the probe's grouped
    permutation; with heavy key duplication an unstable sort would permute
    equal-keyed rows and break bit-identity with the dense oracle's (left
    row, right ARRIVAL order) output, within each logical cell.  Likewise
    the map's sort assemble must keep each bucket's rows in arrival order.
    Runs without a mesh.
    """
    import jax.numpy as jnp
    from repro.core.executor import _local_join, _local_join_dense
    rng = np.random.default_rng(99)
    q = two_way()
    n = 120
    frags = {}
    for rel in ("R", "S"):
        rows = rng.integers(0, 3, size=(n, 3)).astype(np.int32)  # ~40 dups/key
        rows[:, -1] = rng.integers(0, n_cells, size=n)    # logical cell ids
        frags[rel] = jnp.asarray(rows)
    out_s, val_s, ov_s = _local_join(frags, q, 1 << 14)
    out_d, val_d, ov_d = _local_join_dense(frags, q, 1 << 14)
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_d))
    np.testing.assert_array_equal(np.asarray(val_s), np.asarray(val_d))
    assert int(ov_s) == int(ov_d)
    # Sort assemble: rows of one bucket must land in arrival order.
    k, cap = 4, 64
    dest = rng.integers(0, k, size=n)
    rows = np.arange(n * 2, dtype=np.int32).reshape(n, 2)
    buf, _ = _assert_pack_equal(dest, rows, k, cap)
    for b in range(k):                                    # explicit order lock
        want = rows[dest == b][:cap]
        np.testing.assert_array_equal(buf[b][:len(want)], want)


@pytest.mark.parametrize("C", [1, 2, 4, 7])     # 7: ragged remainder tiles
def test_overlap_shuffle_matches_serial_and_reference(C):
    """The chunked map↔all-to-all pipeline must produce exactly the serial
    path's result SET (chunk-major fragment arrival reorders rows, so the
    contract is canonical-multiset, not positional) and the reference join."""
    q = two_way()
    data = skewed_join_dataset(q, 601, 40, skew={"B": 1.9}, seed=21)
    plan = plan_skew_join(q, data, 8)
    expect = reference_join(q, data)
    serial = ShardedJoinExecutor(
        plan, _mesh(), config=ExecutorConfig(out_capacity=65536))
    got_serial = canonical(serial.result_rows(data))
    np.testing.assert_array_equal(got_serial, expect)
    ex = ShardedJoinExecutor(
        plan, _mesh(),
        config=ExecutorConfig(out_capacity=65536, overlap_shuffle=C))
    got = canonical(ex.result_rows(data))
    np.testing.assert_array_equal(got, got_serial)
    assert ex.compile_count == 1


def test_overlap_warm_batches_zero_new_compiles():
    """Chunked sessions stream warm: repeat batches (same shapes) compile
    nothing new, per-chunk caps hold, and results stay reference-exact."""
    q = two_way()
    data = skewed_join_dataset(q, 640, 50, skew={"B": 1.7}, seed=23)
    plan = plan_skew_join(q, data, 8)
    expect = reference_join(q, data)
    for C in (2, 4):
        ex = ShardedJoinExecutor(
            plan, _mesh(),
            config=ExecutorConfig(out_capacity=65536, overlap_shuffle=C))
        ses = ex.session().prepare(data)
        res = ses.run_batch()
        assert ex.compile_count == 1
        for _ in range(3):
            res = ses.run_batch()
        assert ex.compile_count == 1            # zero new compiles when warm
        assert res["shuffle_overflow"].sum() == 0
        np.testing.assert_array_equal(
            canonical(res["rows"][res["valid"]]), expect)


def test_overlap_per_chunk_caps_are_ceil_divided():
    """_derive_caps under overlap: the serial quantized cap ceil-divided by
    C (NOT re-quantized), so total send-buffer rows match the serial plan."""
    q = two_way()
    data = skewed_join_dataset(q, 500, 40, skew={"B": 1.6}, seed=24)
    plan = plan_skew_join(q, data, 8)
    serial = ShardedJoinExecutor(
        plan, _mesh(), config=ExecutorConfig(out_capacity=65536))
    caps_serial = serial.session().prepare(data).caps
    for C in (2, 4, 7):
        ex = ShardedJoinExecutor(
            plan, _mesh(),
            config=ExecutorConfig(out_capacity=65536, overlap_shuffle=C))
        caps = ex.session().prepare(data).caps
        assert caps == {r: -(-c // C) for r, c in caps_serial.items()}


def test_run_batch_result_is_lazy_mapping():
    """run_batch returns a BatchResult Mapping: same six keys and values as
    the old eager dict, materialized on access; session.stats accumulates
    through the lazy pending queue (draining on property access)."""
    from repro.core.executor import BatchResult
    q = two_way()
    data = skewed_join_dataset(q, 400, 40, skew={"B": 1.5}, seed=25)
    plan = plan_skew_join(q, data, 8)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=65536))
    ses = ex.session().prepare(data)
    res = ses.run_batch()
    assert isinstance(res, BatchResult)
    assert set(res) == {"rows", "valid", "shuffle_overflow",
                        "shuffle_overflow_by_rel", "join_overflow",
                        "recv_counts"}
    with pytest.raises(KeyError):
        res["nope"]
    assert res["shuffle_overflow_by_rel"].shape == (8, 2)
    assert res["rows"] is res["rows"]           # cached after first access
    np.testing.assert_array_equal(canonical(res["rows"][res["valid"]]),
                                  reference_join(q, data))
    # Unread batches park their overflow device-side; stats drains on access.
    for _ in range(3):
        ses.run_batch()
    assert len(ses._pending) == 4
    st = ses.stats
    assert st["batches"] == 4
    assert st["shuffle_overflow"].sum() == 0 and not ses._pending
    # Mutation through the property is the live dict (run_with_retry's use).
    ses.stats["retries"] += 1
    assert ses.stats["retries"] == 1


def test_disjoint_domains_empty_output():
    q = two_way()
    rng = np.random.default_rng(11)
    data = {"R": np.stack([rng.integers(0, 50, 100),
                           rng.integers(0, 50, 100)], axis=1),
            "S": np.stack([rng.integers(100, 150, 100),
                           rng.integers(100, 150, 100)], axis=1)}
    plan = plan_skew_join(q, data, 8)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=64))
    assert len(ex.result_rows(data)) == 0
