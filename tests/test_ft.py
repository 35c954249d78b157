"""Fault tolerance: checkpoint round-trip w/ resharding, health, stragglers,
elastic re-mesh — exercised on the 8-device virtual mesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt.checkpoint import Checkpointer
from repro.ft import (HealthMonitor, NodeState, StragglerWatchdog,
                      elastic_remesh, survivors_mesh)
from jax.sharding import AxisType

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def _mesh(shape=(4, 2)):
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _tree(mesh):
    sh = NamedSharding(mesh, P("data", "model"))
    rep = NamedSharding(mesh, P())
    return {
        "w": jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8), sh),
        "b": jax.device_put(jnp.ones((3,), jnp.bfloat16), rep),
        "step": jax.device_put(jnp.int32(7), rep),
    }, {"w": sh, "b": rep, "step": rep}


def test_checkpoint_roundtrip(tmp_path):
    mesh = _mesh()
    tree, sh = _tree(mesh)
    ck = Checkpointer(str(tmp_path))
    ck.save(7, tree, blocking=True)
    assert ck.latest_step() == 7
    abs_tree = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    restored = ck.restore(7, abs_tree, sh)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(tree[k]))


def test_checkpoint_reshard_to_smaller_mesh(tmp_path):
    """512->256-style elastic restore: save on (4,2), restore on (2,2)."""
    mesh = _mesh((4, 2))
    tree, _ = _tree(mesh)
    ck = Checkpointer(str(tmp_path))
    ck.save(3, tree, blocking=True)

    small = jax.make_mesh((2, 2), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    sh2 = {"w": NamedSharding(small, P("data", "model")),
           "b": NamedSharding(small, P()),
           "step": NamedSharding(small, P())}
    abs_tree = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    step, restored = elastic_remesh(ck, abs_tree, sh2)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64, dtype=np.float32).reshape(8, 8))
    assert set(d.id for d in restored["w"].sharding.mesh.devices.flat) \
        == set(d.id for d in small.devices.flat)


def test_checkpoint_async_and_gc(tmp_path):
    mesh = _mesh()
    tree, sh = _tree(mesh)
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    ck.wait()
    ck._gc()
    assert ck.all_steps() == [3, 4]


def test_crash_atomicity(tmp_path):
    """A step dir without COMMITTED must be invisible."""
    mesh = _mesh()
    tree, sh = _tree(mesh)
    ck = Checkpointer(str(tmp_path))
    ck.save(5, tree, blocking=True)
    os.makedirs(tmp_path / "step_9", exist_ok=True)     # simulated torn write
    (tmp_path / "step_9" / "shard_0.npz").write_bytes(b"garbage")
    assert ck.latest_step() == 5


def test_health_monitor_detects_failure():
    t = [0.0]
    hm = HealthMonitor(n_nodes=4, heartbeat_timeout_s=30, suspect_timeout_s=10,
                       clock=lambda: t[0])
    assert hm.failed_nodes() == []
    hm.inject_failure(2)
    t[0] = 15.0
    for n in (0, 1, 3):
        hm.heartbeat(n)
    assert hm.state(2) == NodeState.SUSPECT
    t[0] = 35.0
    for n in (0, 1, 3):
        hm.heartbeat(n)
    assert hm.failed_nodes() == [2]
    assert sorted(hm.healthy_nodes()) == [0, 1, 3]


def test_straggler_watchdog():
    wd = StragglerWatchdog(n_nodes=4, threshold=1.5, evict_after=3)
    for _ in range(5):
        wd.record_step(np.array([1.0, 1.0, 1.0, 4.0]))
    assert wd.stragglers() == [3]
    assert wd.to_evict() == [3]
    w = wd.shard_weights()
    assert w[3] < w[0]          # straggler gets less data
    assert w.sum() == pytest.approx(1.0)


def test_survivors_mesh():
    mesh = _mesh((4, 2))
    small = survivors_mesh(mesh, failed_dp_rows=[1])
    assert dict(small.shape) == {"data": 2, "model": 2}
    # surviving devices only
    lost = set(np.asarray(mesh.devices)[1].flatten())
    assert not (set(small.devices.flatten()) & lost)


def test_end_to_end_elastic_training(tmp_path):
    """Save -> kill a DP row -> re-mesh -> restore -> keep training."""
    import dataclasses
    from repro.configs import ARCHS
    from repro.models.common import init_params
    from repro.optim import AdamWConfig, adamw
    from repro.train import build_train_step

    cfg = dataclasses.replace(ARCHS["qwen2-0.5b"].reduced(), remat="none")
    mesh = _mesh((4, 2))
    B, S = 8, 16
    batch_abs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    fns = build_train_step(cfg, mesh, batch_abs, donate=False,
                           opt_cfg=AdamWConfig(lr=1e-3))
    params = jax.device_put(init_params(fns.layout, jax.random.key(0)),
                            fns.param_shardings)
    opt = jax.device_put(adamw.init(params, AdamWConfig(lr=1e-3)),
                         fns.opt_shardings)
    batch = {"tokens": jnp.zeros((B, S), jnp.int32) + 3,
             "labels": jnp.ones((B, S), jnp.int32)}
    params, opt, m0 = fns.step(params, opt, batch)
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"params": params, "opt": opt}, blocking=True)

    # node failure -> half the DP rows survive
    small = survivors_mesh(mesh, failed_dp_rows=[0])
    fns2 = build_train_step(cfg, small, batch_abs, donate=False,
                            opt_cfg=AdamWConfig(lr=1e-3))
    step, state = elastic_remesh(
        ck, {"params": fns2.params_abstract, "opt": fns2.opt_abstract},
        {"params": fns2.param_shardings, "opt": fns2.opt_shardings})
    assert step == 1
    p2, o2, m1 = fns2.step(state["params"], state["opt"], batch)
    assert np.isfinite(float(m1["loss"]))
    assert int(o2["step"]) == 2          # optimizer state carried over


def test_straggler_all_zero_step_has_no_stragglers():
    """An all-zero step report (no node timed yet) must be a clean no-op:
    no RuntimeWarning from np.median of an empty slice, no nan EMA, no
    stragglers, no strikes."""
    import warnings

    w = StragglerWatchdog(n_nodes=4, evict_after=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # any warning -> test failure
        w.record_step(np.zeros(4))
        assert w.stragglers() == []
        assert w.to_evict() == []
        np.testing.assert_array_equal(w.shard_weights(), np.full(4, 0.25))
        # And the watchdog still works once real times arrive.
        w.record_step(np.array([1.0, 1.0, 1.0, 10.0]))
    assert w.stragglers() == [3]


def test_health_monitor_unknown_node_is_a_clear_error():
    hm = HealthMonitor(n_nodes=4, clock=lambda: 0.0)
    with pytest.raises(ValueError, match=r"unknown node 9.*n_nodes=4"):
        hm.state(9)
    with pytest.raises(ValueError, match="unknown node -1"):
        hm.state(-1)


def test_chaos_injector_determinism():
    """Same seed + schedule -> identical corruption; clock is fully virtual."""
    from repro.ft import ChaosInjector

    data = {"R": np.arange(20, dtype=np.int32).reshape(10, 2)}
    outs = []
    for _ in range(2):
        ch = ChaosInjector(4, seed=7)
        ch.corrupt_rows("R", n_rows=3, at_step=0)
        outs.append(ch.mangle(data)["R"])
    np.testing.assert_array_equal(outs[0], outs[1])
    assert (outs[0] < -1).sum() == 3                # exactly 3 cells mangled
    assert (data["R"] >= 0).all()                   # caller's array untouched
    ch = ChaosInjector(4)
    assert ch.clock() == 0.0
    ch.advance(2.5)
    ch.advance(2.5)
    assert ch.clock() == 5.0 and ch.step == 2
    ch.drop_heartbeats(1)
    assert ch.dropped_heartbeats() == {1}
    ch.restore_heartbeats(1)
    assert ch.dropped_heartbeats() == set()
    assert ch.squeeze({"R": 100, "S": 3}) == {"R": 100, "S": 3}
    ch.squeeze_caps(0.01)
    assert ch.squeeze({"R": 100, "S": 3}) == {"R": 1, "S": 1}
