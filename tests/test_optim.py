"""Optimizer substrate: AdamW (fp32 + int8 states), schedules, grad compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.optim import AdamWConfig, adamw, grad_compress
from repro.optim.schedule import warmup_cosine
from jax.sharding import AxisType


def _quad_problem():
    params = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)),
                               jnp.float32),
              "b": jnp.zeros((16,), jnp.float32)}
    target = jnp.asarray(np.random.default_rng(1).normal(size=(8, 16)),
                         jnp.float32)

    def loss(p):
        return jnp.mean((p["w"] + p["b"] - target) ** 2)

    return params, loss


@pytest.mark.parametrize("bits", [32, 8])
def test_adamw_converges(bits):
    cfg = AdamWConfig(lr=5e-2, weight_decay=0.0, state_bits=bits)
    params, loss = _quad_problem()
    state = adamw.init(params, cfg)
    l0 = float(loss(params))
    for _ in range(60):
        g = jax.grad(loss)(params)
        params, state, metrics = adamw.apply(params, state, g, cfg)
    assert float(loss(params)) < 0.05 * l0
    assert int(state["step"]) == 60
    assert np.isfinite(float(metrics["grad_norm"]))


def test_int8_states_close_to_fp32():
    """Trajectories agree early (quantization noise stays bounded)."""
    params, loss = _quad_problem()
    outs = {}
    for bits in (32, 8):
        cfg = AdamWConfig(lr=1e-2, weight_decay=0.0, state_bits=bits)
        p, s = params, adamw.init(params, cfg)
        for _ in range(10):
            g = jax.grad(loss)(p)
            p, s, _ = adamw.apply(p, s, g, cfg)
        outs[bits] = p
    diff = float(jnp.abs(outs[8]["w"] - outs[32]["w"]).max())
    scale = float(jnp.abs(outs[32]["w"]).max())
    assert diff < 0.05 * scale


def test_grad_clip():
    cfg = AdamWConfig(lr=1.0, grad_clip=1e-3, weight_decay=0.0)
    params = {"w": jnp.ones((4,), jnp.float32)}
    state = adamw.init(params, cfg)
    g = {"w": jnp.full((4,), 1e6, jnp.float32)}
    p2, _, m = adamw.apply(params, state, g, cfg)
    assert float(m["grad_norm"]) == pytest.approx(2e6, rel=1e-3)
    assert float(jnp.abs(p2["w"] - params["w"]).max()) < 2.0   # clipped step


def test_warmup_cosine_shape():
    assert float(warmup_cosine(0, warmup=100, total=1000)) == 0.0
    assert float(warmup_cosine(100, warmup=100, total=1000)) == pytest.approx(1.0)
    assert float(warmup_cosine(1000, warmup=100, total=1000)) == pytest.approx(0.1)
    mid = float(warmup_cosine(550, warmup=100, total=1000))
    assert 0.1 < mid < 1.0


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_compress_roundtrip_bounded_error():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 64)) * 3.0, jnp.float32)
    q, s = grad_compress.compress(x)
    err = jnp.abs(grad_compress.decompress(q, s) - x)
    assert q.dtype == jnp.int8
    assert float(err.max()) <= float(s) * 0.51 + 1e-6   # half-step rounding


def test_error_feedback_accumulates():
    """EF makes the AVERAGE of repeated compressions unbiased."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(256,)) * 1e-3, jnp.float32)
    err = jnp.zeros_like(x)
    total = jnp.zeros_like(x)
    n = 50
    for _ in range(n):
        q, s, err = grad_compress.ef_compress(x, err)
        total = total + grad_compress.decompress(q, s)
    np.testing.assert_allclose(np.asarray(total / n), np.asarray(x),
                               atol=float(jnp.abs(x).max()) * 0.05)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_compressed_psum_in_shard_map():
    """compressed_psum ≈ psum across a manual mesh axis (the cross-pod hop)."""
    mesh = jax.make_mesh((8,), ("pod",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
    errs = jnp.zeros((8, 32), jnp.float32)

    def f(x, e):
        total, new_e = grad_compress.compressed_psum(x[0], "pod", e[0])
        return total[None], new_e[None]

    out, _ = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("pod"), P("pod")),
        out_specs=(P("pod"), P("pod")), check_vma=False))(xs, errs)
    expect = np.asarray(xs).sum(axis=0)
    # each device holds the same decompressed sum
    got = np.asarray(out)
    for d in range(8):
        np.testing.assert_allclose(got[d], expect, atol=0.02 * np.abs(expect).max())
