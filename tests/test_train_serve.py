"""Train/serve step builders on an 8-device (2 data × 4 model) test mesh."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import api, common as C
from repro.optim import AdamWConfig
from repro.serve import build_decode_step, build_prefill
from repro.train import build_train_step
from jax.sharding import AxisType

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def _mesh():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _setup(name, **overrides):
    cfg = dataclasses.replace(ARCHS[name].reduced(), **overrides)
    mesh = _mesh()
    B, S = 4, 16
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    if cfg.family == "encdec":
        batch_abs["frames"] = jax.ShapeDtypeStruct(
            (B, S // cfg.enc_ratio, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        batch_abs["vision_emb"] = jax.ShapeDtypeStruct(
            (B, cfg.vision_tokens, cfg.vision_dim), jnp.bfloat16)
    return cfg, mesh, batch_abs, B, S


def _real_batch(cfg, batch_abs, key):
    ks = jax.random.split(key, len(batch_abs))
    out = {}
    for (k, v), kk in zip(batch_abs.items(), ks):
        if v.dtype == jnp.int32:
            out[k] = jax.random.randint(kk, v.shape, 0, cfg.vocab)
        else:
            out[k] = jax.random.normal(kk, v.shape, v.dtype)
    return out


@pytest.mark.parametrize("name,n_micro,bits", [
    ("qwen3-14b", 1, 32),
    ("qwen3-14b", 2, 32),
    ("mixtral-8x22b", 1, 8),
    ("mamba2-370m", 1, 32),
])
def test_train_step_runs_and_descends(name, n_micro, bits):
    cfg, mesh, batch_abs, B, S = _setup(name)
    fns = build_train_step(cfg, mesh, batch_abs, n_micro=n_micro,
                           opt_cfg=AdamWConfig(lr=1e-2, state_bits=bits),
                           donate=False)
    params = C.init_params(fns.layout, jax.random.key(0))
    params = jax.device_put(params, fns.param_shardings)
    from repro.optim import adamw
    opt = jax.device_put(adamw.init(params, AdamWConfig(lr=1e-2, state_bits=bits)),
                         fns.opt_shardings)
    batch = _real_batch(cfg, batch_abs, jax.random.key(1))
    losses = []
    for i in range(4):
        params, opt, metrics = fns.step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]          # same batch -> loss must descend
    assert int(opt["step"]) == 4


def test_moe_expert_load_metric():
    cfg, mesh, batch_abs, B, S = _setup("mixtral-8x22b")
    fns = build_train_step(cfg, mesh, batch_abs, donate=False)
    params = jax.device_put(C.init_params(fns.layout, jax.random.key(0)),
                            fns.param_shardings)
    from repro.optim import adamw
    opt = jax.device_put(adamw.init(params, AdamWConfig()), fns.opt_shardings)
    batch = _real_batch(cfg, batch_abs, jax.random.key(1))
    _, _, metrics = fns.step(params, opt, batch)
    load = np.asarray(metrics["expert_load"])
    assert load.shape == (cfg.n_experts,)
    assert load.sum() == B * S * cfg.topk * cfg.n_layers


@pytest.mark.parametrize("name", ["qwen2-0.5b", "mamba2-370m", "zamba2-7b",
                                  "seamless-m4t-medium"])
def test_decode_step_runs(name):
    cfg, mesh, batch_abs, B, S = _setup(name)
    fns = build_decode_step(cfg, mesh, batch=B, max_seq=32)
    params = jax.device_put(C.init_params(fns.layout if hasattr(fns, "layout")
                                          else api.layout(cfg),
                                          jax.random.key(0)),
                            fns.param_shardings)
    cache = jax.device_put(api.init_cache(cfg, B, 32), fns.cache_shardings)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    for t in range(3):
        tok2, cache = fns.decode(params, cache, tok, pos + t)
        assert tok2.shape == (B,)
        tok = tok2[:, None]
    assert not bool(jnp.isnan(tok2.astype(jnp.float32)).any())


def test_prefill_runs():
    cfg, mesh, batch_abs, B, S = _setup("qwen3-14b")
    del batch_abs["labels"]
    fns = build_prefill(cfg, mesh, batch_abs)
    params = jax.device_put(C.init_params(api.layout(cfg), jax.random.key(0)),
                            fns.param_shardings)
    batch = _real_batch(cfg, batch_abs, jax.random.key(1))
    lg = fns.prefill(params, batch)
    assert lg.shape == (B, cfg.padded_vocab())
    assert not bool(jnp.isnan(lg).any())
