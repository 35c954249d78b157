"""Validate launch/hlo_analysis against XLA's own cost analysis.

Compiles the same toy transformer twice — scanned and unrolled — on a 512-dev
mesh.  Checks:
  1. parser(scanned).flops ≈ xla_cost(unrolled).flops  (trip-count weighting)
  2. parser(unrolled).flops ≈ xla_cost(unrolled).flops (dot parsing itself)
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import time
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

import sys
sys.path.insert(0, "src")
from repro.launch import hlo_analysis
from jax.sharding import AxisType

mesh = jax.make_mesh((2, 16, 16), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)

D, FF, H, KV, L, V, B, S = 5120, 17408, 40, 8, 40, 151936, 32, 4096
HD = D // H


def init_specs():
    layer = {
        "wq": jax.ShapeDtypeStruct((D, H * HD), jnp.bfloat16),
        "wk": jax.ShapeDtypeStruct((D, KV * HD), jnp.bfloat16),
        "wv": jax.ShapeDtypeStruct((D, KV * HD), jnp.bfloat16),
        "wo": jax.ShapeDtypeStruct((H * HD, D), jnp.bfloat16),
        "w1": jax.ShapeDtypeStruct((D, FF), jnp.bfloat16),
        "w3": jax.ShapeDtypeStruct((D, FF), jnp.bfloat16),
        "w2": jax.ShapeDtypeStruct((FF, D), jnp.bfloat16),
    }
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((L,) + s.shape, s.dtype), layer)
    return {"emb": jax.ShapeDtypeStruct((V, D), jnp.bfloat16), "layers": stacked}


def fwd(params, tokens, unroll):
    x = params["emb"][tokens]

    def body(x, lp):
        h = x
        q = (h @ lp["wq"]).reshape(x.shape[0], x.shape[1], H, HD)
        k = (h @ lp["wk"]).reshape(x.shape[0], x.shape[1], KV, HD)
        v = (h @ lp["wv"]).reshape(x.shape[0], x.shape[1], KV, HD)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (HD ** 0.5)
        mask = jnp.tril(jnp.ones((x.shape[1], x.shape[1]), bool))
        logits = jnp.where(mask, logits, -1e9)
        att = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(*x.shape[:2], -1)
        x = x + o @ lp["wo"]
        g = jax.nn.silu(x @ lp["w1"]) * (x @ lp["w3"])
        return x + g @ lp["w2"], ()

    body = jax.checkpoint(body)
    if unroll:
        for i in range(L):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, _ = body(x, lp)
    else:
        x, _ = jax.lax.scan(body, x, params["layers"])
    return (x @ params["emb"].T).astype(jnp.float32)


def loss_fn(params, tokens, labels, unroll):
    logits = fwd(params, tokens, unroll)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], axis=-1))


def train_step(unroll):
    def f(params, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, tokens, labels, unroll))(params)
        return jax.tree.map(lambda p, g: p - 1e-4 * g.astype(p.dtype),
                            params, grads), loss
    return f


pspec = {
    "emb": P("model", None),
    "layers": {k: P(None, None, "model") for k in ("wq", "wk", "wv", "w1", "w3")}
    | {"wo": P(None, "model", None), "w2": P(None, "model", None)},
}
shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                         is_leaf=lambda x: isinstance(x, P))
tok_sh = NamedSharding(mesh, P(("pod", "data"), None))

results = {}
for unroll in (False, True):
    t0 = time.time()
    comp = jax.jit(train_step(unroll),
                   in_shardings=(shardings, tok_sh, tok_sh),
                   out_shardings=(shardings, NamedSharding(mesh, P()))).lower(
        init_specs(),
        jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B, S), jnp.int32)).compile()
    ca = comp.cost_analysis()
    if isinstance(ca, list):       # older jax: one dict per device partition
        ca = ca[0]
    txt = comp.as_text()
    terms = hlo_analysis.analyze(txt, pod_size=256)
    results[unroll] = (ca["flops"], terms)
    print(f"unroll={unroll}: compile {time.time()-t0:.0f}s  "
          f"xla_flops={ca['flops']:.3e}  parsed_flops={terms.flops:.3e}  "
          f"parsed_coll={terms.coll_bytes_total:.3e}B  "
          f"crosspod={terms.coll_bytes_crosspod:.3e}B  "
          f"hbm={terms.hbm_bytes:.3e}B")
    print("  coll counts:", {k: v for k, v in terms.coll_counts.items() if v})
    print("  coll bytes:", {k: f"{v:.2e}" for k, v in terms.coll_bytes.items()})

xla_unrolled = results[True][0]
parsed_scanned = results[False][1].flops
parsed_unrolled = results[True][1].flops
print(f"\nratio parsed_scanned/xla_unrolled  = {parsed_scanned/xla_unrolled:.3f}")
print(f"ratio parsed_unrolled/xla_unrolled = {parsed_unrolled/xla_unrolled:.3f}")
