"""Batched serving demo: prefill a batch of prompts, then decode with the
sharded KV cache (the decode_32k cell's step function at toy scale).

Run:  PYTHONPATH=src python examples/serve_decode.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.models import api
from repro.models.common import init_params
from repro.serve import build_decode_step
from jax.sharding import AxisType


def main():
    cfg = ARCHS["qwen2-0.5b"].reduced()
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    B, MAX_SEQ, PROMPT, GEN = 8, 128, 16, 32

    fns = build_decode_step(cfg, mesh, batch=B, max_seq=MAX_SEQ)
    params = jax.device_put(init_params(api.layout(cfg), jax.random.key(0)),
                            fns.param_shardings)
    cache = jax.device_put(api.init_cache(cfg, B, MAX_SEQ),
                           fns.cache_shardings)

    # "Prefill" a batch of random prompts token by token (toy; prefill_32k
    # lowers the fused prompt pass).
    rng = jax.random.key(1)
    prompts = jax.random.randint(rng, (B, PROMPT), 0, cfg.vocab)
    tok = prompts[:, :1]
    t0 = time.time()
    for t in range(PROMPT):
        pos = jnp.full((B,), t, jnp.int32)
        nxt, cache = fns.decode(params, cache, prompts[:, t:t + 1], pos)
    print(f"prefilled {B}x{PROMPT} tokens in {time.time()-t0:.2f}s")

    # Greedy decode.
    out = []
    tok = nxt[:, None]
    t0 = time.time()
    for t in range(PROMPT, PROMPT + GEN):
        pos = jnp.full((B,), t, jnp.int32)
        nxt, cache = fns.decode(params, cache, tok, pos)
        out.append(nxt)
        tok = nxt[:, None]
    dt = time.time() - t0
    gen = jnp.stack(out, axis=1)
    print(f"generated {B}x{GEN} tokens in {dt:.2f}s "
          f"({B*GEN/dt:.0f} tok/s on {len(jax.devices())} CPU devices)")
    print("first sequence:", gen[0].tolist())


if __name__ == "__main__":
    main()
