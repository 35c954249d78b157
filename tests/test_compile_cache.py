"""The persistent compilation cache keys on op_name scopes and not on where
the source lies: a step whose operations differ from a cached one only in
their scopes compiles its own executable, and a moved checkout still hits."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = '''
import json
import jax
import jax.numpy as jnp
from repro.launch.cache import enable_compile_cache

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
SRC = """
def f(x):
    with jax.named_scope("{scope}"):
        return jnp.sort(x) * 3 + 1
"""
out = {}
for tag, scope, path in (("a", "phase_a", "/one/x.py"),
                         ("b", "phase_b", "/one/x.py"),
                         ("a_moved", "phase_a", "/two/y.py")):
    ns = {"jax": jax, "jnp": jnp}
    exec(compile(SRC.format(scope=scope), path, "exec"), ns)
    n = len(hits)
    text = jax.jit(ns["f"]).lower(jnp.arange(64)).compile().as_text()
    out[tag] = [len(hits) > n, scope in text]
print(json.dumps(out))
'''


def test_cache_key_holds_scopes_and_no_source_paths(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # [loaded from the cache, the compiled text holds its own scope]
    assert out == {"a": [False, True], "b": [False, True],
                   "a_moved": [True, True]}
