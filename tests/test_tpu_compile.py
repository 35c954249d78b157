"""Compile the main path for a described TPU v5e chip, at the smoke's sizes.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, so it catches what interpret mode cannot — tiles the TPU
refuses, operations Mosaic cannot lower, programs XLA cannot partition.
The one Pallas kernel on the main path is the count pass's `map_count`.
Shapes come from `chip_smoke.py`'s 3-way request (2^21 rows per relation,
k = 4 logical cells on one chip).  The topology is described inside a
fixture, never at import, so every test worker collects the same tests.
The `map_count` fork in `kernels/ops.py` asks `jax.default_backend()`, which
is the CPU here, so the tests steer it to the TPU branch with monkeypatch.
"""
import functools
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import cascade_sizes, plan_skew_join
from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
from repro.kernels import map_pack as mp
from repro.kernels import ops

os.environ.setdefault("TPU_LOG_DIR", "disabled")

_SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def tpu_dispatch(monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


@functools.cache
def _case(chips: int) -> dict:
    """The smoke's first 3-way request on `chips` chips: plan, config, and
    the shapes and shuffle capacities its session derives (counted on as
    many CPU devices)."""
    smoke = _load_smoke()
    query, data = smoke.tenant_batch("paper3", smoke.ROWS, 0)
    plan = plan_skew_join(query, data, smoke.CELLS_PER_CHIP * chips)
    cfg = ExecutorConfig(out_capacity=max(cascade_sizes(query, data)[1:]))
    cpu = Mesh(np.array(jax.devices()[:chips]), ("cells",))
    ses = ShardedJoinExecutor(plan, cpu, config=cfg).session().prepare(data)
    return {"chips": chips, "query": query, "plan": plan, "cfg": cfg,
            "caps": ses.caps, "shapes": ses._shapes}


# Module-scoped, so each case is counted before `tpu_dispatch` steers the
# dispatch to kernels that cannot run on the CPU.
@pytest.fixture(scope="module")
def smoke_case():
    return _case(1)


@pytest.fixture(scope="module", params=[1, 4], ids=["1chip", "4chips"])
def chips_case(request):
    return _case(request.param)


def _sds(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def test_map_count_compiles(smoke_case, one_chip):
    ex_specs = ShardedJoinExecutor(
        smoke_case["plan"], Mesh(np.array(jax.devices()[:1]), ("cells",)),
        config=smoke_case["cfg"]).route_specs
    k = smoke_case["plan"].k
    for rel, shape in zip(smoke_case["query"].relations,
                          smoke_case["shapes"]):
        c = _compile(lambda x, s=ex_specs[rel.name]: mp.map_count(
            x, routes=s, k=k, n_src=1), _sds(shape, one_chip))
        assert _custom_calls(c) == 1, rel.name


@pytest.mark.parametrize("phase", sorted(ops.TPU_IMPL))
def test_dispatch_follows_tpu_impl(phase, one_chip, tpu_dispatch):
    """Each phase's entry point lowers to a kernel on TPU exactly when
    `TPU_IMPL` says so: `map_count` to one, the other three to none (small
    shapes: this checks the choice, not size)."""
    n, sd = 4096, one_chip
    spec = ((((0, 0x9E3779B1, 4, 1),), (0,), 0, (), ()),)
    calls = {
        "map_count": (lambda r: ops.map_count(r, spec, 4, 1),
                      [_sds((n, 2), sd)]),
        "join_probe": (lambda lk, lv, rk, rv: ops.join_probe(lk, lv, rk, rv),
                       [_sds((n, 2), sd), _sds((n,), sd, jnp.bool_),
                        _sds((n, 2), sd), _sds((n,), sd, jnp.bool_)]),
        "scatter_pack": (lambda r, t: ops.scatter_pack(r, spec, t, 4, 1, n),
                         [_sds((n, 2), sd), _sds((4,), sd)]),
        "expand_rows": (lambda l, r, c, lo, pm: ops.expand_rows(
            l, r, c, lo, pm, n), [_sds((n, 3), sd), _sds((n, 3), sd),
                                  _sds((n,), sd), _sds((n,), sd),
                                  _sds((n,), sd)]),
    }
    f, args = calls[phase]
    used = _custom_calls(_compile(f, *args)) > 0
    assert used == (ops.TPU_IMPL[phase] == "kernel")


def test_count_pass_compiles(chips_case, topo, tpu_dispatch):
    """The count pass runs its kernel under shard_map: XLA cannot partition
    a Pallas call over a sharded operand."""
    case = chips_case
    mesh = Mesh(np.array(topo.devices[:case["chips"]]), ("cells",))
    ex = ShardedJoinExecutor(case["plan"], mesh, config=case["cfg"])
    shard = NamedSharding(mesh, P("cells"))
    c = ex._count_pass().lower(
        *(_sds(s, shard) for s in case["shapes"])).compile()
    assert _custom_calls(c) == len(case["shapes"])


def test_executor_step_compiles(smoke_case, topo, tpu_dispatch):
    """The whole map -> all_to_all -> reduce step on one chip at the smoke's
    shapes: it fits the chip's 16 GB.  Every phase of the step runs XLA on
    TPU (`TPU_IMPL`), so it holds no kernel call."""
    case = smoke_case
    mesh = Mesh(np.array(topo.devices[:1]), ("cells",))
    ex = ShardedJoinExecutor(case["plan"], mesh, config=case["cfg"])
    f = ex._compiled_step(case["shapes"], case["caps"])
    args = [_sds((case["plan"].k,), NamedSharding(mesh, P()))]
    args += [_sds(s, NamedSharding(mesh, P("cells"))) for s in case["shapes"]]
    c = f.lower(*args).compile()
    assert _custom_calls(c) == 0
    mem = c.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < 16 * 2**30
