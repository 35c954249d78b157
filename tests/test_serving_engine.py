"""Continuous-batching engine: completion, isolation, batching-invariance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import api
from repro.models.common import init_params
from repro.serve import ServingEngine
from repro.serve.serve_step import build_decode_step
from jax.sharding import AxisType

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def _mesh():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _engine(slots=4, max_seq=48, name="qwen2-0.5b"):
    cfg = ARCHS[name].reduced()
    params = init_params(api.layout(cfg), jax.random.key(0))
    return ServingEngine(cfg, _mesh(), slots, max_seq, params), cfg


def test_all_requests_complete_with_fewer_slots_than_requests():
    eng, cfg = _engine(slots=2)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, size=n).tolist(), m)
            for n, m in ((5, 6), (3, 8), (7, 4), (2, 10), (4, 5))]
    done = eng.run()
    assert all(r.done for r in done)
    for (_, m), r in zip(((5, 6), (3, 8), (7, 4), (2, 10), (4, 5)), reqs):
        assert len(r.out) == m
    assert eng.tokens_out == 6 + 8 + 4 + 10 + 5


def test_continuous_batching_matches_solo_generation():
    """Sharing slots must not change any request's output (isolation)."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 500, size=n).tolist() for n in (4, 6, 3)]
    gen = 5

    eng, cfg = _engine(slots=3)
    reqs = [eng.submit(p, gen) for p in prompts]
    eng.run()
    batched = [r.out for r in reqs]

    solo_outs = []
    for p in prompts:
        eng1, _ = _engine(slots=1)
        r = eng1.submit(p, gen)
        eng1.run()
        solo_outs.append(r.out)

    assert batched == solo_outs


def test_slot_reuse_is_isolated():
    """A reused slot must not leak the previous occupant's state."""
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, 500, size=6).tolist()
    p2 = rng.integers(0, 500, size=4).tolist()

    eng, cfg = _engine(slots=1)          # p2 must reuse p1's slot
    r1 = eng.submit(p1, 4)
    r2 = eng.submit(p2, 4)
    eng.run()

    eng_fresh, _ = _engine(slots=1)
    r2f = eng_fresh.submit(p2, 4)
    eng_fresh.run()
    assert r2.out == r2f.out


def test_occupancy_metric():
    eng, cfg = _engine(slots=4)
    eng.submit([1, 2, 3], 4)
    eng._admit()
    assert eng.occupancy() == 0.25


def test_many_requests_admit_fifo_without_rescans():
    """Admission is a FIFO deque pop, not a full-queue rescan: submitting
    many requests fills free slots in submission order and leaves exactly
    the unadmitted tail waiting."""
    eng, cfg = _engine(slots=3)
    n = 50
    reqs = [eng.submit([1 + (i % 7), 2, 3], 4) for i in range(n)]
    assert len(eng.waiting) == n
    eng._admit()
    assert [eng.slots[i] for i in range(3)] == reqs[:3]   # FIFO order
    assert len(eng.waiting) == n - 3
    # A request cancelled before admission is skipped, not seated.
    reqs[3].done = True
    reqs[0].done = True                                    # finished...
    eng.slots[0] = None                                    # ...slot freed
    eng._admit()
    assert eng.slots[0] is reqs[4]
    assert len(eng.waiting) == n - 5                       # popped 3,4
    # Draining the engine admits everyone else exactly once.
    eng.run()
    assert all(r.done for r in reqs)
    assert not eng.waiting
