"""The chip smoke's pieces on the CPU: its data generator, the exact cascade
sizes it sets capacities from, the numpy oracle it checks against, and the
whole smoke at a small size on one and on four devices."""
import importlib.util
import itertools
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import cascade_sizes, reference_join, running_example, triangle
from repro.core.reference import join_two
from repro.data import chain_query, pinned_skew_batch

_SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _join_two_loops(left, left_attrs, right, right_attrs):
    """Nested-loop natural join: left rows in order, each with its matching
    right rows in arrival order."""
    common = [a for a in left_attrs if a in right_attrs]
    extra = [right_attrs.index(a) for a in right_attrs if a not in common]
    out = []
    for lrow, rrow in itertools.product(left, right):
        if all(lrow[left_attrs.index(a)] == rrow[right_attrs.index(a)]
               for a in common):
            out.append(list(lrow) + [rrow[j] for j in extra])
    return np.asarray(out, np.int64).reshape(len(out), -1)


@pytest.mark.parametrize("seed", range(4))
def test_join_two_matches_nested_loops_in_order(seed):
    """The sort-grouped join emits exactly the nested-loop rows, in the same
    order, over one and two key columns with negative values."""
    rng = np.random.default_rng(seed)
    left = rng.integers(-3, 4, size=(40, 3))
    right = rng.integers(-3, 4, size=(30, 3))
    for la, ra in [(("A", "B", "C"), ("B", "D", "E")),
                   (("A", "B", "C"), ("B", "C", "E"))]:
        got, attrs = join_two(left, la, right, ra)
        assert attrs == la + tuple(a for a in ra if a not in la)
        np.testing.assert_array_equal(got, _join_two_loops(left, la, right,
                                                           ra))


@pytest.mark.parametrize("query,heavy", [
    (running_example(), {"B": "R", "C": "T"}),
    (chain_query(3), {"X1": "R0", "X2": "R2"}),
    (chain_query(4), {"X1": "R1", "X3": "R3"}),
], ids=["paper3", "chain3", "chain4"])
def test_cascade_sizes_match_reference(query, heavy):
    data = pinned_skew_batch(query, 600, heavy, 150, 3, 50, seed=1)
    sizes = cascade_sizes(query, data)
    assert sizes[0] == 600
    assert sizes[-1] == len(reference_join(query, data))
    for j in range(2, len(query.relations)):
        prefix = type(query)(query.relations[:j])
        assert sizes[j - 1] == len(reference_join(prefix, data))


def test_cascade_sizes_rejects_cycles():
    q = triangle()
    with pytest.raises(ValueError, match="not a path query"):
        cascade_sizes(q, {r.name: np.zeros((4, 2), np.int64)
                          for r in q.relations})


def test_pinned_skew_batch_pins_heavy_rows():
    q = running_example()
    heavy = {"B": "R", "C": "T"}
    a = pinned_skew_batch(q, 1024, heavy, 256, 4, 768, seed=3)
    b = pinned_skew_batch(q, 1024, heavy, 256, 4, 768, seed=3)
    for rel in q.relations:
        np.testing.assert_array_equal(a[rel.name], b[rel.name])
        assert a[rel.name].shape == (1024, len(rel.attrs))
    col = {(r.name, x): a[r.name][:, r.attrs.index(x)]
           for r in q.relations for x in r.attrs}
    assert (col["R", "B"] == 0).sum() == 256
    assert (col["S", "B"] == 0).sum() == 4
    assert (col["S", "C"] == 0).sum() == 4
    assert (col["T", "C"] == 0).sum() == 256
    # No S row holds the heavy value on both join attributes.
    assert not ((col["S", "B"] == 0) & (col["S", "C"] == 0)).any()
    with pytest.raises(ValueError, match="zero blocks exceed"):
        pinned_skew_batch(q, 6, heavy, 4, 4, 2)


def test_chip_smoke_refuses_cpu(capsys):
    """Without a TPU the smoke exits non-zero and never reports ok."""
    assert _load_smoke().main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_small_on_cpu(chips, capsys):
    """The smoke's whole path at 8192 rows per relation on CPU devices:
    every request row-exact, and with four devices each receives rows."""
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    smoke = _load_smoke()
    smoke.smoke(jax.devices()[:chips], seed=5, rows=8192)
    out = capsys.readouterr().out
    n_req = len(smoke.make_requests(chips, 5))
    assert out.count("row-exact vs reference_join") == n_req
    assert "MISMATCH" not in out
    if chips == 1:
        assert "hits=1" in out
