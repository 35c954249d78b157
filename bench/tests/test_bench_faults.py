"""The check has to fail: with the control's answers in the program's
place, and with the timed path broken underneath in each way a cell can
break."""
import json

import jax
import numpy as np
import pytest

from bench import run as harness
from bench.tests.rehearse import rehearse, rehearse_four_chips
from repro.serve import join_engine

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def test_control_comes_out_not_correct():
    _, _, line = rehearse("paper3-batch", control="hash_match", rows=8193)
    assert not line["correct"]
    assert line["checks"]["rows_wrong"]["value"] > 0


def _wrap_serve(monkeypatch, before=None, after=None):
    serve = join_engine.JoinServingEngine._serve

    def broken(self, t, req):
        if before:
            before(req)
        serve(self, t, req)
        if after:
            after(req)

    monkeypatch.setattr(join_engine.JoinServingEngine, "_serve", broken)


def _half(req):
    req.data = {r: np.asarray(a)[:len(a) // 2] for r, a in req.data.items()}


def _altered(req):
    req.rows = req.rows.copy()
    req.rows[len(req.rows) // 2, -1] += 1


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["half_the_rows", "one_answer_altered"])
def test_broken_path_is_not_correct(monkeypatch, cell, fault):
    if fault == "half_the_rows":
        _wrap_serve(monkeypatch, before=_half)
    else:
        _wrap_serve(monkeypatch, after=_altered)
    _, run, line = rehearse(cell)
    assert not line["correct"]
    assert line["checks"]["requests_wrong"]["value"] == len(run.completed())


def test_exchange_left_out_is_not_correct(monkeypatch):
    """Each chip joins only its own shard's copies."""
    assert rehearse_four_chips()["rows_wrong"]["value"] == 0
    monkeypatch.setattr(jax.lax, "all_to_all",
                        lambda x, *a, **k: x)
    assert rehearse_four_chips()["rows_wrong"]["value"] > 0
