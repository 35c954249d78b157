"""The benchmark's files: BENCHMARK.json keeps to its schema, and every
configuration, traffic mix and metric is found by name, new ones included,
without an edit to code."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import gen
from bench import run as harness
from bench.record import Run

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_spec_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    metric_keys = {"end_to_end": {"name", "unit", "better", "bound",
                                  "source"},
                   "per_layer": {"name", "unit", "better", "source",
                                 "layer", "moves"}}
    names = []
    for kind, keys in metric_keys.items():
        for m in SPEC[kind]:
            assert set(m) - {"workloads"} == keys, m["name"]
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
            names.append(m["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for k in metric_keys for m in SPEC[k]}) == sum(
        len(SPEC[k]) for k in metric_keys)


def test_every_bound_in_range():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_found_by_name(cell):
    spec, w, config, traffic = harness.load_cell(cell)
    assert w["name"] == cell
    assert traffic["loop"] == "closed"
    assert config["name"] == w["config"]
    assert set(traffic.get("tenant_shares", {})) <= set(config["tenants"])
    # Each cell reports setup_s, one more end-to-end and one per-layer
    # metric, and every per-layer metric moves one that the cell reports.
    e2e = {m["name"] for m in harness.cell_metrics(spec, cell, False)}
    layer = harness.cell_metrics(spec, cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    assert all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("metric", [m["name"] for k in ("end_to_end",
                                                        "per_layer")
                                    for m in SPEC[k]])
def test_every_metric_has_a_reader_that_reads_nothing_from_nothing(metric):
    read = harness.metric_reader(metric)
    assert read(Run("none", 1, {"hbm_bytes_per_s": 1.0})) is None or \
        metric == "setup_s"


# A generator a later change might add: Zipf-keyed (z = 1) foreign keys
# from a fact table to two dimension tables `dim_share` times smaller.
STAR_GENERATOR = '''
import numpy as np

from bench.gen import relations

KEYS = {"config": {"zipf_z", "dim_share"}, "tenant": set()}


def _zipf(rng, n_keys, n, z):
    w = 1.0 / np.arange(1, n_keys + 1) ** z
    return rng.permutation(n_keys)[rng.choice(n_keys, n, p=w / w.sum())]


def tenant_data(config, tenant, rows, data_seed):
    rng = np.random.default_rng(data_seed)
    (fact, _), *dims = relations(config, tenant)
    n_dim = rows // config["dim_share"]
    cols = [_zipf(rng, n_dim, rows, config["zipf_z"]) for _ in dims]
    out = {fact: np.stack(cols + [np.arange(rows)], axis=1)}
    for name, _ in dims:
        out[name] = np.stack([rng.permutation(n_dim), np.arange(n_dim)], 1)
    return {k: v.astype(np.int32) for k, v in out.items()}
'''
STAR_CONFIG = {
    "name": "star-zipf", "generator": "star_zipf", "rehearsal": {"rows": 2048},
    "tenants": {"star": {"relations": [["F", ["A", "B", "X"]],
                                       ["DA", ["A", "Y"]],
                                       ["DB", ["B", "Z"]]]}},
    "rows": 1 << 20, "zipf_z": 1.0, "dim_share": 8, "k_per_chip": 4,
    "out_capacity": {"rule": "max_prefix", "slack": 1.0}}


def _files(tree):
    return {str(p.relative_to(tree)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(tree.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _add_star_cell(root):
    """A deployment as a later change adds it: new files, and new entries
    in BENCHMARK.json."""
    bench = root / "bench"
    (bench / "generators/star_zipf.py").write_text(STAR_GENERATOR)
    (bench / "configs/star-zipf.json").write_text(json.dumps(STAR_CONFIG))
    (bench / "traffic/closed1-pool3.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "pool": 3}))
    (bench / "metrics/star_requests.py").write_text(
        "def read(run):\n    return len(run.completed()) or None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "star-zipf", "source": "x",
                            "file": "bench/configs/star-zipf.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "star-batch", "config": "star-zipf",
                              "traffic": "closed1-pool3", "chips": 1,
                              "why": "x"})
    spec["end_to_end"].append({"name": "star_requests", "unit": "requests",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["star-batch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_config_and_traffic_files_are_picked_up(tmp_path):
    """A later change adds a cell by adding files and entries only: here a
    deployment with a generator of its own rehearses to `correct` in a copy
    of the tree whose existing benchmark files are left as they were."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    before = _files(tmp_path / "bench")
    spec = json.loads(json.dumps(SPEC))
    config = json.loads((ROOT / "bench/configs/paper3-2m.json").read_text())
    config.update(name="paper3u-2m", heavy_share=1 << 30)
    (tmp_path / "bench/configs/paper3u-2m.json").write_text(
        json.dumps(config))
    (tmp_path / "bench/traffic/closed1-pool3.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "pool": 3}))
    spec["configs"].append({"name": "paper3u-2m", "source": "x",
                            "file": "bench/configs/paper3u-2m.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "paper3u-batch", "config": "paper3u-2m",
                              "traffic": "closed1-pool3", "chips": 1,
                              "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    _, cell, config, traffic = harness.load_cell("paper3u-batch", tmp_path)
    assert config["heavy_share"] == 1 << 30 and traffic["pool"] == 3
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 7.0\n")
    assert harness.metric_reader("new_metric", tmp_path / "bench")(None) == 7

    _add_star_cell(tmp_path)
    _, _, config, _ = harness.load_cell("star-batch", tmp_path)
    assert config["generator"] == "star_zipf"
    code = ("import json; from bench.tests.rehearse import rehearse; "
            "_, _, line = rehearse('star-batch'); print(json.dumps(line))")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.pathsep.join([str(tmp_path), str(ROOT / "src")])}
    done = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0, line["checks"]
    assert line["metrics"]["star_requests"]["value"] == line["attempted"] > 0
    assert set(line["metrics"]) == {"star_requests", "setup_s"}
    after = _files(tmp_path / "bench")
    assert {f: after[f] for f in before} == before


@pytest.mark.parametrize("change", [
    {"generator": "no_such_generator"}, {"generator": None},
    {"hot_keys": 3}, {"rehearsal": {"hot_keys": 3}}, {"rehearsal": None},
    {"tenants": {"star": {**STAR_CONFIG["tenants"]["star"],
                          "heavy": {"A": "F"}}}}],
    ids=["unknown_generator", "no_generator", "unknown_config_key",
         "unknown_rehearsal_key", "no_rehearsal", "unknown_tenant_key"])
def test_a_configuration_the_harness_cannot_read_is_refused(tmp_path,
                                                             change):
    """A generator that is not there, or a key that neither the harness
    nor the configuration's generator reads, is refused as the cell is
    loaded, never ignored."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    _add_star_cell(tmp_path)
    harness.load_cell("star-batch", tmp_path)
    path = tmp_path / "bench/configs/star-zipf.json"
    path.write_text(json.dumps({**STAR_CONFIG, **change}))
    with pytest.raises(ValueError):
        harness.load_cell("star-batch", tmp_path)


@pytest.mark.parametrize("change", [
    {"loop": "open"}, {"arrivals": "onoff"}, {"sizes": "log_uniform"},
    {"clients": 2}, {"pool": 0}, {"tenant_shares": {"nobody": 1.0}}],
    ids=["loop", "unknown_key", "size_law", "clients", "pool", "tenant"])
def test_traffic_the_generator_cannot_make_is_refused(change):
    _, _, config, traffic = harness.load_cell("paper3-batch")
    with pytest.raises(ValueError):
        gen.check_traffic({**traffic, **change}, config)


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")


def test_unknown_device_kind_is_an_error():
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.device_peaks("cpu")


def test_a_cpu_makes_the_run_fail_without_a_result(capsys):
    """JAX here has only the CPU: the run exits 1 and prints no result."""
    rc = harness.main(["--workload", "paper3-batch", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 1
    assert "{" not in out and "no TPU" in err
