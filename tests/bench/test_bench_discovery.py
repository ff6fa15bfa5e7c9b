"""A configuration, a traffic mix, a per-layer metric and a cell added as
new files and manifest entries, with no file of the benchmark edited."""
import json
import shutil

import jax
import pytest

from bench import discover, run
from bench.run import ROOT


@pytest.fixture
def tree(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    (b / "configs" / "line-apsp.json").write_text(json.dumps({
        "name": "line-apsp", "source": "https://example.org/line",
        "generator": "collaboration_graph", "n": 96,
        "params": {"ws_k": 4, "ws_p": 0.2, "clique_every": 32},
        "reduced": {}}))
    (b / "traffic" / "dense-plain.json").write_text(json.dumps({
        "entry": "dense_cohesion", "band": 1e-5,
        "call": {"method": "triplet", "ties": "drop", "normalize": True},
        "limits": {"c_err": 1e-5, "comm_faults": 0}}))
    (b / "metrics" / "jobs.count.py").write_text(
        "def read(ctx):\n    return float(ctx.jobs)\n")
    man["configs"].append({"name": "line-apsp",
                           "source": "https://example.org/line",
                           "file": "bench/configs/line-apsp.json",
                           "reduced": [], "why": "a test deployment"})
    man["workloads"].append({"name": "line-dense", "config": "line-apsp",
                             "traffic": "dense-plain", "chips": 1,
                             "why": "a test cell"})
    man["per_layer"].append({"name": "jobs.count", "unit": "jobs",
                             "better": "higher", "source": "host_clock",
                             "layer": "harness", "moves": "solve_s",
                             "workloads": ["line-dense"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp_path


def test_new_files_are_found_by_name(tree):
    man = discover.manifest(tree)
    cell = discover.workload(man, "line-dense")
    assert discover.config(man, cell, tree)["n"] == 96
    assert discover.traffic("dense-plain", tree)["entry"] == "dense_cohesion"
    assert [m["name"] for m in discover.per_layer(man, cell)] == ["jobs.count"]
    assert discover.module("metrics", "jobs.count", tree).read


@pytest.mark.parametrize("trace", [False, True])
def test_new_cell_runs_and_reports(tree, trace):
    man = discover.manifest(tree)
    cell = discover.workload(man, "line-dense")
    r = run.run_cell(man, cell, 5, 0.2, trace, jax.devices()[:1], root=tree,
                     log=lambda s: None)
    assert r["correct"], r["checks"]
    if trace:
        assert r["metrics"]["jobs.count"]["value"] >= 1
    else:
        assert set(r["metrics"]) == {"setup_s", "solve_s", "peak_hbm_gib"}


def test_unknown_names_are_errors(tree):
    man = discover.manifest(tree)
    with pytest.raises(KeyError):
        discover.workload(man, "no-such-cell")
    with pytest.raises(KeyError):
        discover.module("metrics", "no.such.metric", tree)
