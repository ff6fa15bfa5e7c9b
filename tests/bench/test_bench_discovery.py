"""A configuration, a traffic mix, an entry, a per-layer metric and a cell
added as new files and manifest entries, with no file of the benchmark
edited: the harness runs the cell, and the CPU tests size it and break it
from its own files."""
import importlib
import json
import shutil

import jax
import pytest

from bench import discover, run
from bench.run import ROOT
from test_bench_faults import _alter, _halve, _patch
from tiny import GENERATOR_N, RESULTS, program, tiny_n

MAN = discover.manifest()
ENTRIES = sorted(p.stem for p in (ROOT / "bench" / "entries").glob("*.py"))


@pytest.fixture
def tree(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    (b / "configs" / "line-apsp.json").write_text(json.dumps({
        "name": "line-apsp", "source": "https://example.org/line",
        "generator": "collaboration_graph", "n": 96, "test_n": 64,
        "params": {"ws_k": 4, "ws_p": 0.2, "clique_every": 32},
        "reduced": {}}))
    # a new entry: the dense one under another name, so that nothing keyed
    # by an entry's name can find it
    (b / "entries" / "dense_plain.py").write_text(
        (b / "entries" / "dense_cohesion.py").read_text())
    (b / "traffic" / "dense-plain.json").write_text(json.dumps({
        "entry": "dense_plain", "band": 1e-5,
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
    assert discover.traffic("dense-plain", tree)["entry"] == "dense_plain"
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


def test_new_config_and_entry_reach_the_cpu_tests(tree):
    man = discover.manifest(tree)
    cell = discover.workload(man, "line-dense")
    assert tiny_n(man, cell, tree) == 64
    target, name, result = program(cell, tree)
    assert (target.__name__, name, result) == ("repro.core.pald", "cohesion",
                                               "dense")


@pytest.mark.parametrize("fault", [_alter, _halve],
                         ids=lambda f: f.__name__.strip("_"))
def test_new_entry_is_broken_where_it_declares(tree, monkeypatch, fault):
    man = discover.manifest(tree)
    cell = discover.workload(man, "line-dense")
    _patch(monkeypatch, cell, fault, tree)
    r = run.run_cell(man, cell, 7, 0.1, False, jax.devices()[:1], root=tree,
                     n=tiny_n(man, cell, tree), log=lambda s: None)
    assert r["failed"] == 0
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cfg", MAN["configs"], ids=lambda c: c["name"])
def test_every_config_has_a_test_size(cfg):
    body = json.loads((ROOT / cfg["file"]).read_text())
    assert body.get("test_n") or body["generator"] in GENERATOR_N


@pytest.mark.parametrize("name", ENTRIES)
def test_every_entry_declares_its_program(name):
    entry = discover.module("entries", name)
    mod, attr = entry.PROGRAM
    assert callable(getattr(importlib.import_module(mod), attr))
    assert entry.RESULT in RESULTS
