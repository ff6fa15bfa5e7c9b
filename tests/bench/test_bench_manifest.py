"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
finds its files."""
import json
import re

import pytest

from bench import discover
from bench.run import ROOT

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w
               for w in MAN["command"])


@pytest.mark.parametrize("entry", MAN["configs"] + MAN["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
            assert "\t" not in entry[key]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_fields(m):
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    if m in MAN["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
    else:
        assert m["moves"] in {e["name"] for e in MAN["end_to_end"]}
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda c: c["name"])
def test_cell_finds_its_files_and_reports(cell):
    cfg = discover.config(MAN, cell)
    traffic = discover.traffic(cell["traffic"])
    assert (ROOT / "bench" / "entries" / f"{traffic['entry']}.py").is_file()
    assert traffic["limits"]
    e2e = {m["name"] for m in discover.end_to_end(MAN, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert discover.per_layer(MAN, cell)
    assert cell["chips"] in (1, 4)
    if traffic.get("n", cfg["n"]) != cfg["n"]:
        entry = next(c for c in MAN["configs"] if c["name"] == cell["config"])
        assert "n" in entry["reduced"] and "n" in cfg["reduced"]


@pytest.mark.parametrize("cfg", MAN["configs"], ids=lambda c: c["name"])
def test_config_reduced_keys_are_explained(cfg):
    body = json.loads((ROOT / cfg["file"]).read_text())
    assert set(cfg["reduced"]) == set(body["reduced"])
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
