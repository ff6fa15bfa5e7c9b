"""Find the benchmark's pieces by the names ``BENCHMARK.json`` gives.

Everything that belongs to one configuration, one traffic mix, one entry
point, one per-layer metric or one kernel's work count sits in a file of its
own under ``bench/``; adding one is adding a file and a manifest entry:

* ``configs/<config>.json``  the deployment (path given by the manifest);
* ``traffic/<traffic>.json`` the job mix: which entry, its arguments, n,
  and the limits of the comparison that decides ``correct``;
* ``entries/<entry>.py``     builds inputs and drives one program entry;
* ``metrics/<metric>.py``    one per-layer metric, ``read(ctx)``;
* ``kernels/<kernel>.py``    one kernel's trace names and work count.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def manifest(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def workload(man: dict, name: str) -> dict:
    for cell in man["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                   f"{[c['name'] for c in man['workloads']]})")


def config(man: dict, cell: dict, root: Path = ROOT) -> dict:
    for cfg in man["configs"]:
        if cfg["name"] == cell["config"]:
            with open(Path(root) / cfg["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration {cell['config']!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    with open(Path(root) / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def module(kind: str, name: str, root: Path = ROOT):
    """Load ``bench/<kind>/<name>.py`` (names may hold '.' and '-')."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernels(root: Path = ROOT) -> dict:
    """Every kernel work model under ``bench/kernels``, by file name."""
    return {p.stem: module("kernels", p.stem, root)
            for p in sorted((Path(root) / "bench" / "kernels").glob("*.py"))}


def per_layer(man: dict, cell: dict) -> list[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    mine = {m["name"] for m in end_to_end(man, cell)}
    return [m for m in man["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def end_to_end(man: dict, cell: dict) -> list[dict]:
    return [m for m in man["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]]
