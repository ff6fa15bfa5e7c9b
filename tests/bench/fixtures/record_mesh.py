#!/usr/bin/env python3
"""Record the mesh trace fixture of tests/bench on a four-chip host.

    python3 tests/bench/fixtures/record_mesh.py

Runs the four-chip k-NN cell at n = 4,096 for a fraction of a second with
the trace on, through ``bench.run.run_cell``, and copies the raw
``.xplane.pb`` next to this file as ``<cell>-<n>.xplane.pb``.
"""
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
for _p in (ROOT / "src", ROOT):
    sys.path.insert(0, str(_p))

CELL, N, SEED, SECONDS = "sift262k-knn-mesh4", 4096, 14, 0.2


def main() -> int:
    from bench import discover, run

    tune_dir = run.prepare()
    man = discover.manifest(ROOT)
    cell = discover.workload(man, CELL)
    devices = run.accelerator(cell["chips"])
    if devices is None:
        return 3
    with tempfile.TemporaryDirectory() as tdir:
        r = run.run_cell(man, cell, SEED, SECONDS, True, devices, n=N,
                         trace_dir=tdir)
        print(json.dumps(r))
        shutil.copy(next(Path(tdir).rglob("*.xplane.pb")),
                    HERE / f"{CELL}-{N}.xplane.pb")
    shutil.rmtree(tune_dir, ignore_errors=True)
    return 0 if r["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
