#!/usr/bin/env python3
"""Record the trace fixture of tests/bench on a chip.

    python3 tests/bench/fixtures/record.py

Runs the sparse k-NN cell at n = 1,024 for 0.3 s with the trace on, through
``bench.run.run_cell``, and copies the raw ``.xplane.pb`` next to this file.
"""
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
for _p in (ROOT / "src", ROOT):
    sys.path.insert(0, str(_p))

CELL, N, SEED, SECONDS = "sift65k-knn-sparse", 1024, 11, 0.3


def main() -> int:
    from bench import discover, run

    tune_dir = run.prepare()
    devices = run.accelerator(1)
    if devices is None:
        return 3
    man = discover.manifest(ROOT)
    with tempfile.TemporaryDirectory() as tdir:
        r = run.run_cell(man, discover.workload(man, CELL), SEED, SECONDS,
                         True, devices, n=N, trace_dir=tdir)
        print(r)
        shutil.copy(next(Path(tdir).rglob("*.xplane.pb")),
                    HERE / f"{CELL}.xplane.pb")
    shutil.rmtree(tune_dir, ignore_errors=True)
    return 0 if r["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
