#!/usr/bin/env python3
"""Record the stage fixtures of tests/bench on a chip: traces that hold the
program's own spans, for ``bench/stages.py``.

    python3 tests/bench/fixtures/record_stages.py

Runs the dense cell at n = 512 and the feature facade at n = 1,024 for a
fraction of a second each with the trace on, through
``bench.run.run_cell``, copies each raw ``.xplane.pb`` next to this file as
``<cell>-<n>.xplane.pb``, and prints the stage lines of each.
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

# cell: (n, seconds, seed)
CELLS = {"grqc-dense-tri": (512, 0.15, 12),
         "sift16k-knn-facade": (1024, 0.05, 13)}


def main() -> int:
    from bench import discover, run, stages

    tune_dir = run.prepare()
    devices = run.accelerator(1)
    if devices is None:
        return 3
    man = discover.manifest(ROOT)
    ok = True
    for cell, (n, seconds, seed) in CELLS.items():
        with tempfile.TemporaryDirectory() as tdir:
            r = run.run_cell(man, discover.workload(man, cell), seed, seconds,
                             True, devices, n=n, trace_dir=tdir)
            print(json.dumps(r))
            out = HERE / f"{cell}-{n}.xplane.pb"
            shutil.copy(next(Path(tdir).rglob("*.xplane.pb")), out)
        t = stages.read(out)
        for line in stages.lines(t, len(t.base.spans_named("job.call"))):
            print(json.dumps(line))
        ok = ok and r["correct"]
    shutil.rmtree(tune_dir, ignore_errors=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
