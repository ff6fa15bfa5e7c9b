#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,... [--control-seeds 1,2,3]

For each seed, in one process: build the cell's inputs, run its job through
the same entry and compiled programs the window drives (as many jobs as it
takes to reach the seed's drawn sample), and compare the kept answers with
the float32 reference: the program's reading.  For each control seed, put
the reference computed in bfloat16 in the program's place and compare it
the same way: the control's reading.  Prints one JSON line per seed, then
the largest program reading and the smallest control reading of each
number.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def readings(man: dict, cell: dict, seed: int, control: bool, devices, *,
             root: Path = ROOT, n: int | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import discover
    from bench.job import Context

    cfg = discover.config(man, cell, root)
    traffic = discover.traffic(cell["traffic"], root)
    entry = discover.module("entries", traffic["entry"], root)
    job = entry.build(Context(config=cfg, traffic=traffic, seed=seed,
                              devices=devices,
                              n=n or traffic.get("n", cfg["n"])))
    for i in range(job.sample + 1):
        out = jax.block_until_ready(job.call())
        host = job.post(out) if job.has_post else None
        job.warm(out, host)
        job.keep(i, out, host)
        del out, host
    kept = job.collect()
    job.release()
    ref = job.reference(jnp.float32)
    row = {"seed": seed, "program": job.compare(kept, ref)}
    if control:
        row["control"] = job.compare(job.as_kept(job.reference(jnp.bfloat16)),
                                     ref)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    import shutil

    import jax

    from bench import discover
    from bench.run import accelerator, prepare

    man = discover.manifest(ROOT)
    cell = discover.workload(man, args.workload)
    tune_dir = prepare()
    devices = accelerator(cell["chips"])
    if devices is None:
        shutil.rmtree(tune_dir, ignore_errors=True)
        return 3
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for s in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(man, cell, s, s in ctrl, devices))
        print(json.dumps(rows[-1]), flush=True)
    summary = {"device_kind": jax.devices()[0].device_kind, "seeds": len(rows)}
    for side, pick in (("program", max), ("control", min)):
        got = [r[side] for r in rows if side in r]
        if got:
            summary[side] = {k: pick(g[k] for g in got) for k in got[0]}
    print(json.dumps(summary), flush=True)
    shutil.rmtree(tune_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
