#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration and its traffic
mix are found by name from ``BENCHMARK.json`` (see ``bench/discover.py``).
A run builds the cell's inputs from the seed, warms up the cell's own
shapes, runs whole jobs back to back until ``--seconds`` have passed, then
compares the kept answers with the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, ``breakdown`` (traced runs) and, last,
``checks``: each number compared, with its limit.  The same numbers close
standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR``, or
``<checkout>/.jax_cache``; the program's tuning cache is a fresh empty file
in a temporary directory, so tiles resolve as on a fresh install.
"""
from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

GIB = float(1 << 30)


def process_age() -> float:
    """Seconds since this process started (import time where /proc is
    missing)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def prepare() -> str:
    """Pin the caches before anything compiles: JAX's compile cache at
    ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``, every
    program cached; the program's tuning cache a fresh empty file.
    Returns the tuning cache's temporary directory, for the caller to
    remove."""
    tune_dir = tempfile.mkdtemp(prefix="bench_tune_")
    os.environ["REPRO_TUNE_CACHE"] = str(Path(tune_dir) / "blocktune.json")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import repro  # noqa: F401  - the system under test must be here
    return tune_dir


def accelerator(chips: int):
    """The first ``chips`` TPU devices, or None."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench/run.py: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s). No CPU fallback.",
              file=sys.stderr)
        return None
    return devs[:chips]


class Compiles:
    """Counts backend compiles while armed."""

    def __init__(self):
        self.armed, self.count = False, 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _span(on: bool, name: str):
    import jax

    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def run_cell(man: dict, cell: dict, seed: int, seconds: float, trace: bool,
             devices, *, root: Path = ROOT, n: int | None = None,
             log=print, trace_dir: str | None = None) -> dict:
    """One run of ``cell``: set-up, window, comparison.  Returns the result
    object; ``log`` gets the earlier lines.  ``n`` overrides the problem
    size (tests), ``trace_dir`` keeps the raw trace there."""
    import jax

    from bench import discover, trace as tr
    from bench.job import Context

    cfg = discover.config(man, cell, root)
    traffic = discover.traffic(cell["traffic"], root)
    entry = discover.module("entries", traffic["entry"], root)
    limits = traffic["limits"]
    ctx = Context(config=cfg, traffic=traffic, seed=seed, devices=devices,
                  n=n or traffic.get("n", cfg["n"]))
    job = entry.build(ctx)
    log(json.dumps({"cell": cell["name"], "n": ctx.n, "seed": seed,
                    "device_kind": devices[0].device_kind,
                    "plan": job.explain()}, default=str))

    out = jax.block_until_ready(job.call())            # warm-up
    job.warm(out, job.post(out))
    del out
    setup_s = process_age()

    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    tdir = trace_dir or (tempfile.mkdtemp(prefix="bench_trace_")
                         if trace else None)
    attempted = failed = 0
    error = None
    if trace:
        # harness spans and device ops only: no Python call tracing
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    compiles.armed = True
    with _span(trace, "window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            attempted += 1
            try:
                with _span(trace, "job.call"):
                    out = jax.block_until_ready(job.call())
                host = None
                if job.has_post:
                    with _span(trace, "job.communities"):
                        host = job.post(out)
                job.keep(attempted - 1, out, host)
                del out, host
            except Exception:  # noqa: BLE001 - a failed job is counted
                failed += 1
                error = traceback.format_exc()
                break
        window_s = time.perf_counter() - t0
    compiles.armed = False
    if trace:
        jax.profiler.stop_trace()
    completed = attempted - failed
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    kept = job.collect() if completed else []
    job.release()
    if error:
        print(error, file=sys.stderr)
    numbers = job.compare(kept, job.reference()) if completed else {}
    checks = {k: {"value": numbers.get(k), "limit": v}
              for k, v in limits.items()}
    correct = (completed > 0 and failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values()))

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    log(json.dumps({"jobs": completed, "window_s": window_s,
                    "compiles_in_window": compiles.count,
                    "device_kind": device["kind"]}))
    if trace:
        paths = list(Path(tdir).rglob("*.xplane.pb"))
        red = tr.reduce(paths[0])
        if not trace_dir:
            shutil.rmtree(tdir, ignore_errors=True)
        mctx = MetricContext(red, completed, job.work, devices, root)
        log(json.dumps({"kernels": mctx.kernel_rows(),
                        "device_kind": device["kind"]}))
        metrics = {}
        for m in discover.per_layer(man, cell):
            value = discover.module("metrics", m["name"], root).read(mctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red.mean_busy_s(), window_s=red.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown=red.breakdown())
    else:
        values = {"setup_s": setup_s, "solve_s": window_s / max(completed, 1),
                  "peak_hbm_gib": peak / GIB}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in discover.end_to_end(man, cell)}
        result.update(metrics=metrics, device=device)
    result["checks"] = checks
    return result


class MetricContext:
    """What a per-layer metric's ``read(ctx)`` may use: the reduced trace,
    the jobs it covers, the cell's sizes, the peaks and the kernels' work
    counts."""

    def __init__(self, reduced, jobs: int, work: dict, devices, root: Path):
        from bench import discover

        self.trace = reduced
        self.jobs = jobs
        self.work = work
        self.devices = devices
        with open(Path(root) / "bench" / "peaks.json") as f:
            self.peaks = json.load(f).get(devices[0].device_kind)
        self._kernels = discover.kernels(root)

    def kernel_seconds(self, mod) -> float:
        """Device seconds in ``mod``'s kernel over the window, averaged
        over devices."""
        tot = sum(s for name, s in self.trace.op_seconds().items()
                  if any(m in name for m in mod.MATCH))
        return tot / max(len(self.trace.ops), 1)

    def kernel_rows(self) -> list[dict]:
        """Per kernel that ran: device ms per job, the least ms the chip
        could take for its work, the share, and which term bounds it."""
        rows = []
        for name, mod in self._kernels.items():
            secs = self.kernel_seconds(mod)
            if secs <= 0 or not self.jobs:
                continue
            if self.peaks is None:
                raise KeyError(f"no peaks for {self.devices[0].device_kind!r}"
                               " in bench/peaks.json")
            w = mod.work(**self.work)
            terms = {"mxu": w.get("mxu_flops", 0) / self.peaks["mxu_flops_per_s"],
                     "vpu": w.get("vpu_ops", 0) / self.peaks["vpu_ops_per_s"],
                     "hbm": w.get("bytes", 0) / self.peaks["hbm_bytes_per_s"]}
            bound = max(terms, key=terms.get)
            per_job = secs / self.jobs
            rows.append({"kernel": name, "ms_per_job": per_job * 1e3,
                         "least_ms": terms[bound] * 1e3,
                         "roofline_pct": 100 * terms[bound] / per_job,
                         "bound": bound})
        return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import discover

    man = discover.manifest(ROOT)
    cell = discover.workload(man, args.workload)
    tune_dir = prepare()
    devices = accelerator(cell["chips"])
    if devices is None:
        return 3
    with open(ROOT / "bench" / "peaks.json") as f:
        if devices[0].device_kind not in json.load(f):
            print(f"bench/run.py: device kind {devices[0].device_kind!r} is "
                  "not in bench/peaks.json", file=sys.stderr)
            return 3
    try:
        result = run_cell(man, cell, args.seed, args.seconds,
                          bool(args.trace), devices,
                          log=lambda s: print(s, flush=True))
    finally:
        shutil.rmtree(tune_dir, ignore_errors=True)
    for name, c in result["checks"].items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print(f"check {name}: {c['value']!r} <= {c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
