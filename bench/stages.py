#!/usr/bin/env python3
"""Device time put down to the program stage that launched it.

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s>

runs one cell with the trace on, through ``bench.run.run_cell``, and prints
its lines, then ``{"stages": ...}``, ``{"idle_by_span": ...}`` and
``{"stage_metrics": ...}``, and last the run's result.  Without a TPU it
exits 3, as ``bench/run.py`` does.

The program marks its stages with profiler spans named ``<layer>.<stage>``
(``engine.plan``, ``pipeline.pad``, ``kernel.topk``,
``analysis.components``, ...).  Besides what ``bench/trace.py`` reduces, a
traced run's ``.xplane.pb`` holds the host thread's
``PJRT_LoadedExecutable_Execute linkage`` event for every device program
launched (host clock), and each device's ``XLA Modules`` line, one event
per program run with a ``run_id`` that rises with every run (device
clock).  Programs run on a device in the order they were launched, so the
k-th launch is the k-th module there; each module's operations then belong
to the innermost program span that held the launch.  Where the counts
differ, or the ``run_id``s do not rise, nothing is guessed: ``stages`` is
None and ``why`` says what did not match.  The join holds for programs that
run on every device they are launched to; a trace with single-device
programs on a multi-device mesh does not join.

The device's clock and the host's differ by up to a millisecond in a
trace, so the join goes by order and never by time.  Idle gaps are the one
place where the two clocks meet (a device gap against the host span over
it, as in ``bench/trace.py``); gaps shorter than that skew may take a
neighbour's label.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import trace as tr  # noqa: E402

LAYERS = ("engine", "resilience", "pipeline", "kernel", "analysis")
MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute linkage"
UNATTRIBUTED = "unattributed"


def is_program_span(name: str) -> bool:
    layer, dot, stage = name.partition(".")
    return bool(dot and stage) and layer in LAYERS


@dataclasses.dataclass
class Stages:
    """Totals over the traced window, per program span name: host self
    seconds (the span less the program spans directly inside it), device
    seconds of the programs launched under it as the innermost span
    (averaged over devices) and launches.  ``UNATTRIBUTED`` holds the
    device time and launches outside every program span, and operations
    outside every module.  ``ops`` holds, per stage, the device seconds of
    each operation name."""
    host_s: collections.Counter
    device_s: collections.Counter
    launches: collections.Counter
    ops: dict[str, collections.Counter]

    def table(self, jobs: int, top: int = 3) -> dict:
        """Per span name: host ms, device ms and launches per job, and the
        stage's ``top`` operations by device ms per job."""
        jobs = max(jobs, 1)
        names = sorted(set(self.host_s) | set(self.device_s)
                       | set(self.launches))
        none = collections.Counter()
        return {n: {"host_ms": (1e3 * self.host_s[n] / jobs
                                if n in self.host_s else None),
                    "device_ms": 1e3 * self.device_s[n] / jobs,
                    "launches": self.launches[n] / jobs,
                    "top_ops": [[op, 1e3 * s / jobs] for op, s in
                                self.ops.get(n, none).most_common(top)]}
                for n in names}


@dataclasses.dataclass
class Trace:
    base: tr.Reduced
    # the program's spans (name, start_s, end_s) starting in the window,
    # in start order
    program: list[tuple[str, float, float]]
    # host start of every launch in the trace, in order
    launches: list[float]
    # per device, (name, start_s, end_s, run_id) of every module, in order
    modules: dict[int, list[tuple[str, float, float, int]]]

    # -- host timeline --------------------------------------------------
    def _segments(self, harness: bool) -> list[tuple[float, float, str]]:
        """Disjoint (start, end, label) pieces of the host timeline: the
        innermost program span, else (``harness``) the harness's job span,
        else nothing (``BETWEEN`` with ``harness``)."""
        spans = [(s, e, n, 1) for n, s, e in self.program]
        if harness:
            spans += [(s, e, n, 0) for n, s, e in self.base.spans
                      if n in tr.JOB_SPANS]
        cuts = sorted({t for s, e, _, _ in spans for t in (s, e)})
        starts = sorted(range(len(spans)), key=lambda i: spans[i][0])
        active: list[int] = []
        segs, j = [], 0
        for a, b in zip(cuts, cuts[1:]):
            while j < len(starts) and spans[starts[j]][0] <= a:
                active.append(starts[j])
                j += 1
            active = [i for i in active if spans[i][1] > a]
            if active:
                # program spans over harness spans, then the latest start
                i = max(active, key=lambda i: (spans[i][3], spans[i][0],
                                               -spans[i][1]))
                segs.append((a, b, spans[i][2]))
            elif harness:
                segs.append((a, b, tr.BETWEEN))
        return segs

    def program_seconds(self, name: str) -> float | None:
        """Host seconds in the program spans named ``name``, or None."""
        durs = [e - s for n, s, e in self.program if n == name]
        return sum(durs) if durs else None

    def self_seconds(self) -> collections.Counter:
        """Host self seconds per program span name."""
        out: collections.Counter = collections.Counter()
        stack: list[tuple[str, float, float]] = []
        for sp in sorted(self.program, key=lambda sp: (sp[1], -sp[2])):
            while stack and stack[-1][2] <= sp[1]:
                stack.pop()
            out[sp[0]] += sp[2] - sp[1]
            if stack and sp[2] <= stack[-1][2]:
                out[stack[-1][0]] -= sp[2] - sp[1]
            stack.append(sp)
        return out

    # -- the launch join -----------------------------------------------
    @property
    def why(self) -> str | None:
        """Why the launches and the modules do not join, or None."""
        for dev, mods in sorted(self.modules.items()):
            if len(mods) != len(self.launches):
                return (f"device {dev}: {len(mods)} modules against "
                        f"{len(self.launches)} launches")
            ids = [m[3] for m in mods]
            if any(b <= a for a, b in zip(ids, ids[1:])):
                return f"device {dev}: run_ids do not rise with start time"
        if not self.modules:
            return "no modules in the trace"
        return None

    def launch_stages(self) -> list[str] | None:
        """The innermost program span of each launch (``UNATTRIBUTED``
        outside every one), or None when the join does not hold."""
        if self.why is not None:
            return None
        segs = self._segments(harness=False)
        starts = [s for s, _, _ in segs]
        out = []
        for t in self.launches:
            i = bisect.bisect_right(starts, t) - 1
            out.append(segs[i][2] if i >= 0 and t < segs[i][1]
                       else UNATTRIBUTED)
        return out

    @functools.cached_property
    def stages(self) -> Stages | None:
        """The window's totals by stage, or None when the join does not
        hold."""
        labels = self.launch_stages()
        if labels is None:
            return None
        lo, hi = self.base.window
        launches = collections.Counter(
            lab for t, lab in zip(self.launches, labels) if lo <= t < hi)
        ndev = max(len(self.base.ops), 1)
        device_s: collections.Counter = collections.Counter()
        ops: dict[str, collections.Counter] = {}
        for dev, dev_ops in self.base.ops.items():
            mods = self.modules.get(dev, [])
            starts = [m[1] for m in mods]
            for name, s, e, _ in dev_ops:
                i = bisect.bisect_right(starts, s) - 1
                stage = (labels[i] if i >= 0 and s <= mods[i][2]
                         else UNATTRIBUTED)
                device_s[stage] += (e - s) / ndev
                ops.setdefault(stage, collections.Counter())[name] += (
                    (e - s) / ndev)
        return Stages(host_s=self.self_seconds(), device_s=device_s,
                      launches=launches, ops=ops)

    # -- idle time --------------------------------------------------------
    def gaps(self) -> list[tuple[float, float]]:
        """(start, end) of each idle gap of the first device in the window,
        as ``Reduced.idle_gaps`` finds them."""
        lo, hi = self.base.window
        device = min(self.base.ops, default=0)
        gaps, t = [], lo
        for s, e in self.base.busy_intervals(device) + [(hi, hi)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        return gaps

    def idle_by_span(self) -> collections.Counter:
        """Idle seconds of the first device, by the innermost span over
        each piece of each gap: a program span, else the harness's job
        span, else ``between_jobs``."""
        segs = self._segments(harness=True)
        out: collections.Counter = collections.Counter()
        j = 0
        for a, b in self.gaps():
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            k, t = j, a
            while t < b:
                if k < len(segs) and segs[k][0] <= t:
                    end = min(b, segs[k][1])
                    out[segs[k][2]] += end - t
                    t, k = end, k + 1
                else:   # before the next span, or past the last one
                    end = min(b, segs[k][0]) if k < len(segs) else b
                    out[tr.BETWEEN] += end - t
                    t = end
        return out


def read(path) -> Trace:
    """Reduce one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    base = tr.reduce(path)
    lo, hi = base.window
    program: list[tuple[str, float, float]] = []
    launches: list[float] = []
    modules: dict[int, list[tuple[str, float, float, int]]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            dev = int(plane.name[len(tr.DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(
                        (e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                         next((int(v) for k, v in e.stats
                               if k == "run_id"), -1))
                        for e in line.events)
        elif plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == LAUNCH:
                        launches.append(e.start_ns * 1e-9)
                    elif (is_program_span(e.name)
                          and lo <= e.start_ns * 1e-9 < hi):
                        program.append((e.name, e.start_ns * 1e-9,
                                        e.end_ns * 1e-9))
    for mods in modules.values():
        mods.sort(key=lambda m: m[1])
    return Trace(base=base, program=sorted(program, key=lambda sp: sp[1]),
                 launches=sorted(launches), modules=modules)


# -- what the per-layer metrics of the program's stages read -------------
def _host_ms(span: str):
    def read_(t: Trace, jobs: int):
        secs = t.program_seconds(span)
        return None if secs is None or not jobs else 1e3 * secs / jobs
    return read_


def _device_ms(stage: str):
    def read_(t: Trace, jobs: int):
        st = t.stages
        if st is None or not jobs or not st.launches[stage]:
            return None
        return 1e3 * st.device_s[stage] / jobs
    return read_


def _launches(t: Trace, jobs: int):
    st = t.stages
    return None if st is None or not jobs else sum(st.launches.values()) / jobs


METRICS = {
    "engine.plan_ms": _host_ms("engine.plan"),
    "engine.validate_ms": _host_ms("engine.validate"),
    "analysis.ties_ms": _host_ms("analysis.strong_ties"),
    "analysis.components_ms": _host_ms("analysis.components"),
    "pipeline.gather_ms": _device_ms("pipeline.gather_cube"),
    "pipeline.scatter_ms": _device_ms("pipeline.scatter_dense"),
    "pipeline.launches": _launches,
}


def lines(t: Trace, jobs: int) -> list[dict]:
    """The three lines a traced run prints about the program's stages."""
    st = t.stages
    per_job = max(jobs, 1)
    return [
        {"stages": None if st is None else st.table(jobs), "jobs": jobs,
         "launch_join": t.why or "ok"},
        {"idle_by_span": {k: v / per_job for k, v in
                          sorted(t.idle_by_span().items())}},
        {"stage_metrics": {k: f(t, jobs) for k, f in METRICS.items()}},
    ]


def main(argv=None) -> int:
    import argparse
    import shutil
    import tempfile

    from bench import discover, run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    man = discover.manifest(ROOT)
    cell = discover.workload(man, args.workload)
    tune_dir = run.prepare()
    devices = run.accelerator(cell["chips"])
    if devices is None:
        return 3
    tdir = tempfile.mkdtemp(prefix="bench_stages_")
    jobs = {}

    def log(s: str) -> None:
        print(s, flush=True)
        jobs.update(json.loads(s))

    try:
        result = run.run_cell(man, cell, args.seed, args.seconds, True,
                              devices, trace_dir=tdir, log=log)
        t = read(next(Path(tdir).rglob("*.xplane.pb")))
        for line in lines(t, jobs["jobs"]):
            print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
        shutil.rmtree(tune_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
