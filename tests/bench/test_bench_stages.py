"""Device time put down to the program stage that launched it
(``bench/stages.py``): on the committed sparse fixture (no program spans),
on hand-made traces, and with the join refused when launches and modules
do not match.  The committed fixture's per-layer metrics and breakdown are
pinned here to the values the harness read before ``bench/stages.py``
existed."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import discover, stages as S, trace as tr
from bench.run import MetricContext, ROOT

SPARSE = Path(__file__).parent / "fixtures" / "sift65k-knn-sparse.xplane.pb"

# what bench/trace.py and the metrics read from SPARSE (13 jobs)
PINNED_METRICS = {
    "analysis.host_ms": 7.202658461538464,
    "device.idle_frac": 0.9441762964403484,
    "engine.dispatch_ms": 0.24010792307692266,
    "kernels.ms": 0.7206157692307685,
    "kernels_roofline": 0.9670875337250341,
    "pipeline.xla_ms": 0.6211963846153776,
}
PINNED_BREAKDOWN = {
    "device_ops": [
        ["topk_pallas.1", 0.0070231030000000375],
        ["knn_values_pallas.1", 0.0023449019999999543],
        ["mul.1", 0.0019635630000000084],
        ["pad.1", 0.0013588380000000913],
        ["fusion", 0.001322579999999969],
        ["copy.1", 0.0007303650000001008],
        ["reduce_sum.7", 0.0005990950000000564],
        ["copy", 0.0005718519999998589],
        ["copy-done", 0.0003137049999998795],
        ["sub.1", 0.00025135699999994515]],
    "idle_gaps": [
        ["job.communities", 0.00881406799999998],
        ["job.communities", 0.008750673000000014],
        ["job.communities", 0.00862263499999999],
        ["job.communities", 0.00861004],
        ["job.communities", 0.008500304000000014],
        ["job.communities", 0.008480860000000007],
        ["job.communities", 0.008396190000000026],
        ["job.communities", 0.008382622999999978],
        ["job.communities", 0.008365823999999994],
        ["job.communities", 0.00835841100000001]],
}


@pytest.fixture(scope="module")
def sparse():
    return S.read(SPARSE)


def _ctx(red, jobs, **work):
    dev = SimpleNamespace(device_kind="TPU v5 lite")
    return MetricContext(red, jobs, work, [dev], ROOT)


@pytest.mark.parametrize("name", sorted(PINNED_METRICS))
def test_pinned_metrics_of_the_committed_fixture(sparse, name):
    ctx = _ctx(sparse.base, 13, n=1024, d=128, k=32)
    assert discover.module("metrics", name).read(ctx) == PINNED_METRICS[name]


def test_pinned_breakdown_of_the_committed_fixture(sparse):
    assert sparse.base.breakdown() == PINNED_BREAKDOWN
    assert tr.reduce(SPARSE).breakdown() == PINNED_BREAKDOWN


def test_launches_join_modules_in_the_committed_fixture(sparse):
    jobs = len(sparse.base.spans_named("job.call"))
    assert jobs == 13 and sparse.why is None
    assert len(sparse.launches) == len(sparse.modules[0]) == 390
    ids = [m[3] for m in sparse.modules[0]]
    assert ids == sorted(set(ids))
    st = sparse.stages
    # recorded before the program had spans: every launch is unattributed
    assert sparse.program == [] and set(st.launches) == {S.UNATTRIBUTED}
    assert S.METRICS["pipeline.launches"](sparse, jobs) == 30.0
    assert sum(st.device_s.values()) == pytest.approx(
        sum(sparse.base.op_seconds().values()), rel=1e-12)


def test_idle_by_span_fills_the_idle_time(sparse):
    idle = sparse.idle_by_span()
    assert set(idle) <= {"job.call", "job.communities", tr.BETWEEN}
    want = sparse.base.window_s - sparse.base.busy_s(0)
    assert sum(idle.values()) == pytest.approx(want, rel=1e-9)
    assert sum(s for _, s in sparse.base.idle_gaps()) == pytest.approx(
        want, rel=1e-9)


def _hand_made(launches=(1.3, 2.1, 4.0, 9.5)):
    base = tr.Reduced(
        window=(0.0, 10.0),
        ops={0: [("pad.1", 1.4, 1.6, "xla"), ("topk_pallas.1", 2.3, 2.9,
                                               "kernel"),
                 ("copy", 2.9, 3.0, "xla"), ("mul", 4.1, 4.5, "xla"),
                 ("stray", 5.0, 5.1, "xla"), ("sum", 9.6, 9.8, "xla")]},
        spans=[("job.call", 0.5, 6.0), ("job.communities", 6.0, 9.0)])
    program = [("engine.execute", 1.0, 5.0), ("pipeline.pad", 1.2, 1.5),
               ("kernel.topk", 2.0, 2.2), ("analysis.strong_ties", 6.5, 7.5),
               ("analysis.components", 7.5, 8.5)]
    modules = {0: [("jit_pad", 1.4, 1.6, 7), ("jit_topk", 2.2, 3.0, 8),
                   ("jit_mul", 4.1, 4.5, 9), ("jit_sum", 9.6, 9.8, 10)]}
    return S.Trace(base=base, program=program, launches=list(launches),
                   modules=modules)


def test_hand_made_attribution():
    t = _hand_made()
    assert t.why is None
    # each launch goes to the innermost program span over it
    assert t.launch_stages() == ["pipeline.pad", "kernel.topk",
                                 "engine.execute", S.UNATTRIBUTED]
    st = t.stages
    assert st.launches == {"pipeline.pad": 1, "kernel.topk": 1,
                           "engine.execute": 1, S.UNATTRIBUTED: 1}
    assert st.device_s["kernel.topk"] == pytest.approx(0.7)
    assert st.device_s["engine.execute"] == pytest.approx(0.4)
    # "stray" runs in no module, "sum" in a module launched outside spans
    assert st.device_s[S.UNATTRIBUTED] == pytest.approx(0.3)
    assert sum(st.device_s.values()) == pytest.approx(
        sum(t.base.op_seconds().values()))
    # self time: engine.execute less its two children
    assert st.host_s["engine.execute"] == pytest.approx(4.0 - 0.3 - 0.2)
    assert t.program_seconds("analysis.components") == pytest.approx(1.0)
    assert t.program_seconds("pipeline.scatter_dense") is None
    table = st.table(jobs=2)
    assert table["kernel.topk"]["device_ms"] == pytest.approx(350.0)
    assert table["kernel.topk"]["top_ops"][0][0] == "topk_pallas.1"
    assert table[S.UNATTRIBUTED]["host_ms"] is None


def test_hand_made_idle_by_span():
    t = _hand_made()
    # gaps 0-1.4, 1.6-2.3, 3.0-4.1, 4.5-5.0, 5.1-9.6 and 9.8-10, each split
    # over the innermost span of each piece
    want = {tr.BETWEEN: 0.5 + 0.6 + 0.2, "job.call": 0.5 + 0.9,
            "engine.execute": 0.2 + 0.4 + 0.1 + 1.1 + 0.5,
            "pipeline.pad": 0.2, "kernel.topk": 0.2,
            "job.communities": 0.5 + 0.5, "analysis.strong_ties": 1.0,
            "analysis.components": 1.0}
    idle = t.idle_by_span()
    assert set(idle) == set(want)
    for k, v in want.items():
        assert idle[k] == pytest.approx(v), k
    assert sum(idle.values()) == pytest.approx(
        t.base.window_s - t.base.busy_s(0))


@pytest.mark.parametrize("change", ["one launch less", "one launch more",
                                    "run_ids out of order"])
def test_no_join_when_launches_and_modules_differ(change):
    t = _hand_made()
    if change == "one launch less":
        t = _hand_made(launches=(1.3, 2.1, 4.0))
    elif change == "one launch more":
        t = _hand_made(launches=(0.2, 1.3, 2.1, 4.0, 9.5))
    else:
        mods = list(t.modules[0])
        mods[1] = mods[1][:3] + (99,)
        t = dataclasses.replace(t, modules={0: mods})
    assert t.why
    assert t.launch_stages() is None and t.stages is None
    for name in ("pipeline.gather_ms", "pipeline.scatter_ms",
                 "pipeline.launches"):
        assert S.METRICS[name](t, 2) is None
    # the host spans do not need the join
    assert S.METRICS["analysis.ties_ms"](t, 2) == pytest.approx(500.0)
    head = S.lines(t, 2)[0]
    assert head["stages"] is None and head["launch_join"] == t.why


def test_no_join_in_the_committed_fixture_with_one_launch_dropped(sparse):
    t = dataclasses.replace(sparse, launches=sparse.launches[1:])
    assert t.why == "device 0: 390 modules against 389 launches"
    assert t.stages is None
    assert S.METRICS["pipeline.launches"](t, 13) is None


@pytest.mark.parametrize("name,want", [
    ("engine.plan", True), ("kernel.focus_tri", True),
    ("analysis.components", True), ("resilience.step", True),
    ("job.call", False), ("window", False), ("engine", False),
    ("PjitFunction(_pad)", False), ("tpu::System::Execute", False)])
def test_program_span_names(name, want):
    assert S.is_program_span(name) is want


# -- the stage fixtures: recorded on a TPU v5e with the program's spans
# (tests/bench/fixtures/record_stages.py)
DENSE = Path(__file__).parent / "fixtures" / "grqc-dense-tri-512.xplane.pb"
FACADE = Path(__file__).parent / "fixtures" / "sift16k-knn-facade-1024.xplane.pb"


@pytest.fixture(scope="module")
def dense():
    return S.read(DENSE)


@pytest.fixture(scope="module")
def facade():
    return S.read(FACADE)


def _jobs(t):
    return len(t.base.spans_named("job.call"))


@pytest.mark.parametrize("which", ["dense", "facade"])
def test_stage_fixture_joins_and_adds_up(request, which):
    path = DENSE if which == "dense" else FACADE
    t = request.getfixturevalue(which)
    assert path.stat().st_size < 1 << 20
    assert t.why is None and len(t.launches) == len(t.modules[0])
    st = t.stages
    # every launch of the program's job ran inside one of its spans
    assert S.UNATTRIBUTED not in st.launches
    assert sum(st.launches.values()) == len(t.launches)
    assert sum(st.device_s.values()) == pytest.approx(
        sum(t.base.op_seconds().values()), rel=1e-12)
    assert sum(t.idle_by_span().values()) == pytest.approx(
        t.base.window_s - t.base.busy_s(0), rel=1e-9)
    # host self times add up to the time inside the outermost spans
    outer = sum(e - s for n, s, e in t.program
                if n in ("engine.plan", "engine.validate", "engine.execute",
                         "analysis.strong_ties", "analysis.components"))
    assert sum(st.host_s.values()) == pytest.approx(outer, rel=1e-9)


def _op_s(t, prefix):
    return sum(s for n, s in t.base.op_seconds().items()
               if n.startswith(prefix))


def test_dense_fixture_stages(dense):
    jobs = _jobs(dense)
    assert jobs == 15
    assert {n for n, _, _ in dense.program} == {
        "engine.plan", "engine.validate", "engine.execute", "pipeline.pad",
        "kernel.focus_tri", "pipeline.weights", "kernel.cohesion_tri",
        "pipeline.finish", "analysis.strong_ties", "analysis.components"}
    st = dense.stages
    per_job = {k: v / jobs for k, v in st.launches.items()}
    assert per_job == {"engine.validate": 1, "kernel.focus_tri": 1,
                       "pipeline.weights": 11, "kernel.cohesion_tri": 1,
                       "pipeline.finish": 1}
    # the cohesion program is the kernel and the sum of its two halves
    assert st.device_s["kernel.cohesion_tri"] == pytest.approx(
        _op_s(dense, "cohesion_tri_pallas"), rel=0.01)
    # the focus program also mirrors the packed blocks into U
    assert st.ops["kernel.focus_tri"]["focus_tri_pallas.1"] == pytest.approx(
        _op_s(dense, "focus_tri_pallas"), rel=1e-12)
    got = {k: f(dense, jobs) for k, f in S.METRICS.items()}
    assert got["pipeline.launches"] == 15.0
    assert got["pipeline.gather_ms"] is None
    assert got["pipeline.scatter_ms"] is None
    for k in ("engine.plan_ms", "engine.validate_ms", "analysis.ties_ms",
              "analysis.components_ms"):
        assert got[k] > 0, k
    host_ms = discover.module("metrics", "analysis.host_ms").read(
        _ctx(dense.base, jobs, n=512))
    assert got["analysis.ties_ms"] + got["analysis.components_ms"] <= host_ms


def test_facade_fixture_stages(facade):
    jobs = _jobs(facade)
    st = facade.stages
    assert {k: v / jobs for k, v in st.launches.items()} == {
        "pipeline.pad": 1, "kernel.topk": 1, "pipeline.gather_cube": 25,
        "kernel.knn_values": 1, "pipeline.finish": 2,
        "pipeline.scatter_dense": 31}
    assert st.device_s["kernel.topk"] == pytest.approx(
        _op_s(facade, "topk_pallas"), rel=0.01)
    assert st.device_s["kernel.knn_values"] == pytest.approx(
        _op_s(facade, "knn_values_pallas"), rel=0.01)
    got = {k: f(facade, jobs) for k, f in S.METRICS.items()}
    assert got["pipeline.launches"] == 61.0
    assert got["pipeline.gather_ms"] > 0 and got["pipeline.scatter_ms"] > 0
    assert got["engine.plan_ms"] > 0
    assert got["analysis.ties_ms"] is None
    assert got["analysis.components_ms"] is None
    lines = S.lines(facade, jobs)
    assert [next(iter(ln)) for ln in lines] == ["stages", "idle_by_span",
                                                "stage_metrics"]
    assert json.loads(json.dumps(lines)) == lines


def test_stages_command_needs_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/stages.py", "--workload", "grqc-dense-tri",
         "--seed", "4294967311", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 3, r.stderr[-2000:]
    assert '"stages"' not in r.stdout
