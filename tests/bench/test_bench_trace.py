"""The trace reduction, on a small trace recorded on a TPU v5e (the
sparse k-NN cell at n = 1,024, a few jobs) and on hand-made intervals."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import discover, trace as tr
from bench.run import MetricContext, ROOT

FIXTURE = Path(__file__).parent / "fixtures" / "sift65k-knn-sparse.xplane.pb"
# the four-chip k-NN cell at n = 4,096, recorded by fixtures/record_mesh.py
MESH = Path(__file__).parent / "fixtures" / "sift262k-knn-mesh4-4096.xplane.pb"
LABELS = {"job.call", "job.communities", tr.BETWEEN}


@pytest.fixture(scope="module")
def red():
    return tr.reduce(FIXTURE)


def _raw_ops():
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(FIXTURE)).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    out.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events)
    return out


def test_fixture_is_small_and_has_the_harness_spans(red):
    assert FIXTURE.stat().st_size < 1 << 20
    names = {n for n, _, _ in red.spans}
    assert {"job.call", "job.communities"} <= names
    assert red.window_s > 0 and list(red.ops) == [0]


def test_busy_is_the_union_of_op_intervals(red):
    lo, hi = red.window
    events = sorted((max(s, lo), min(e, hi)) for _, s, e in _raw_ops()
                    if min(e, hi) > max(s, lo))
    union, end = 0.0, lo
    for s, e in events:
        if e > end:
            union += e - max(s, end)
            end = e
    assert red.busy_s(0) == pytest.approx(union, rel=1e-9)
    assert 0 < red.busy_s(0) <= red.window_s


def test_per_op_sums(red):
    want = {}
    for name, s, e in _raw_ops():
        short = tr.op_kind(name)[0]
        want[short] = want.get(short, 0.0) + e - s
    got = red.op_seconds()
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9)
    kernels = set(red.op_seconds("kernel"))
    assert any(k.startswith("topk_pallas") for k in kernels)
    assert any(k.startswith("knn_values_pallas") for k in kernels)


def test_gaps_are_labelled_and_fill_the_window(red):
    gaps = red.idle_gaps()
    assert gaps and {g for g, _ in gaps} <= LABELS
    assert "job.communities" in {g for g, _ in gaps}
    assert sum(s for _, s in gaps) + red.busy_s(0) == pytest.approx(
        red.window_s, rel=1e-9)


def test_breakdown_shape(red):
    b = red.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in b[key])
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_hand_made_intervals():
    red = tr.Reduced(
        window=(0.0, 10.0),
        ops={0: [("a.1", 1.0, 3.0, "kernel"), ("b", 2.0, 4.0, "xla"),
                 ("a.1", 6.0, 7.0, "kernel"), ("c", 9.5, 12.0, "collective")]},
        spans=[("job.call", 0.5, 5.0), ("job.communities", 5.0, 8.0)])
    assert red.busy_intervals(0) == [(1.0, 4.0), (6.0, 7.0), (9.5, 10.0)]
    assert red.busy_s(0) == pytest.approx(4.5)
    # each gap takes the label of the span around its midpoint
    assert red.idle_gaps() == [("between_jobs", 2.5),
                               ("job.communities", 2.0), ("job.call", 1.0)]
    assert red.op_seconds("kernel") == {"a.1": 3.0}
    assert red.op_seconds()["c"] == pytest.approx(2.5)


@pytest.mark.parametrize("long,want", [
    ('%topk_pallas.1 = (f32[8,128]{1,0}, s32[8,128]{1,0}) custom-call(f32[8,'
     '128]{1,0} %X.1), custom_call_target="tpu_custom_call"',
     ("topk_pallas.1", "kernel")),
    ("%all-gather-start.2 = (f32[4,128]{1,0:T(8,128)}, f32[16,128]{1,0}) "
     "all-gather-start(f32[4,128]{1,0:T(8,128)} %p), dimensions={0}",
     ("all-gather-start.2", "collective")),
    ("%fusion.3 = f32[64]{0:T(1024)S(1)} fusion(f32[64]{0} %a), kind=kLoop",
     ("fusion.3", "xla")),
])
def test_op_kind(long, want):
    assert tr.op_kind(long) == want


def test_metrics_read_from_the_fixture(red):
    man = discover.manifest()
    cell = discover.workload(man, "sift65k-knn-sparse")
    jobs = len(red.spans_named("job.call"))
    dev = SimpleNamespace(device_kind="TPU v5 lite")
    ctx = MetricContext(red, jobs, dict(n=1024, d=128, k=32), [dev], ROOT)
    got = {m["name"]: discover.module("metrics", m["name"]).read(ctx)
           for m in discover.per_layer(man, cell)}
    assert 0 < got["device.idle_frac"] < 1
    busy_ms = 1e3 * red.busy_s(0) / jobs
    assert got["kernels.ms"] + got["pipeline.xla_ms"] == pytest.approx(
        busy_ms, rel=0.05)
    assert 0 < got["kernels_roofline"] < 100
    assert got["analysis.host_ms"] > 0
    rows = ctx.kernel_rows()
    assert {r["kernel"] for r in rows} == {"topk", "knn_values"}
    assert all(r["bound"] in ("mxu", "vpu", "hbm") for r in rows)


def test_collective_ms_on_hand_made_intervals():
    red = tr.Reduced(
        window=(0.0, 10.0),
        ops={0: [("all-gather-start", 1.0, 1.5, "collective"),
                 ("all-gather-done", 2.0, 3.0, "collective"),
                 ("fusion", 3.0, 6.0, "xla")],
             1: [("collective-permute-start", 1.0, 2.0, "collective"),
                 ("collective-permute-done", 4.0, 4.5, "collective"),
                 ("topk_pallas.1", 5.0, 9.0, "kernel")]},
        spans=[("job.call", 0.5, 5.0), ("job.call", 5.0, 9.5)])
    dev = SimpleNamespace(device_kind="TPU v5 lite")
    ctx = MetricContext(red, 2, {}, [dev, dev], ROOT)
    read = discover.module("metrics", "mesh.collective_ms").read
    # (1.5 + 1.5) s over 2 devices, over 2 jobs
    assert read(ctx) == pytest.approx(1e3 * 3.0 / 2 / 2)
    red.ops = {d: [o for o in ops if o[3] != "collective"]
               for d, ops in red.ops.items()}
    assert read(ctx) is None


def test_xla_ms_counts_a_loop_through_its_body():
    red = tr.Reduced(
        window=(0.0, 10.0),
        ops={0: [("while.1", 1.0, 5.0, "xla"), ("top_k.2", 1.0, 3.0, "xla"),
                 ("fusion.3", 3.5, 4.5, "xla"), ("all-gather", 5.0, 6.0,
                                                  "collective"),
                 ("k.1", 6.0, 8.0, "kernel"), ("custom-call", 6.0, 6.0,
                                               "xla")]},
        spans=[("job.call", 0.5, 9.0)])
    dev = SimpleNamespace(device_kind="TPU v5 lite")
    ctx = MetricContext(red, 1, {}, [dev], ROOT)
    # the loop's 4 s are its body's 2 + 1, not 4 + 3
    assert discover.module("metrics", "pipeline.xla_ms").read(ctx) == \
        pytest.approx(3e3)


def test_mesh_fixture_metrics_on_four_devices():
    assert MESH.stat().st_size < 1 << 20
    red = tr.reduce(MESH)
    assert sorted(red.ops) == [0, 1, 2, 3]
    assert not red.op_seconds("kernel") and red.op_seconds("collective")
    man = discover.manifest()
    cell = discover.workload(man, "sift262k-knn-mesh4")
    jobs = len(red.spans_named("job.call"))
    dev = SimpleNamespace(device_kind="TPU v5 lite")
    ctx = MetricContext(red, jobs, dict(n=4096, d=128, k=32, p=4),
                        [dev] * 4, ROOT)
    got = {m["name"]: discover.module("metrics", m["name"]).read(ctx)
           for m in discover.per_layer(man, cell)}
    assert set(got) == {"device.idle_frac", "pipeline.xla_ms",
                        "mesh.collective_ms"}
    assert 0 < got["device.idle_frac"] < 1 and got["mesh.collective_ms"] > 0
    # the selection loops' bodies are counted once: XLA and collective
    # time add up to the busy time
    busy_ms = 1e3 * red.mean_busy_s() / jobs
    assert got["pipeline.xla_ms"] + got["mesh.collective_ms"] == \
        pytest.approx(busy_ms, rel=0.02)
