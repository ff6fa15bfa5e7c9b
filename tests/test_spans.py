"""The program's profiler spans: names, nesting and order, read back from a
CPU trace with ``jax.profiler.ProfileData``."""
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.core import analysis, knn, pald
from repro.kernels import ops

LAYERS = ("engine", "resilience", "pipeline", "kernel", "analysis")


def _spans(tmp_path: Path, fn):
    """Run ``fn`` once untraced (compiles), then under a trace; return the
    program's spans as (name, start_ns, end_ns), in start order."""
    jax.block_until_ready(fn())
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(fn())
    return _read(tmp_path)


def _read(tmp_path: Path):
    (path,) = tmp_path.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.end_ns) for e in line.events
                       if e.name.split(".")[0] in LAYERS)
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _children(spans, parent):
    return [sp[0] for sp in spans if sp is not parent and _inside(sp, parent)]


def _dist(X):
    D = np.sqrt(((X[:, None, :] - X[None, :, :]) ** 2).sum(-1))
    np.fill_diagonal(D, 0.0)
    return D


def test_dense_tri_spans_nest_in_order(tmp_path, small_D):
    D = jnp.asarray(small_D)
    spans = _spans(tmp_path, lambda: pald.cohesion(
        D, method="kernel", schedule="tri", impl="interpret"))
    top = [sp for sp in spans if sp[0].startswith("engine.")]
    assert [sp[0] for sp in top] == ["engine.plan", "engine.validate",
                                     "engine.execute"]
    assert top[0][2] <= top[1][1] and top[1][2] <= top[2][1]
    assert _children(spans, top[0]) == _children(spans, top[1]) == []
    assert _children(spans, top[2]) == [
        "pipeline.pad",          # the engine's pad to the plan's block
        "pipeline.pad",          # pald_tri's pad to the largest tile
        "kernel.focus_tri", "pipeline.weights", "kernel.cohesion_tri",
        "pipeline.finish",       # pald_tri's slice
        "pipeline.finish"]       # the executor's slice and 1/(n-1)
    inner = [sp for sp in spans if not sp[0].startswith("engine.")]
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


@pytest.mark.parametrize("impl,stages", [
    (None, ["pipeline.pad", "pipeline.finish", "pipeline.scatter_dense",
            "pipeline.finish"]),
    ("interpret", ["pipeline.pad", "kernel.topk", "pipeline.finish",
                   "pipeline.pad", "pipeline.gather_cube",
                   "kernel.knn_values", "pipeline.finish",
                   "pipeline.scatter_dense", "pipeline.finish"]),
])
def test_knn_facade_spans(tmp_path, impl, stages):
    X = np.random.default_rng(3).normal(size=(200, 8)).astype(np.float32)
    kw = {} if impl is None else {"impl": impl}
    spans = _spans(tmp_path, lambda: pald.from_features(
        X, method="knn", k=4, **kw))
    names = [sp[0] for sp in spans]
    assert names[:3] == ["engine.plan", "engine.validate", "engine.execute"]
    assert _children(spans, spans[2]) == stages == names[3:]


def test_select_cohere_has_no_engine_spans(tmp_path):
    X = np.random.default_rng(4).normal(size=(64, 8)).astype(np.float32)
    spans = _spans(tmp_path, lambda: ops.select_cohere(
        X, k=4, impl="interpret", normalize=True))
    assert [sp[0] for sp in spans] == [
        "pipeline.pad", "kernel.topk", "pipeline.finish", "pipeline.pad",
        "pipeline.gather_cube", "kernel.knn_values", "pipeline.finish",
        "pipeline.finish"]


def test_dense_communities_spans(tmp_path, clustered_D):
    C = np.asarray(pald.cohesion(jnp.asarray(clustered_D)))
    spans = _spans(tmp_path, lambda: analysis.communities(C))
    assert [sp[0] for sp in spans] == ["analysis.strong_ties",
                                       "analysis.components"]
    assert spans[0][2] <= spans[1][1]


def test_knn_communities_spans(tmp_path):
    X = np.random.default_rng(5).normal(size=(48, 3))
    graph, vals = ops.pald_knn(jnp.asarray(_dist(X)), k=6, normalize=True)
    vals = np.asarray(vals)
    spans = _spans(tmp_path, lambda: knn.communities(graph, vals))
    assert [sp[0] for sp in spans] == ["analysis.strong_ties",
                                       "analysis.components"]


def test_resilience_step_span(tmp_path, small_D):
    from repro.testing import faults

    D = jnp.asarray(small_D)
    p = pald.plan(D, method="kernel", schedule="tri", impl="interpret",
                  on_error="fallback")
    with faults.failing("engine.execute"):
        spans = _spans(tmp_path, lambda: p.execute(D))
    names = [sp[0] for sp in spans]
    assert "resilience.step" in names
    step = spans[names.index("resilience.step")]
    execute = spans[names.index("engine.execute")]
    assert _inside(step, execute)


def test_nothing_is_recorded_without_a_trace(tmp_path, small_D):
    D = jnp.asarray(small_D)
    C = pald.cohesion(D, method="kernel", schedule="tri", impl="interpret")
    analysis.communities(np.asarray(C))
    X = np.random.default_rng(6).normal(size=(48, 3)).astype(np.float32)
    pald.from_features(X, method="knn", k=4).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        pass
    assert _read(tmp_path) == []
