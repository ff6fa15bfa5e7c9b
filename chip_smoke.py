#!/usr/bin/env python3
"""Smoke run of PaLD's main path on a TPU, through the public entry points.

    python chip_smoke.py            # one chip: dense-tri, knn-engine, knn-sparse
    python chip_smoke.py --mesh 4   # four chips: the sharded k-NN path only

Phases (one process, no children; data made from ``--seed``):

* dense-tri   ``pald.cohesion(D, method="kernel", schedule="tri")`` on the
              integer APSP distances of a collaboration-like graph with
              n = 5,242 nodes (ca-GrQc in the source paper's Appendix C).
              Checked against the pairwise oracle at n = 150, against
              ``method="triplet"`` at n = 5,242, and for mass conservation
              (normalized ``weight="split"`` cohesion sums to n/2).
* knn-engine  ``pald.plan(kind="features", method="knn", k=32).execute(X)``
              on mixed-density clusters at n = 16,384, d = 128 (the SIFT
              width); the plan must resolve ``impl="pallas"`` and record no
              degradation.  Checked against the jnp sparse values.
* knn-sparse  ``ops.select_cohere(X, k=32, impl="pallas")`` at n = 65,536,
              d = 128.  Checked against ``impl="jnp"``, against a float64
              numpy recomputation of 256 rows, and for communities that are
              pure with respect to the seeded cluster labels.
* mesh-knn    (``--mesh 4`` only) ``distributed_knn.pald_knn_sharded`` with
              the ring and 2d strategies at n = 262,144 on a 2x2 mesh,
              checked against single-device ``select_cohere(impl="jnp")``.

Features are integers in [0, 255], as uint8 SIFT descriptors are: every dot
product is exact in f32, so every path computes bitwise the same distances
and the selected neighbors can be compared index for index.

Each kernel phase records the Pallas calls it made and compiles them again
to confirm ``tpu_custom_call`` in their HLO.  Every phase prints one JSON
line (backend compile seconds, warm wall seconds after
``block_until_ready``, the device's ``peak_bytes_in_use``, the device
kind); the last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or when any check fails,
the script exits non-zero and prints no such line.

The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when it is set, and
is ``<repo>/.jax_cache`` otherwise.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke.py: no PaLD sources under {ROOT / 'src'}; run it "
             "from a checkout of the repository")
sys.path.insert(0, str(ROOT / "src"))
# a cold tuning cache: every tile is the pallas default plan() reports
os.environ.setdefault("REPRO_TUNE_CACHE",
                      str(ROOT / ".jax_cache" / "blocktune.json"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

K = 32
D_FEAT = 128
N_ORACLE, N_TRI, N_ENGINE, N_SPARSE, N_MESH = 150, 5242, 16384, 65536, 262144
IMPL = "pallas"


def log(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


# -- timing and observation ---------------------------------------------------
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    # backend (XLA and Mosaic) compiles only: trace events nest, one per
    # jitted function inside another, and would count twice
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def timed(fn):
    """(result, backend compile seconds, wall seconds) of one call, synced."""
    c0, t0 = _compile_s[0], time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, _compile_s[0] - c0, time.perf_counter() - t0


@contextlib.contextmanager
def kernel_calls():
    """Record every Pallas entry-point call made inside the block."""
    from repro.kernels import ops, pald_knn

    targets = [(ops, "focus_tri_pallas"), (ops, "cohesion_tri_pallas"),
               (ops, "topk_pallas"), (pald_knn, "knn_values_pallas")]
    calls, saved = {}, []
    for mod, name in targets:
        fn = getattr(mod, name)
        saved.append((mod, name, fn))

        def spy(*args, _fn=fn, _name=name, **kw):
            shapes = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                           for a in args)
            calls.setdefault((_name, shapes, tuple(sorted(kw.items()))),
                             (_fn, shapes, kw))
            return _fn(*args, **kw)

        setattr(mod, name, spy)
    try:
        yield calls
    finally:
        for mod, name, fn in saved:
            setattr(mod, name, fn)


def check(ok, what) -> None:
    """Fail the phase unless ``ok``; stays in force under ``python -O``."""
    if not ok:
        raise AssertionError(what)


def check_kernels(calls, expected) -> list[str]:
    """Compile each recorded kernel call again; each must be a Mosaic
    kernel (``tpu_custom_call``) that fits the device."""
    names = sorted({key[0] for key in calls})
    missing = set(expected) - set(names)
    check(not missing, f"kernels never called (not the pallas path): {missing}")
    for (name, _, _), (fn, shapes, kw) in calls.items():
        compiled = fn.lower(*shapes, **kw).compile()
        check("tpu_custom_call" in compiled.as_text(), name)
    return names


def peak_bytes(dev=None) -> int:
    return int((dev or jax.devices()[0]).memory_stats()["peak_bytes_in_use"])


# -- data ----------------------------------------------------------------------
def clusters(n: int, seed: int, n_clusters: int = 32):
    """Mixed-density Gaussian clusters, rounded and clipped to [0, 255].

    Three spreads (2, 6, 18) over well-separated centers; returns float32
    features and the cluster label of each row."""
    rng = np.random.default_rng(seed)
    centers = rng.integers(0, 160, size=(n_clusters, D_FEAT))
    spread = np.array([2.0, 6.0, 18.0])[np.arange(n_clusters) % 3]
    labels = rng.integers(0, n_clusters, size=n)
    X = centers[labels] + rng.normal(size=(n, D_FEAT)) * spread[labels, None]
    return np.clip(np.rint(X), 0, 255).astype(np.float32), labels


# -- phases --------------------------------------------------------------------
def phase_dense_tri(seed: int) -> dict:
    from benchmarks.bench_graphs import collaboration_graph
    from repro.core import pald
    from repro.core.reference import pald_pairwise_reference

    tri = dict(method="kernel", schedule="tri", on_error="raise")
    expected = ("focus_tri_pallas", "cohesion_tri_pallas")
    # oracle at n = 150
    D_small = collaboration_graph(N_ORACLE, seed=seed)
    with kernel_calls() as calls:
        C_small = np.asarray(pald.cohesion(jnp.asarray(D_small), **tri))
    check_kernels(calls, expected)
    ref = pald_pairwise_reference(D_small, ties="drop", normalize=True)
    np.testing.assert_allclose(C_small, ref, rtol=1e-5, atol=1e-6)

    n = N_TRI
    t0 = time.perf_counter()
    D_np = collaboration_graph(n, seed=seed)
    setup_s = time.perf_counter() - t0
    D = jax.device_put(D_np)
    p = pald.plan(D, **tri)
    check(p.impl == IMPL, p.explain())
    with kernel_calls() as calls:
        C, compile_s, cold_s = timed(lambda: p.execute(D))
        _, _, wall_s = timed(lambda: p.execute(D))
    kernels = check_kernels(calls, expected)
    check(not p.explain()["degradations"], p.explain()["degradations"])
    C_trip = pald.cohesion(D, method="triplet", on_error="raise")
    err_trip = float(jnp.max(jnp.abs(C - C_trip)))
    check(jnp.allclose(C, C_trip, rtol=1e-5, atol=1e-6), err_trip)
    C_split = pald.cohesion(D, weight="split", **tri)
    mass = float(np.asarray(C_split, np.float64).sum())
    check(abs(mass - n / 2) <= 1e-3 * n / 2, mass)
    ex = p.explain()
    return dict(n=n, ties_distinct=int(np.unique(D_np).size), setup_s=setup_s,
                compile_s=compile_s, cold_s=cold_s, wall_s=wall_s,
                block=ex["block"], block_z=ex["block_z"],
                block_source=ex["block_source"], kernels=kernels,
                max_abs_err_vs_triplet=err_trip, split_mass=mass,
                split_mass_expected=n / 2, oracle_n150="allclose")


def phase_knn_engine(seed: int) -> dict:
    from repro.core import pald
    from repro.kernels import ops

    n = N_ENGINE
    t0 = time.perf_counter()
    X_np, _ = clusters(n, seed)
    X = jax.device_put(X_np)
    setup_s = time.perf_counter() - t0
    p = pald.plan(X, kind="features", method="knn", k=K, metric="euclidean",
                  on_error="raise")
    check(p.impl == IMPL, p.explain())
    with kernel_calls() as calls:
        C, compile_s, cold_s = timed(lambda: p.execute(X))
        _, _, wall_s = timed(lambda: p.execute(X))
    kernels = check_kernels(calls, ("topk_pallas", "knn_values_pallas"))
    ex = p.explain()
    check(not ex["degradations"], ex["degradations"])
    check(C.shape == (n, n) and bool(jnp.isfinite(C).all()),
          f"cohesion {C.shape} not finite and (n, n)")
    g, v = ops.select_cohere(X, k=K, impl="jnp", normalize=True)
    rows = jnp.arange(n)[:, None]
    np.testing.assert_allclose(np.asarray(C[rows, g.indices]),
                               np.asarray(v[:, 1:]), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(jnp.diagonal(C)),
                               np.asarray(v[:, 0]), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(C.sum()), float(v.sum()), rtol=1e-4)
    return dict(n=n, d=D_FEAT, k=K, setup_s=setup_s, compile_s=compile_s,
                cold_s=cold_s, wall_s=wall_s, impl=ex["impl"],
                block=ex["block"], block_source=ex["block_source"],
                select_block=ex["select_block"],
                select_tile=ex["select_tile"],
                select_source=ex["select_source"], kernels=kernels,
                degradations=len(ex["degradations"]))


def _check_rows(X_np, graph, n_rows: int, seed: int) -> float:
    """Max relative error of the k neighbor distances of sampled rows
    against a float64 numpy k-nearest recomputation."""
    rng = np.random.default_rng(seed)
    rows = rng.choice(X_np.shape[0], size=n_rows, replace=False)
    X64 = X_np.astype(np.float64)
    sq = (X64 * X64).sum(1)
    d2 = sq[rows, None] + sq[None, :] - 2.0 * X64[rows] @ X64.T
    d2[np.arange(n_rows), rows] = np.inf                  # self excluded
    ref = np.sqrt(np.maximum(np.sort(d2, axis=1)[:, :K], 0.0))
    got = np.asarray(graph.distances)[rows].astype(np.float64)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
    own = np.sqrt(np.maximum(np.take_along_axis(
        d2, np.asarray(graph.indices)[rows], axis=1), 0.0))
    np.testing.assert_allclose(got, own, rtol=1e-6, atol=1e-6)
    return float(np.max(np.abs(got - ref) / np.maximum(ref, 1e-30)))


def phase_knn_sparse(seed: int) -> dict:
    from repro.core import knn
    from repro.kernels import ops

    n = N_SPARSE
    t0 = time.perf_counter()
    X_np, labels = clusters(n, seed + 1)
    X = jax.device_put(X_np)
    setup_s = time.perf_counter() - t0
    run = lambda: ops.select_cohere(X, k=K, impl=IMPL,  # noqa: E731
                                    normalize=True)
    with kernel_calls() as calls:
        (g, v), compile_s, cold_s = timed(run)
        _, _, wall_s = timed(run)
    kernels = check_kernels(calls, ("topk_pallas", "knn_values_pallas"))
    (gj, vj), _, jnp_cold_s = timed(
        lambda: ops.select_cohere(X, k=K, impl="jnp", normalize=True))
    _, _, jnp_wall_s = timed(
        lambda: ops.select_cohere(X, k=K, impl="jnp", normalize=True))
    np.testing.assert_array_equal(np.asarray(g.indices), np.asarray(gj.indices))
    np.testing.assert_allclose(np.asarray(v), np.asarray(vj),
                               rtol=1e-5, atol=1e-7)
    row_err = _check_rows(X_np, g, 256, seed)
    comms = [c for c in knn.communities(g, np.asarray(v)) if len(c) > 1]
    impure = [c for c in comms if np.unique(labels[c]).size > 1]
    check(comms and not impure, (len(comms), len(impure)))
    return dict(n=n, d=D_FEAT, k=K, setup_s=setup_s, compile_s=compile_s,
                cold_s=cold_s, wall_s=wall_s, jnp_cold_s=jnp_cold_s,
                jnp_wall_s=jnp_wall_s, kernels=kernels,
                indices_equal_jnp=True,
                max_abs_diff_vs_jnp=float(jnp.max(jnp.abs(v - vj))),
                sampled_rows_max_rel_err=row_err, communities=len(comms),
                points_in_communities=int(sum(len(c) for c in comms)),
                impure_communities=len(impure))


def phase_mesh(seed: int, n_dev: int) -> dict:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed_knn import pald_knn_sharded
    from repro.kernels import ops

    devs = jax.devices()
    check(len(devs) >= n_dev, f"--mesh {n_dev} needs {n_dev} devices")
    devs = devs[:n_dev]
    mesh = Mesh(np.array(devs).reshape(2, n_dev // 2), ("r", "c"))
    n = N_MESH
    t0 = time.perf_counter()
    X_np, _ = clusters(n, seed + 2)
    X = jax.device_put(X_np, NamedSharding(mesh, P(("r", "c"), None)))
    setup_s = time.perf_counter() - t0
    check(len(X.sharding.device_set) == n_dev, X.sharding)
    out = dict(n=n, d=D_FEAT, k=K, devices=n_dev, setup_s=setup_s)
    results = {}
    # peak_bytes_in_use is a high-water mark: the strategy with the smaller
    # footprint may not raise it again, so growth is asserted over both
    before = [peak_bytes(d) for d in devs]
    for strategy in ("ring", "2d"):
        run = lambda: pald_knn_sharded(  # noqa: E731
            X, mesh, k=K, metric="euclidean", strategy=strategy,
            normalize=True, on_error="raise")
        (g, v), compile_s, cold_s = timed(run)
        _, _, wall_s = timed(run)
        check(len(v.sharding.device_set) == n_dev, v.sharding)
        results[strategy] = (np.asarray(g.indices), np.asarray(v))
        out[strategy] = dict(compile_s=compile_s, cold_s=cold_s,
                             wall_s=wall_s,
                             peak_bytes_per_device=[peak_bytes(d)
                                                    for d in devs])
    after = [peak_bytes(d) for d in devs]
    check(all(a > b for a, b in zip(after, before)), (before, after))
    out["peak_bytes_before"] = before
    X0 = jax.device_put(X_np, devs[0])
    # the tile-min prefilter keeps the one-chip reference's top_k narrow
    ref_run = lambda: ops.select_cohere(  # noqa: E731
        X0, k=K, impl="jnp", tile=512, normalize=True)
    (gr, vr), compile_s, cold_s = timed(ref_run)
    out["reference"] = dict(compile_s=compile_s, cold_s=cold_s)
    ri, rv = np.asarray(gr.indices), np.asarray(vr)
    for strategy, (idx, vals) in results.items():
        np.testing.assert_array_equal(idx, ri)
        np.testing.assert_allclose(vals, rv, rtol=1e-5, atol=1e-7)
        out[strategy]["indices_equal_reference"] = True
        out[strategy]["max_abs_diff_vs_reference"] = float(
            np.max(np.abs(vals - rv)))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, choices=(4,), default=None,
                    help="run only the sharded k-NN phase on this many chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU (JAX found {dev.platform!r}); this is "
              "a chip run and has no CPU fallback", file=sys.stderr)
        return 2
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    from repro.core.resilience import DegradationWarning

    warnings.simplefilter("error", DegradationWarning)

    phases = ([("mesh-knn", lambda s: phase_mesh(s, args.mesh))]
              if args.mesh else
              [("dense-tri", phase_dense_tri),
               ("knn-engine", phase_knn_engine),
               ("knn-sparse", phase_knn_sparse)])
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            res = fn(args.seed)
        except Exception:  # noqa: BLE001 - report and fail the run
            traceback.print_exc()
            log(phase=name, ok=False, seconds=time.perf_counter() - t0)
            return 1
        log(phase=name, ok=True, seconds=time.perf_counter() - t0,
            peak_bytes=peak_bytes(), device_kind=dev.device_kind, **res)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
