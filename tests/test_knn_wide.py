"""k-NN PaLD at a wide feature axis (d = 960, the GIST-960 shape) against the
plain reference, and the chunked neighbor cube against the single gather
it replaces.

Integer features in [0, 90] keep every squared distance an exact f32
integer (2 * 960 * 90^2 < 2^24), so the neighbors and distances are
compared bit for bit; the values are sums of reciprocals whose order
differs between the program and the reference, so they are compared to a
stated tolerance.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from bench import reference as R
from repro.core import knn, pald
from repro.kernels import ops

N, D, K = 500, 960, 32
VAL_TOL = 1e-6          # relative to the largest value: f32 sum order
FLOAT_TOL = 1e-5        # float features: distances round differently


def _int_features(n=N, d=D, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.integers(0, 64, size=(8, d))
    X = centers[rng.integers(0, 8, size=n)] + rng.normal(size=(n, d)) * 6
    return np.clip(np.rint(X), 0, 90).astype(np.float32)


@pytest.fixture(scope="module")
def wide():
    X = _int_features()
    return X, R.knn_cohesion(X, K)


def _exact_f32_distances(ref):
    """The reference's distances as the f32 sqrt of its exact integer
    squared distances."""
    d2 = np.rint(ref["distances"] ** 2).astype(np.float32)
    return np.sqrt(d2)


def _check(indices, distances, values, ref, tol=VAL_TOL):
    np.testing.assert_array_equal(np.asarray(indices), ref["indices"])
    np.testing.assert_array_equal(np.asarray(distances),
                                  _exact_f32_distances(ref))
    v = np.asarray(values, np.float64)
    scale = np.max(np.abs(ref["values"]))
    assert np.max(np.abs(v - ref["values"])) <= tol * scale


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_select_cohere_matches_reference_at_d960(wide, impl):
    X, ref = wide
    graph, vals = ops.select_cohere(jnp.asarray(X), k=K, impl=impl,
                                    normalize=True)
    _check(graph.indices, graph.distances, vals, ref)


def test_from_features_knn_matches_reference_at_d960(wide):
    X, ref = wide
    C = np.asarray(pald.from_features(jnp.asarray(X), method="knn", k=K),
                   np.float64)
    want = np.zeros((N, N))
    rows = np.arange(N)
    want[rows[:, None], ref["indices"]] = ref["values"][:, 1:]
    want[rows, rows] = ref["values"][:, 0]
    assert np.max(np.abs(C - want)) <= VAL_TOL * np.max(np.abs(want))


def test_float_features_match_reference_at_d960():
    """Float features round differently in the program's tiles and the
    reference's slabs, so neighbors at nearly equal distances may trade
    places: the distances agree to FLOAT_TOL, a neighbor differs only where
    the reference's distances there are that close, and on the rows whose
    neighbor lists agree the values agree to FLOAT_TOL but for at most one
    in a hundred, where a comparison of two ulp-close pair distances went
    the other way and a focus count moved by one."""
    X = np.random.default_rng(1).normal(size=(N, D)).astype(np.float32)
    ref = R.knn_cohesion(X, K)
    graph, vals = ops.select_cohere(jnp.asarray(X), k=K, impl="jnp",
                                    normalize=True)
    dist = np.asarray(graph.distances, np.float64)
    np.testing.assert_allclose(dist, ref["distances"], rtol=FLOAT_TOL)
    moved = np.asarray(graph.indices) != ref["indices"]
    assert moved.sum() <= N * K // 1000
    rows, cols = np.nonzero(moved)
    for r, c in zip(rows, cols):
        j = list(ref["indices"][r]).index(int(graph.indices[r, c])) \
            if int(graph.indices[r, c]) in ref["indices"][r] else K - 1
        a, b = ref["distances"][r, c], ref["distances"][r, j]
        assert abs(a - b) <= FLOAT_TOL * a, (r, c, a, b)
    same = ~moved.any(axis=1)
    close = np.isclose(np.asarray(vals)[same], ref["values"][same],
                       rtol=FLOAT_TOL, atol=FLOAT_TOL * np.max(ref["values"]))
    assert close.mean() >= 0.99


# -- the chunked neighbor cube -------------------------------------------------
def _single_gather(x, idx_p, kind, kp):
    """The cube as one eager gather of every padded row, then lane-padded."""
    g = ops._gather_tiles(x, idx_p, kind, "euclidean")
    k = idx_p.shape[1]
    return jnp.pad(g, ((0, 0), (0, kp - k), (0, kp - k)))


@pytest.mark.parametrize("kind", ["features", "distance"])
@pytest.mark.parametrize("blocks_a_chunk", [1, 2, 3, 64])
@pytest.mark.parametrize("kp", [K, 128])
def test_chunked_cube_equals_single_gather(monkeypatch, kind, blocks_a_chunk,
                                           kp):
    n, d, block = 300, 96, 64
    X = jnp.asarray(_int_features(n, d, seed=2))
    x = X if kind == "features" else jnp.sqrt(
        jnp.maximum(jnp.sum((X[:, None] - X[None]) ** 2, -1), 0.0))
    graph = knn.knn_from_features(X, K)
    w = d if kind == "features" else K
    monkeypatch.setattr(ops, "_CUBE_STAGE_BYTES",
                        blocks_a_chunk * block * K * w * 4)
    lay = ops.knn_cube_layout(n, K, d if kind == "features" else None,
                              block=block)
    m = lay["padded_rows"]
    assert m == 320 and lay["chunks"] == min(-(-5 // blocks_a_chunk), 5)
    dn_p = ops._pad2(graph.distances, m, K, jnp.inf)
    idx_p = ops._pad2(graph.indices, m, K, 0)
    dn2, idx2, g = ops._neighbor_cube(x, dn_p, idx_p, kind=kind,
                                      metric="euclidean", rows=lay["rows"],
                                      kp=kp)
    np.testing.assert_array_equal(np.asarray(g),
                                  np.asarray(_single_gather(x, idx_p, kind,
                                                            kp)))
    np.testing.assert_array_equal(np.asarray(dn2),
                                  np.asarray(ops._pad2(dn_p, m, kp, jnp.inf)))
    np.testing.assert_array_equal(np.asarray(idx2),
                                  np.asarray(ops._pad2(idx_p, m, kp, 0)))


def test_cube_layout_at_the_benchmark_shapes():
    """One chunk where the SIFT cells' gather fits in the budget; GIST-960's
    (65,536, 32, 960) gather is split so no chunk stages more than 1 GiB."""
    sift = ops.knn_cube_layout(65536, 32, 128, block=64)
    assert (sift["rows"], sift["chunks"]) == (65536, 1)
    assert sift["staged_bytes"] == 1 << 30
    gist = ops.knn_cube_layout(65536, 32, 960, block=64)
    assert (gist["rows"], gist["chunks"]) == (8192, 8)
    assert gist["staged_bytes"] <= 1 << 30
    # a block that alone exceeds the budget is a chunk of its own
    huge = ops.knn_cube_layout(1024, 32, 1 << 18, block=64)
    assert (huge["rows"], huge["chunks"]) == (64, 16)


@pytest.mark.parametrize("blocks,budget_blocks", [(3, 1.5), (5, 2.5), (7, 3),
                                                  (1024, 136.5), (13, 1)])
def test_cube_layout_stays_within_the_budget(monkeypatch, blocks,
                                             budget_blocks):
    """Whole blocks a chunk, as even as they allow, and never more staged
    bytes than the budget, also where the blocks do not divide evenly."""
    block, k, d = 16, 8, 96
    per_block = block * k * d * 4
    monkeypatch.setattr(ops, "_CUBE_STAGE_BYTES", int(budget_blocks *
                                                      per_block))
    lay = ops.knn_cube_layout(blocks * block, k, d, block=block)
    assert lay["staged_bytes"] <= ops._CUBE_STAGE_BYTES
    assert lay["rows"] % block == 0
    assert lay["chunks"] == -(-blocks // int(budget_blocks))
    assert lay["chunks"] * lay["rows"] >= lay["padded_rows"]
    assert (lay["chunks"] - 1) * lay["rows"] < lay["padded_rows"]


def test_knn_values_cube_is_chunked_end_to_end(monkeypatch):
    """``knn_values``'s interpret path through several chunks gives the
    values of the jnp path, bit for bit."""
    X = jnp.asarray(_int_features(200, 96, seed=3))
    graph = knn.knn_from_features(X, 8)
    monkeypatch.setattr(ops, "_CUBE_STAGE_BYTES", 16 * 8 * 96 * 4)
    assert ops.knn_cube_layout(200, 8, 96, block=16)["chunks"] == 13
    a = ops.knn_values(X, graph, kind="features", block=16, impl="interpret")
    b = ops.knn_values(X, graph, kind="features", block=16, impl="jnp")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
