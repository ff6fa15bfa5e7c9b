"""Streaming top-k selection conformance (ISSUE 9).

The selection contract is stated on distance VALUES: given the same
distances, every selection implementation — the streaming Pallas kernel
(interpret mode on CPU), the jnp lax.map scan in both strategies (direct
full-width top_k and the exact tile-min prefilter), and the host-driven
chunked degradation rung — is BITWISE identical to the reference
``_top_k_rows``: stable ``lax.top_k`` on negated distances,
lower-index-first tie-break, self excluded.  Selection feeds every
downstream sparse result, so a one-ulp or one-rank divergence here is a
silent correctness bug, not a tolerance question.

Each path computes its own distances, with one f32 ``dist_tile`` whose d-sum
order is the backend's (a tile GEMM and a slab GEMM may differ by an ulp on
XLA:CPU).  So the network is tested on given distance tiles directly, and
the paths are compared on integer-valued features, whose distances are
exact in f32 and therefore identical on every path.

The fused select->cohere pipeline is covered too: it must bitwise-equal
the two-stage ``knn_from_features`` -> ``ops.pald_knn`` composition
under every built-in weight functional.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import knn
from repro.core.features import dist_tile
from repro.kernels import ops
from repro.kernels.pald_topk import fold_tile, next_pow2, topk_pallas

METRICS = ("sqeuclidean", "euclidean", "cosine", "manhattan")
_IMAX = np.iinfo(np.int32).max


def _features(n, d, seed=0, with_dups=True):
    """Small-integer features: every distance is exact in f32, so every
    path sees identical values, with plenty of distance ties."""
    rng = np.random.default_rng(seed)
    X = rng.integers(-4, 5, size=(n, d)).astype(np.float32)
    if with_dups and n >= 8:
        # duplicated rows force zero-distance ties -> exercises the
        # lower-index-first tie-break in every implementation
        X[n // 3] = X[5]
        X[n - 2] = X[1]
    return X


def _reference(X, k, metric="euclidean", pad_to=None):
    """The contract: masked stable top_k over the full distance row.

    ``pad_to`` computes the distances on a zero-row-padded (m, d) input
    with padded rows/cols masked out — the shape the Pallas kernel's
    tiles see, so that float features with a shape-dependent GEMM order
    still give the kernel and the reference identical values."""
    n = X.shape[0]
    m = pad_to or n
    Xp = np.zeros((m, X.shape[1]), np.float32)
    Xp[:n] = X
    Xd = jnp.asarray(Xp)
    D = dist_tile(Xd, Xd, metric, loop_d=False)
    ids = jnp.arange(m)
    bad = (ids[:, None] == ids[None, :]) | (ids[None, :] >= n)
    dv, di = knn._top_k_rows(jnp.where(bad, -jnp.inf, -D), k)
    return dv[:n], di[:n]


def _fold_rows(D, k, block_z, order=None):
    """Run the kernel's selection network alone over given distances.

    ``D`` (b, n) is folded tile by tile (``order`` permutes the visit
    order) through ``pald_topk.fold_tile``; +inf entries are masked, as the
    kernel masks self and padding."""
    b, n = D.shape
    nt = n // block_z
    order = jnp.asarray(np.arange(nt) if order is None else order, jnp.int32)
    out_w = max(next_pow2(k), 128)

    def kern(order_ref, d_ref, v_ref, i_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            v_ref[...] = jnp.full_like(v_ref, jnp.inf)
            i_ref[...] = jnp.full_like(i_ref, _IMAX)

        cv = d_ref[...]
        cols = order_ref[t] * block_z + jax.lax.broadcasted_iota(
            jnp.int32, cv.shape, 1)
        fold_tile(v_ref, i_ref, cv, jnp.where(jnp.isinf(cv), _IMAX, cols))

    spec = pl.BlockSpec((b, out_w), lambda t, o: (0, 0))
    vals, idx = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nt,),
            in_specs=[pl.BlockSpec((b, block_z), lambda t, o: (0, o[t]))],
            out_specs=[spec, spec]),
        out_shape=[jax.ShapeDtypeStruct((b, out_w), jnp.float32),
                   jax.ShapeDtypeStruct((b, out_w), jnp.int32)],
        interpret=True,
    )(order, D)
    return vals[:, :k], idx[:, :k]


def _check(graph, ref_d, ref_i):
    assert graph.distances.dtype == ref_d.dtype
    assert graph.indices.dtype == ref_i.dtype
    np.testing.assert_array_equal(np.asarray(graph.distances),
                                  np.asarray(ref_d))
    np.testing.assert_array_equal(np.asarray(graph.indices),
                                  np.asarray(ref_i))


@pytest.mark.parametrize("metric", METRICS)
def test_jnp_strategies_match_reference(metric):
    n, d, k = 103, 4, 9  # prime-ish n: every tile/slab path hits padding
    X = _features(n, d)
    ref_d, ref_i = _reference(X, k, metric)
    for tile in (n, 16):  # direct and tile-min prefilter
        g = ops.topk_select(jnp.asarray(X), k, metric=metric,
                            impl="jnp", tile=tile)
        _check(g, ref_d, ref_i)


@pytest.mark.parametrize("metric", METRICS)
def test_chunked_rung_matches_reference(metric):
    n, d, k = 97, 3, 7
    X = _features(n, d)
    ref_d, ref_i = _reference(X, k, metric)
    g = ops.topk_select(jnp.asarray(X), k, metric=metric,
                        impl="chunked", block=32)
    _check(g, ref_d, ref_i)


@pytest.mark.parametrize("metric", METRICS)
def test_streaming_kernel_matches_reference(metric):
    n, d, k = 103, 4, 9
    X = _features(n, d)
    ref_d, ref_i = _reference(X, k, metric)
    g = ops.topk_select(jnp.asarray(X), k, metric=metric,
                        impl="interpret", block=64, tile=32)
    _check(g, ref_d, ref_i)


@pytest.mark.parametrize("k", (1, 33, 102))
def test_edge_k_all_impls(k):
    n, d = 103, 4
    X = _features(n, d)
    ref_d, ref_i = _reference(X, k)
    for kw in ({"impl": "jnp", "tile": n}, {"impl": "jnp", "tile": 16},
               {"impl": "chunked"}, {"impl": "interpret"}):
        g = ops.topk_select(jnp.asarray(X), k, **kw)
        _check(g, ref_d, ref_i)


def test_kernel_direct_entry_matches_top_k_rows():
    """topk_pallas itself (below the ops facade), prime n, RAGGED d, float
    features.

    d=5 makes the distance GEMM shape-sensitive on XLA:CPU, so the
    reference is computed at the kernel's own padded shape (see
    ``_reference``): this isolates the claim that the streaming
    machinery — self/pad masking, bitonic merge, tie-break — adds zero
    error for any d."""
    n, d, k = 97, 5, 13
    X = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)
    X[n // 3] = X[5]
    m = 128  # pad to one 128-row block
    for metric in METRICS:
        ref_d, ref_i = _reference(X, k, metric, pad_to=m)
        Xp = np.zeros((m, d), np.float32)
        Xp[:n] = X
        vals, idx = topk_pallas(jnp.asarray(Xp), k=k, metric=metric,
                                n_valid=n, block=128, block_z=128,
                                interpret=True)
        np.testing.assert_array_equal(np.asarray(vals[:n]),
                                      np.asarray(ref_d))
        np.testing.assert_array_equal(np.asarray(idx[:n]),
                                      np.asarray(ref_i))


def test_tile_visit_order_is_irrelevant():
    """Composite-key merge is a total order over distinct indices, so the
    running best-list is the same whatever order candidate tiles fold in
    — checked by varying block_z, which permutes the fold."""
    n, d, k = 128, 4, 9
    X = _features(n, d)
    ref_d, ref_i = _reference(X, k)
    for bz in (16, 32, 128):
        g = ops.topk_select(jnp.asarray(X), k, impl="interpret",
                            block=64, tile=bz)
        _check(g, ref_d, ref_i)


def test_batched_selection_via_vmap():
    """(B, n, d) stacks: the jnp selection path is vmap-composable and
    each batch element bitwise-matches its own single-item run."""
    B, n, d, k = 3, 64, 4, 7
    Xb = np.stack([_features(n, d, seed=s) for s in range(B)])

    def one(x):
        g = ops.topk_select(x, k, impl="jnp", tile=16)
        return g.distances, g.indices

    dv, di = jax.vmap(one)(jnp.asarray(Xb))
    for b in range(B):
        ref_d, ref_i = _reference(Xb[b], k)
        np.testing.assert_array_equal(np.asarray(dv[b]), np.asarray(ref_d))
        np.testing.assert_array_equal(np.asarray(di[b]), np.asarray(ref_i))


def test_facade_delegates_to_topk_select():
    """knn_from_features stays the standalone entry, backed by the same
    machinery — identical output, including under the tile knob."""
    n, d, k = 103, 4, 9
    X = _features(n, d)
    ref_d, ref_i = _reference(X, k)
    g = knn.knn_from_features(jnp.asarray(X), k)
    _check(g, ref_d, ref_i)
    g2 = knn.knn_from_features(jnp.asarray(X), k, row_chunk=32, tile=16)
    _check(g2, ref_d, ref_i)


@pytest.mark.parametrize("ties", ("drop", "split", "ignore"))
def test_fused_pipeline_bitwise_equals_two_stage(ties):
    n, d, k = 103, 4, 9
    X = jnp.asarray(_features(n, d))
    graph = knn.knn_from_features(X, k)
    _, ref_vals = ops.pald_knn(X, k=k, kind="features", graph=graph,
                               ties=ties)
    for sel in (None, "jnp", "chunked", "interpret"):
        g, vals = ops.select_cohere(X, k=k, select=sel, ties=ties)
        np.testing.assert_array_equal(np.asarray(g.indices),
                                      np.asarray(graph.indices))
        np.testing.assert_array_equal(np.asarray(vals),
                                      np.asarray(ref_vals))


def test_fused_engine_path_matches_two_stage_dense():
    """from_features(method=knn) end-to-end: fused executor == scattered
    two-stage composition, bitwise."""
    from repro.core import pald

    n, d, k = 64, 4, 7
    X = jnp.asarray(_features(n, d))
    graph = knn.knn_from_features(X, k)
    _, vals = ops.pald_knn(X, k=k, kind="features", graph=graph)
    ref = knn.scatter_dense(graph, vals)
    out = pald.from_features(X, k=k, normalize=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("k, block_z", [(1, 16), (9, 32), (33, 64),
                                        (102, 128), (130, 256)])
def test_network_matches_top_k_rows_on_given_tiles(k, block_z):
    """The selection contract on given values: float distances with exact
    ties and masked (+inf) entries, folded tile by tile in a shuffled
    order, equal ``_top_k_rows`` on the same values bit for bit."""
    rng = np.random.default_rng(k)
    b, n = 16, 512
    D = rng.random((b, n)).astype(np.float32)
    D[:, ::7] = np.round(D[:, ::7], 1)             # many exact ties
    D[rng.random((b, n)) < 0.05] = np.inf          # masked entries
    order = rng.permutation(n // block_z)
    vals, idx = _fold_rows(jnp.asarray(D), k, block_z, order)
    ref_v, ref_i = knn._top_k_rows(-jnp.asarray(D), k)
    ref_i = jnp.where(jnp.isinf(ref_v), _IMAX, ref_i)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_i))
