"""Sparse k-NN PaLD: neighborhood selection, struct, and tile semantics.

The triplet-comparison algorithms of the source paper are inherently
O(n^3)-work / O(n^2)-memory — at n = 50k the distance matrix alone is
10 GiB and the comparison count is 1.25e14, which caps the dense pipeline
at a few tens of thousands of points.  *Partitioned K-nearest neighbor
local depth* (Baron, Darling, Davis & Pfeifer, arXiv:2108.08864) shows
that PaLD restricted to k-nearest-neighbor conflict foci preserves the
community structure the full computation finds, at O(n * k^2) cost.  This
module is that restriction, engineered to the same contracts as every
dense path (shared ``core/weights.py`` weight functionals,
engine-registered executor, tuning-cache tiles):

``NeighborGraph``
    The CSR-style neighborhood struct: ``indices (n, k)`` int32 and
    ``distances (n, k)`` float32, row ``x`` holding x's k nearest other
    points sorted by (distance, index).  A NamedTuple, so it is a pytree
    and traces through ``jit`` / ``vmap`` unchanged.

``knn_from_distances(D, k)`` / ``knn_from_features(X, k, metric=...)``
    Top-k selection from a precomputed matrix or — chunked, never
    materializing D — straight from feature vectors.  Tie-break at the
    k boundary is deterministic: equal distances admit the LOWER index
    first (``jax.lax.top_k``'s stable order on the negated distances).

``knn_values_tile(dn, g, own_wins, ties)``
    The exact-within-neighborhood PaLD semantics for one row tile — the
    single tile body shared by the blocked-jnp fallback
    (``kernels/ops._knn_values_jnp``) and the Pallas kernel
    (``kernels/pald_knn.py``), the same way ``core/weights.py`` is shared
    by every dense tile body.

``scatter_dense(graph, values)``
    Expand the sparse (n, k+1) cohesion values into the dense (n, n) C
    the rest of the API speaks — the ``method="knn"`` executors end with
    this; large-n consumers keep the sparse form instead.

Semantics (what ``method="knn"`` approximates)
----------------------------------------------
For every DIRECTED conflict pair (x, y) with y in N_k(x), the conflict
focus is restricted to the candidate set {x} ∪ N_k(x) (which contains y
by construction), and only the x role accumulates support:

    U_k[x, y] = sum_{z in {x} ∪ N_k(x)} focus_weight(d_xz, d_yz, d_xy)
    C[x, z]  += support_weight(d_xz, d_yz, d_xy) / U_k[x, y]

with the focus/support contributions — and therefore the ``ties=`` /
``weight=`` contract — taken verbatim from ``core/weights.py``.  Row x of
C is supported only at
z in {x} ∪ N_k(x), which is exactly the sparse (n, k+1) value layout.

At k = n-1 the candidate set is all n points and the directed pair sum
ranges over every ordered pair, so the restriction is the identity and
U_k, C coincide with the dense definition (asserted in the conformance
matrix; the engine executor runs the dense path outright there, see
``kernels/ops.pald_knn``).  For k < n-1 the directed formulation keeps
each row's computation local to its own neighborhood — O(k^2) work and
O(k^2) gathered distances per point, no cross-row reduction — which is
what makes the single-pass (block, k) kernel schedule possible.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from .weights import (DEFAULT_TIES, focus_weight, resolve_weight,
                      support_weight)

__all__ = [
    "NeighborGraph",
    "knn_from_distances",
    "knn_from_features",
    "knn_values_tile",
    "scatter_dense",
    "local_depths",
    "universal_threshold",
    "strong_ties",
    "communities",
]


class NeighborGraph(NamedTuple):
    """k-nearest-neighbor structure of n points (a jit-friendly pytree).

    Attributes:
        indices: (n, k) int32 — row x holds the indices of x's k nearest
            OTHER points (self always excluded), ordered by increasing
            distance with exact ties broken toward the lower index.
        distances: (n, k) float32 — the matching distances, so
            ``distances[x, j] == d(x, indices[x, j])``.
    """

    indices: jnp.ndarray
    distances: jnp.ndarray

    @property
    def n(self) -> int:
        return self.indices.shape[0]

    @property
    def k(self) -> int:
        return self.indices.shape[1]


def _top_k_rows(neg_rows: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(distances, indices) of the k smallest entries per row of -neg_rows.

    ``lax.top_k`` is stable (equal values surface the lower index first),
    which on negated distances yields the deterministic tie-break the
    whole knn contract relies on."""
    vals, idx = jax.lax.top_k(neg_rows, k)
    return -vals, idx.astype(jnp.int32)


def knn_from_distances(D: jnp.ndarray, k: int) -> NeighborGraph:
    """Select each point's k nearest neighbors from a distance matrix.

    Args:
        D: (n, n) distance matrix with a zero diagonal.  Cast to float32
            (the pipeline-wide comparison dtype) before selection.
        k: neighborhood size, ``0 <= k <= n-1``.  k = 0 yields an empty
            graph (shape (n, 0)); callers normally clamp to n-1.

    Returns:
        NeighborGraph with ``indices (n, k)`` / ``distances (n, k)``; the
        self point never appears in its own neighbor list.

    Raises:
        ValueError: if ``k`` exceeds n-1 (there are only n-1 other points).

    Example:
        >>> import jax.numpy as jnp
        >>> D = jnp.asarray([[0., 1., 4.], [1., 0., 2.], [4., 2., 0.]])
        >>> g = knn_from_distances(D, k=1)
        >>> g.indices.tolist(), g.distances.tolist()
        ([[1], [0], [1]], [[1.0], [1.0], [2.0]])
    """
    D = jnp.asarray(D, jnp.float32)
    n = D.shape[0]
    if k > max(n - 1, 0):
        raise ValueError(f"k={k} exceeds the n-1={n - 1} available neighbors")
    if k <= 0:
        return NeighborGraph(jnp.zeros((n, 0), jnp.int32),
                             jnp.zeros((n, 0), jnp.float32))
    eye = jnp.eye(n, dtype=bool)
    dist, idx = _top_k_rows(jnp.where(eye, -jnp.inf, -D), k)
    return NeighborGraph(idx, dist)


def knn_from_features(
    X: jnp.ndarray,
    k: int,
    *,
    metric: str = "euclidean",
    row_chunk: int | str = 1024,
    impl: str | None = None,
    tile: int | str = "auto",
) -> NeighborGraph:
    """Select k nearest neighbors straight from feature vectors.

    The distance matrix is never materialized.  Since PR 9 this is a thin
    facade over the streaming selection machinery in
    ``kernels.ops.topk_select``: the Pallas streaming kernel
    (``kernels/pald_topk.py``) on TPU, the blocked-jnp fallback (direct or
    tile-min-prefiltered slab top-k) elsewhere — every impl bitwise
    identical to the original slab-``lax.top_k`` contract, stable
    lower-index-first tie-break included.

    Args:
        X: (n, d) feature matrix, any float dtype (cast to float32 once).
        k: neighborhood size, ``0 <= k <= n-1``.
        metric: one of ``features.METRICS`` (sqeuclidean, euclidean,
            cosine, manhattan) — the same tile primitive
            (``features.dist_tile``) the fused kernels use, so distances
            agree with ``cdist_reference`` up to summation order.
        row_chunk: rows per selection slab; bounds peak memory
            (O(row_chunk * n + n * k)), does not change the result.
            ``"auto"`` resolves via the ``pald_topk:k<k>:d<d>`` tuning
            cache pass.
        impl: selection impl override ('pallas'/'interpret'/'jnp'/
            'chunked'); None = backend default.
        tile: tile-min prefilter width (see ``kernels.ops.topk_select``);
            "auto" = tuned, a value >= n disables the prefilter.

    Returns:
        NeighborGraph over the metric's distances.

    Raises:
        ValueError: unknown metric, or ``k > n-1``.

    Example:
        >>> import jax.numpy as jnp
        >>> X = jnp.asarray([[0.0], [1.0], [3.0]])
        >>> knn_from_features(X, k=2).indices.tolist()
        [[1, 2], [0, 2], [1, 0]]
    """
    from repro.kernels.ops import topk_select

    return topk_select(X, k, metric=metric, impl=impl, block=row_chunk,
                       tile=tile)


# ---------------------------------------------------------------------------
# the exact-within-neighborhood tile body (shared by jnp fallback + kernel)
# ---------------------------------------------------------------------------
def knn_values_tile(
    dn: jnp.ndarray,
    g: jnp.ndarray,
    own_wins: jnp.ndarray | None,
    ties=DEFAULT_TIES,
    *,
    k_valid: int | None = None,
) -> jnp.ndarray:
    """Sparse cohesion values for one (b, k) row tile of the knn graph.

    Args:
        dn: (b, k) neighbor distances d(x, nbr_j) for the tile's rows.
        g: (b, k, k) gathered neighbor-to-neighbor distances
            ``g[i, a, b] = d(nbr_a(x_i), nbr_b(x_i))`` with an exactly
            zero diagonal.
        own_wins: (b, k) bool — global index of x > index of nbr_j; the
            index tiebreak for functionals declaring
            ``needs_index_tiebreak`` (None otherwise).
        ties: weight functional (name or instance); the focus/support
            contributions come verbatim from ``core/weights``.
        k_valid: number of REAL neighbor columns when k was padded up to
            a lane quantum (Pallas path).  Padded columns carry +inf pair
            distances but FINITE junk gathered distances (their indices
            point at arbitrary real rows), so they are masked out of both
            the focus count (candidate axis) and the pair weights (pair
            axis) here.  None = all columns real.

    Returns:
        (b, k+1) float32 values: column 0 is z = x (self support), column
        1+j is z = nbr_j.  Un-normalized (no 1/(n-1) factor).

    The whole body is plain broadcast arithmetic over the (b, k, k) cube —
    it traces identically inside ``jit`` (the jnp fallback) and inside a
    Pallas kernel body, which is how the two impls stay bit-faithful to
    each other.  Reductions use explicit ``sum`` (not a matmul) so the
    accumulation order is the same everywhere.
    """
    b, k = dn.shape
    zero = jnp.zeros_like(dn)
    mvalid = None
    if k_valid is not None and k_valid < k:
        mvalid = (jnp.arange(k) < k_valid).astype(jnp.float32)
    # pass 1: restricted focus size per directed pair (x, nbr_j):
    # z = x contributes focus_weight(0, d_yx, d_xy); z = nbr_m the cube term
    fw_self = focus_weight(zero, dn, dn, ties)                     # (b, k)
    fw_nbr = focus_weight(dn[:, None, :], g, dn[:, :, None], ties)  # (b, j, m)
    if mvalid is not None:
        fw_nbr = fw_nbr * mvalid[None, None, :]
    U = fw_self + jnp.sum(fw_nbr, axis=-1, dtype=jnp.float32)
    W = jnp.where(U > 0, 1.0 / jnp.where(U > 0, U, 1.0), 0.0)
    if mvalid is not None:
        W = W * mvalid[None, :]
    # pass 2: support of every candidate z against the same pair set
    wfun = resolve_weight(ties)
    if wfun.share is not None:
        # conserves-mass factoring (core/weights contract): support ==
        # nan-guarded share * focus on the SAME (own, other, pair)
        # triples as pass 1, so reuse the focus cube instead of
        # evaluating a second smooth (b, k, k) cube — two op-heavy cube
        # chains in this single fused body make XLA's merged loop spill
        # registers (~3x), and the reuse is bitwise-free
        # no nan-guard on the product: share(a, b) is nan only when BOTH
        # operands are +inf, and the gathered g is finite by construction
        # (junk values at padded slots, never inf), while the focus cube
        # is already guarded — so the product is always finite here
        sw_nbr = wfun.share(dn[:, None, :], g) * fw_nbr
        sw_self = wfun.share(zero, dn) * fw_self
    else:
        ow = None if own_wins is None else own_wins[:, :, None]
        sw_nbr = support_weight(dn[:, None, :], g, dn[:, :, None], ties, ow)
        sw_self = support_weight(zero, dn, dn, ties, own_wins)
    cv_nbr = jnp.sum(sw_nbr * W[:, :, None], axis=1, dtype=jnp.float32)
    cv_self = jnp.sum(sw_self * W, axis=1, dtype=jnp.float32)
    return jnp.concatenate([cv_self[:, None], cv_nbr], axis=1)


def gather_tile_from_distances(D: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """(b, k, k) neighbor-to-neighbor distances gathered from dense D."""
    return D[idx[:, :, None], idx[:, None, :]]


def gather_tile_from_features(X: jnp.ndarray, idx: jnp.ndarray,
                              metric: str) -> jnp.ndarray:
    """(b, k, k) neighbor-to-neighbor distances recomputed from features.

    The diagonal (a == b: the same neighbor against itself) is forced to
    exactly zero — the matmul formulation of d(x, x) is only zero up to fp
    noise, and the "x is in its own focus" invariant needs it exact."""
    from .features import dist_tile

    Xn = X[idx]                                               # (b, k, d)
    G = jax.vmap(lambda A: dist_tile(A, A, metric))(Xn)       # (b, k, k)
    same = idx[:, :, None] == idx[:, None, :]
    return jnp.where(same, 0.0, G)


# ---------------------------------------------------------------------------
# sparse-result utilities
# ---------------------------------------------------------------------------
def scatter_dense(graph: NeighborGraph, values: jnp.ndarray) -> jnp.ndarray:
    """Expand sparse (n, k+1) cohesion values to the dense (n, n) matrix.

    Args:
        graph: the NeighborGraph the values were computed on.
        values: (n, k+1) from the knn pipeline (column 0 = self).

    Returns:
        (n, n) float32 C with ``C[x, x] = values[x, 0]``,
        ``C[x, graph.indices[x, j]] = values[x, 1+j]`` and exact zeros
        everywhere else (entries the knn restriction never supports).
    """
    n = graph.indices.shape[0]
    rows = jnp.arange(n)
    C = jnp.zeros((n, n), jnp.float32)
    if graph.k:
        C = C.at[rows[:, None], graph.indices].set(values[:, 1:])
    return C.at[rows, rows].set(values[:, 0])


def local_depths(values: jnp.ndarray) -> jnp.ndarray:
    """l_x = sum_z c_xz over the stored entries (all others are exact 0)."""
    return jnp.sum(values, axis=-1)


def universal_threshold(values: np.ndarray) -> float:
    """tau = mean(self-cohesion) / 2 on the sparse value layout.

    The sparse analogue of ``analysis.universal_threshold``: column 0 of
    ``values`` IS the diagonal of C.  Assumes normalized values (the
    default ``normalize=True`` of the public entry points)."""
    return float(np.mean(np.asarray(values)[..., 0])) / 2.0


def strong_ties(graph: NeighborGraph, values: np.ndarray,
                threshold: float | None = None):
    """Symmetrized strong ties on the sparse structure.

    A tie (x, y) is strong when ``min(c_xy, c_yx) >= tau``; a direction
    the knn restriction never stored counts as cohesion 0, so only
    MUTUAL neighbor pairs can be strong — the same conservative
    symmetrization ``analysis.strong_ties`` applies densely.

    Args:
        graph: the NeighborGraph.
        values: (n, k+1) cohesion values.
        threshold: tau override; default ``universal_threshold(values)``.

    Returns:
        (src, dst, weight) numpy arrays of the strong directed edges with
        src < dst (each unordered strong tie reported once).
    """
    idx = np.asarray(graph.indices)
    n, k = idx.shape
    v = np.asarray(values)
    tau = universal_threshold(v) if threshold is None else threshold
    if k == 0:
        z = np.zeros(0)
        return z.astype(np.int64), z.astype(np.int64), z
    src = np.repeat(np.arange(n, dtype=np.int64), k)
    dst = idx.ravel().astype(np.int64)
    w = v[:, 1:].ravel().astype(np.float64)
    key = src * n + dst
    order = np.argsort(key)
    skey = key[order]
    pos = np.searchsorted(skey, dst * n + src)
    pos_c = np.minimum(pos, len(skey) - 1)
    has_rev = skey[pos_c] == dst * n + src
    w_rev = np.where(has_rev, w[order][pos_c], 0.0)
    sym = np.minimum(w, w_rev)
    keep = (sym >= tau) & (src < dst)
    return src[keep], dst[keep], sym[keep]


def communities(graph: NeighborGraph, values: np.ndarray,
                threshold: float | None = None) -> list[list[int]]:
    """Connected components of the sparse strong-tie graph.

    Same output contract as ``analysis.communities``: components sorted
    by size (largest first, ties by smallest member), members ascending,
    and the same profiler spans around its two host stages.

    Example:
        >>> import jax.numpy as jnp
        >>> D = jnp.asarray([[0., 1., 9., 9.], [1., 0., 9., 9.],
        ...                  [9., 9., 0., 1.], [9., 9., 1., 0.]])
        >>> from repro.kernels.ops import pald_knn
        >>> g, vals = pald_knn(D, k=2, normalize=True)
        >>> communities(g, vals)
        [[0, 1], [2, 3]]
    """
    from .analysis import connected_components

    with TraceAnnotation("analysis.strong_ties"):
        src, dst, _ = strong_ties(graph, values, threshold)
    with TraceAnnotation("analysis.components"):
        return connected_components(graph.indices.shape[0], zip(src, dst))
