"""Pallas streaming top-k neighbor selection: feature tiles in, best-list out.

PR 5's ``knn_from_features`` computes each (row_chunk, n) distance slab and
immediately reduces it with a full-width ``lax.top_k`` — correct, but the
slab still round-trips HBM and the reduction re-scans all n candidates per
row.  This kernel is the streaming form of the same contract: the grid walks
(block, d) x (block_z, d) feature tile pairs (the dataflow of
``kernels/pald_fused.py``), computes each (block, block_z) distance tile
in-register via ``features.dist_tile``, and folds it into a running
(block, out_w) best-list held in the output ref — so neither D nor any full
per-row score vector ever exists in HBM.

Selection network
-----------------
Each tile is sorted descending with a bitonic network over COMPOSITE
(value, index) keys — compare-exchange swaps on
``(v1 > v2) | ((v1 == v2) & (i1 > i2))`` — so its best entries end the
tile.  The last ``out_w`` lanes follow the ascending incumbent best-list to
form a bitonic 2*out_w sequence, and one bitonic merge keeps the out_w best
(``fold_tile``).  Partners are found by lane rotation (``pltpu.roll``), the
form Mosaic lowers; the network never reverses or reshapes the lane axis.
Because every real candidate has a distinct global column index, the
composite key is a total order, which makes the maintained list exactly the
first out_w entries of the stable ``lax.top_k`` order on negated distances —
the lower-index-first tie-break of ``core.knn._top_k_rows`` — independent
of the tile visit order.

Masking contract: the self column and every padded row/column (global index
>= ``n_valid``) enter the network as (+inf, INT32_MAX) and therefore lose
to every real candidate; with k <= n-1 real candidates per row they can
never reach the returned k columns of a real row.

TPU alignment: the best-list is ``out_w = max(kp, 128)`` lanes wide (kp is
k rounded up to a power of two); the caller slices back to k.
``block_z`` must be a power of two >= kp.

Contract: selection is exact on the distance values it is given — the
network reproduces ``_top_k_rows`` on the same values bit for bit
(tests/test_topk_conformance.py feeds it identical tiles).  The distances
come from ``dist_tile``, whose dot products run at
``Precision.HIGHEST`` (f32 in, f32 accumulate) on every path, so the kernel
and the jnp slab paths compute the same f32 distance up to the order of the
d-term sum.  That order belongs to the backend (XLA:CPU picks its GEMM
blocking per shape, so a (block, block_z) tile and a (chunk, n) slab can
differ by an ulp); where every dot product is exact in f32 — small integer
features such as uint8 descriptors — the distances, and hence the selected
neighbors, are bitwise equal on every path.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.features import dist_tile

__all__ = ["topk_pallas", "fold_tile", "sort_pairs", "merge_pairs",
           "next_pow2"]

_LANE = 128
_IDX_PAD = np.iinfo(np.int32).max


def next_pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1)."""
    p = 1
    while p < x:
        p *= 2
    return p


def _pairs_gt(v1, i1, v2, i2):
    """Composite strict greater-than on (value, index) keys — the dual of
    the stable lower-index-first tiebreak of ``core.knn._top_k_rows``."""
    return (v1 > v2) | ((v1 == v2) & (i1 > i2))


def _partner(x, lane, j: int):
    """``x`` at lane ``lane ^ j``, for every lane of a (b, w) tile.

    Two lane rotations by +-j hold the partner on one side or the other;
    rotating the lane iota the same way says which, so the result does not
    depend on the rotation's direction convention.  Mosaic lowers a lane
    rotation natively, where a lane reshape or reversal is refused."""
    w = x.shape[-1]
    src = pltpu.roll(lane, j, 1)
    return jnp.where(src == (lane ^ j), pltpu.roll(x, j, 1),
                     pltpu.roll(x, w - j, 1))


def _cx_pass(v, i, j: int, k: int | None, descending: bool = False):
    """One compare-exchange pass at stride ``j`` over the last axis.

    Lane l pairs with lane l ^ j; the lower lane of a pair keeps the
    smaller composite key when the pair sorts ascending.  ``k`` is the
    bitonic sort stage (direction alternates per k-aligned run, ascending
    first, or descending first with ``descending``); ``k=None`` is the
    all-ascending merge form.  Each pair's swap decision is taken from its
    lower lane's point of view, so both lanes agree even on keys that
    compare unordered."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    pv, pi = _partner(v, lane, j), _partner(i, lane, j)
    # boolean algebra, not a select: Mosaic lowers no select of i1 vectors
    is_lo = (lane & j) == 0
    swap = ((is_lo & _pairs_gt(v, i, pv, pi))
            | (~is_lo & _pairs_gt(pv, pi, v, i)))
    if k is not None:
        flip = (lane & k) == 0 if descending else (lane & k) != 0
        swap = swap ^ flip
    return jnp.where(swap, pv, v), jnp.where(swap, pi, i)


def sort_pairs(v, i, descending: bool = False):
    """Full bitonic sort of (b, w) pairs by (value, index), ascending or
    ``descending``.

    ``w`` must be a power of two.  log2(w)*(log2(w)+1)/2 vectorized
    compare-exchange passes; equal composite keys only arise between
    padding sentinels, where a swap is a no-op."""
    w = v.shape[-1]
    k = 2
    while k <= w:
        j = k // 2
        while j >= 1:
            v, i = _cx_pass(v, i, j, k, descending)
            j //= 2
        k *= 2
    return v, i


def merge_pairs(v, i):
    """Bitonic merge: (b, w) pairs forming a bitonic sequence -> ascending.

    log2(w) passes.  Used on ``incumbent ++ descending candidates``, which
    is ascending-then-descending and hence bitonic."""
    w = v.shape[-1]
    j = w // 2
    while j >= 1:
        v, i = _cx_pass(v, i, j, None)
        j //= 2
    return v, i


def _topk_kernel(xi_ref, xj_ref, val_ref, idx_ref, *, metric, n_valid,
                 block, block_z):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, _IDX_PAD)

    roff = pl.program_id(0) * block
    coff = j * block_z
    # loop_d=False: the d-streamed manhattan form accumulates in a
    # different summation order than the slab paths' broadcast-cube sum,
    # which breaks the bitwise-vs-_top_k_rows contract.  The (block,
    # block_z, d) cube lives only for this tile, so VMEM stays bounded.
    dt = dist_tile(xi_ref[...], xj_ref[...], metric,
                   loop_d=False)                         # (block, block_z)
    rows = roff + jax.lax.broadcasted_iota(jnp.int32, (block, block_z), 0)
    cols = coff + jax.lax.broadcasted_iota(jnp.int32, (block, block_z), 1)
    # exclude-self masking: unlike masked_dist_tile's zero diagonal, the
    # selection contract removes x from its own candidate set entirely
    bad = (rows >= n_valid) | (cols >= n_valid) | (rows == cols)
    cv = jnp.where(bad, jnp.inf, dt)
    ci = jnp.where(bad, _IDX_PAD, cols)
    fold_tile(val_ref, idx_ref, cv, ci)


def fold_tile(val_ref, idx_ref, cv, ci):
    """Fold one (b, w) candidate tile into the running best-list refs.

    ``val_ref``/``idx_ref`` hold the (b, out_w) incumbent, ascending by
    (value, index); ``cv``/``ci`` are the tile's values and global indices,
    masked entries already (+inf, INT32_MAX).  Sorted descending, the tile
    ends in its out_w best: those lanes after the incumbent form a bitonic
    2*out_w sequence, and one merge keeps the out_w best of both.  This is
    the whole selection contract: given the same values, the result is
    that of ``core.knn._top_k_rows``."""
    b, w = cv.shape
    out_w = val_ref.shape[1]
    cv, ci = sort_pairs(cv, ci, descending=True)
    if w >= out_w:
        cv, ci = cv[:, w - out_w:], ci[:, w - out_w:]
    else:
        fill = (b, out_w - w)
        cv = jnp.concatenate([jnp.full(fill, jnp.inf, jnp.float32), cv], 1)
        ci = jnp.concatenate([jnp.full(fill, _IDX_PAD, jnp.int32), ci], 1)
    mv, mi = merge_pairs(jnp.concatenate([val_ref[...], cv], axis=1),
                         jnp.concatenate([idx_ref[...], ci], axis=1))
    val_ref[...] = mv[:, :out_w]
    idx_ref[...] = mi[:, :out_w]


@functools.partial(jax.jit, static_argnames=(
    "k", "metric", "n_valid", "block", "block_z", "interpret"))
def topk_pallas(
    X: jnp.ndarray,            # (m, d) zero-padded features
    *,
    k: int,
    metric: str = "euclidean",
    n_valid: int,
    block: int = 128,
    block_z: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Streaming k-nearest selection: (m, d) features -> (m, k) best-lists.

    Returns ``(distances, indices)`` rows sorted ascending by
    (distance, index) — bitwise the rows of ``core.knn._top_k_rows`` on the
    masked distance matrix.  Rows >= ``n_valid`` are junk (+inf / INT32_MAX)
    for the caller to slice off; ``m`` must divide by both ``block`` and
    ``block_z``, and ``block_z`` must be a power of two >= next_pow2(k).
    """
    m, d = X.shape
    kp = next_pow2(max(k, 1))
    assert m % block == 0 and m % block_z == 0, (m, block, block_z)
    assert block_z == next_pow2(block_z) and block_z >= kp, (block_z, kp)
    out_w = max(kp, _LANE)
    kernel = functools.partial(
        _topk_kernel, metric=metric, n_valid=n_valid, block=block,
        block_z=block_z)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(m // block, m // block_z),   # col axis last: sequential fold
        in_specs=[
            pl.BlockSpec((block, d), lambda i, j: (i, 0)),     # rows
            pl.BlockSpec((block_z, d), lambda i, j: (j, 0)),   # candidates
        ],
        out_specs=[
            pl.BlockSpec((block, out_w), lambda i, j: (i, 0)),
            pl.BlockSpec((block, out_w), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, out_w), jnp.float32),
            jax.ShapeDtypeStruct((m, out_w), jnp.int32),
        ],
        interpret=interpret,
        name="topk_pallas",
    )(X.astype(jnp.float32), X.astype(jnp.float32))
    return vals[:, :k], idx[:, :k]
