"""Feature-space PaLD: distances from vectors, fused or materialized.

Every real workload starts from feature vectors, not a distance matrix —
yet the classic pipeline materializes the full O(n^2) ``D`` in HBM before
pass 1, exactly the kind of avoidable data movement the paper's blocking
analysis (W = Theta(n^3/sqrt(M))) warns about.  This module is the feature
front-end:

``cdist_reference(X, Y=None, metric=...)``
    Plain-jnp pairwise distances for the supported metrics.  The oracle the
    fused kernels are tested against, and the "materialize-then-PaLD" path.

The public entry point lives in ``repro.core.pald.from_features`` — a thin
facade over the execution-plan engine (``core/engine.py``), which resolves
``method="fused"`` (distance tiles computed on the fly from ``(block, d)``
feature tiles inside the kernel, so ``D`` never hits HBM — DESIGN.md §10)
vs. the materialize-once paths, and owns the batched ``(B, n, d)`` layer.

Supported metrics (see ``METRICS``): ``sqeuclidean``, ``euclidean``,
``cosine``, ``manhattan``.  All distance computation is float32; inputs of
any float dtype are cast exactly once at the executor boundary (float64
inputs are explicitly, not silently, downcast).

Tile-level building blocks (``dist_tile``, ``masked_dist_tile``) are shared
by the Pallas kernels (``repro.kernels.pald_fused``), the jnp fused
fallback (``repro.kernels.ops``), and the feature-sharded distributed
strategies (``repro.core.distributed``), so every path computes bit-wise
comparable distances.
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

METRICS = ("sqeuclidean", "euclidean", "cosine", "manhattan")

Metric = Literal["sqeuclidean", "euclidean", "cosine", "manhattan"]

_NORM_EPS = 1e-30  # cosine guard: zero vectors get distance 1, not nan

__all__ = [
    "METRICS",
    "cdist_reference",
    "dist_tile",
    "masked_dist_tile",
    "pad_features",
]


# ---------------------------------------------------------------------------
# tile-level distance computation (usable inside Pallas kernel bodies)
# ---------------------------------------------------------------------------
def dist_tile(XA: jnp.ndarray, XB: jnp.ndarray, metric: str,
              *, loop_d: bool = False) -> jnp.ndarray:
    """(ma, d) x (mb, d) -> (ma, mb) distances, float32.

    The dot-product metrics contract the feature axis with one
    ``dot_general`` at ``Precision.HIGHEST``: full f32 inputs and f32
    accumulation in XLA and in a Mosaic kernel alike, so no backend may
    round the features to bf16 first.  That makes every caller, Pallas
    kernel or jnp slab, compute the same f32 distance up to the order of
    the d-term sum.  The order is the backend's: two tile shapes can differ
    by an ulp (XLA:CPU picks its GEMM blocking by shape), and features whose
    dot products are exact in f32 (small integers, such as uint8 SIFT
    descriptors) give bitwise-equal distances on every path.

    ``loop_d=True`` walks the feature axis one column at a time instead of
    materializing the (ma, mb, d) broadcast cube — the manhattan form the
    Pallas kernels use so VMEM stays at tile size.  The walk is unrolled at
    trace time (static column slices), which is what Mosaic lowers.
    Zero-padded feature columns are exact no-ops for every metric (they add
    0 to dots, norms and absolute differences), which is what lets the
    kernels pad d up to the TPU lane quantum.
    """
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r} (expected one of {METRICS})")
    XA = XA.astype(jnp.float32)
    XB = XB.astype(jnp.float32)
    if metric in ("sqeuclidean", "euclidean"):
        na = jnp.sum(XA * XA, axis=1, keepdims=True)            # (ma, 1)
        nb = jnp.sum(XB * XB, axis=1, keepdims=True)            # (mb, 1)
        dot = jax.lax.dot_general(
            XA, XB, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        d2 = jnp.maximum(na + nb.T - 2.0 * dot, 0.0)
        return jnp.sqrt(d2) if metric == "euclidean" else d2
    if metric == "cosine":
        na = jnp.sqrt(jnp.maximum(jnp.sum(XA * XA, axis=1, keepdims=True),
                                  _NORM_EPS))
        nb = jnp.sqrt(jnp.maximum(jnp.sum(XB * XB, axis=1, keepdims=True),
                                  _NORM_EPS))
        dot = jax.lax.dot_general(
            XA, XB, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        return 1.0 - dot / (na * nb.T)
    # manhattan
    if loop_d:
        acc = jnp.zeros((XA.shape[0], XB.shape[0]), jnp.float32)
        for j in range(XA.shape[1]):
            acc = acc + jnp.abs(XA[:, j:j + 1] - XB[:, j:j + 1].T)
        return acc
    return jnp.sum(jnp.abs(XA[:, None, :] - XB[None, :, :]), axis=-1)


def masked_dist_tile(XA: jnp.ndarray, XB: jnp.ndarray, metric: str,
                     row_off, col_off, n_valid: int,
                     *, loop_d: bool = False) -> jnp.ndarray:
    """Distance tile with the padding contract of ``pad_distance_matrix``
    applied in-register: rows/cols at global index >= n_valid are +inf
    (padded points are infinitely far from everything) and the exact global
    diagonal is 0 (fp noise in ``d(x, x)`` must not break the "x is always
    in its own focus" invariant)."""
    D = dist_tile(XA, XB, metric, loop_d=loop_d)
    ma, mb = D.shape
    rows = row_off + jax.lax.broadcasted_iota(jnp.int32, (ma, mb), 0)
    cols = col_off + jax.lax.broadcasted_iota(jnp.int32, (ma, mb), 1)
    D = jnp.where((rows >= n_valid) | (cols >= n_valid), jnp.inf, D)
    return jnp.where(rows == cols, 0.0, D)


# ---------------------------------------------------------------------------
# materialized reference distances
# ---------------------------------------------------------------------------
def cdist_reference(X: jnp.ndarray, Y: jnp.ndarray | None = None,
                    *, metric: Metric = "euclidean") -> jnp.ndarray:
    """Pairwise distances in plain jnp, float32.

    With ``Y=None`` the square form zeroes its diagonal exactly (the
    dot-product formulation of d(x, x) is only zero up to fp noise), so it
    composes with ``pald.cohesion`` without spurious self-distances.
    """
    from .resilience import fault_point

    fault_point("features.cdist", metric=metric)
    X = jnp.asarray(X, jnp.float32)
    square = Y is None
    Y = X if square else jnp.asarray(Y, jnp.float32)
    D = dist_tile(X, Y, metric)
    if square:
        n = X.shape[0]
        D = D.at[jnp.arange(n), jnp.arange(n)].set(0.0)
    return D


def pad_features(X: jnp.ndarray, quantum: int) -> tuple[jnp.ndarray, int]:
    """Pad rows of X up to a multiple of ``quantum`` with zero vectors.

    Unlike distance-matrix padding, the +inf semantics can't be expressed in
    feature space; the fused kernels re-impose them per tile via
    ``masked_dist_tile(n_valid=...)``.  Returns (padded X, original n).
    """
    n = X.shape[0]
    m = -(-n // quantum) * quantum
    if m == n:
        return X, n
    return jnp.pad(X, ((0, m - n), (0, 0))), n


# The public entry point (``pald.from_features``) and the batched layer live
# in ``repro.core.pald`` / ``repro.core.engine``; this module provides the
# metric tile primitives every executor shares.
