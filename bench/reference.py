"""Plain PaLD references that decide ``correct``, and their lower-precision
controls.

Written from the definitions (Berenhaut, Moore & Melvin, PNAS 2022; the
source paper's Algorithm 1 with exact ties dropped; the k-NN restriction of
Baron et al., arXiv:2108.08864) in straightforward ``jax.numpy`` and numpy.
Nothing here imports the program under test or takes anything it made.

``dtype`` is the precision of the arithmetic that can round: ``float32``
is the reference, ``bfloat16`` the control one step below it.  Hop counts
and the benchmark's integer features are exact in both, so the control
lowers what rounds: focus counts, weights and sums (dense), and distance
values, neighbor-to-neighbor distances, counts and sums (k-NN).

Semantics, ``ties="drop"``: for a pair (x, y) the focus is
U_xy = {z : d(x,z) < d(x,y) or d(y,z) < d(x,y)}; z in U_xy supports x when
d(x,z) < d(y,z), y when d(y,z) < d(x,z), neither on a tie; C[x,z] sums
1/|U_xy| over the pairs in which z supports x, over (n - 1).
"""
from __future__ import annotations

import functools

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

import jax
import jax.numpy as jnp


# -- dense PaLD ---------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def _dense_rows(D, xs, *, block: int, dtype):
    """C rows for x in ``xs`` (a multiple of ``block`` long), unnormalized."""
    D = D.astype(dtype)
    n = D.shape[0]

    def one(xb):
        Dx = D[xb]                                     # (b, n): d(x, .)
        dxy = Dx[:, :, None]                           # (b, y, 1)
        F = (Dx[:, None, :] < dxy) | (D[None, :, :] < dxy)   # (b, y, z)
        U = jnp.sum(F, axis=-1, dtype=dtype)           # (b, y)
        W = jnp.where(U > 0, (1 / jnp.where(U > 0, U, 1)).astype(dtype),
                      jnp.zeros((), dtype))
        S = F & (Dx[:, None, :] < D[None, :, :])
        return jnp.sum(jnp.where(S, W[:, :, None], jnp.zeros((), dtype)),
                       axis=1, dtype=dtype)            # (b, z)

    out = jax.lax.map(one, xs.reshape(-1, block))
    return out.reshape(-1, n)


def dense_cohesion(D: np.ndarray, *, dtype=jnp.float32, block: int = 8,
                   device=None) -> np.ndarray:
    """Normalized (n, n) PaLD cohesion of distance matrix ``D``, float64."""
    n = D.shape[0]
    m = -(-n // block) * block
    xs = np.minimum(np.arange(m), n - 1).astype(np.int32)
    Dd = jax.device_put(np.asarray(D, np.float32), device)
    C = _dense_rows(Dd, jax.device_put(xs, device), block=block, dtype=dtype)
    return np.asarray(C[:n], np.float64) / max(n - 1, 1)


# -- k-NN PaLD ------------------------------------------------------------------
def _sqdist(A, B, dtype):
    """Squared euclidean distances; exact for integer features in f32."""
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    A = A.astype(jnp.float32)
    B = B.astype(jnp.float32)
    na = jnp.sum(A * A, axis=-1)
    nb = jnp.sum(B * B, axis=-1)
    dot = jnp.einsum("...id,...jd->...ij", A, B, precision=prec,
                     preferred_element_type=jnp.float32)
    d2 = jnp.maximum(na[..., :, None] + nb[..., None, :] - 2.0 * dot, 0.0)
    return d2.astype(dtype)


@functools.partial(jax.jit, static_argnames=("k", "block", "dtype"))
def _knn_rows(X, r0, *, k: int, block: int, dtype):
    """Neighbors, squared distances and unnormalized values of rows
    ``r0 .. r0 + block`` (the slab clamps at the end of X)."""
    n = X.shape[0]
    r0 = jnp.minimum(r0, n - block)
    rows = r0 + jnp.arange(block)
    Xr = jax.lax.dynamic_slice_in_dim(X, r0, block, 0)
    d2 = _sqdist(Xr, X, dtype)                                  # (b, n)
    d2 = jnp.where(rows[:, None] == jnp.arange(n)[None, :],
                   jnp.asarray(jnp.inf, dtype), d2)
    # top_k keeps the lower index first among equal values
    neg, idx = jax.lax.top_k(-d2, k)
    dn = -neg                                                   # (b, k)
    g = _sqdist(X[idx], X[idx], dtype)                          # (b, k, k)
    g = jnp.where(idx[:, :, None] == idx[:, None, :],
                  jnp.zeros((), dtype), g)
    zero = jnp.zeros((), dtype)
    one = jnp.ones((), dtype)
    # pair (x, y = nbr_j); candidates z = x and z = nbr_m
    dxy = dn[:, :, None]                                        # (b, j, 1)
    dxz = dn[:, None, :]                                        # (b, 1, m)
    F = (dxz < dxy) | (g < dxy)                                 # (b, j, m)
    f_self = dn > 0                                             # (b, j)
    U = (jnp.sum(F, axis=-1, dtype=dtype)
         + jnp.where(f_self, one, zero))
    W = jnp.where(U > 0, (1 / jnp.where(U > 0, U, one)).astype(dtype), zero)
    S = F & (dxz < g)
    v_nbr = jnp.sum(jnp.where(S, W[:, :, None], zero), axis=1, dtype=dtype)
    v_self = jnp.sum(jnp.where(f_self, W, zero), axis=1, dtype=dtype)
    vals = jnp.concatenate([v_self[:, None], v_nbr], axis=1)
    return rows, idx, dn, vals


def knn_cohesion(X: np.ndarray, k: int, *, dtype=jnp.float32,
                 block: int = 512, devices=None) -> dict:
    """Exact k-NN PaLD of features ``X``: ``indices`` (n, k), ``distances``
    (n, k) euclidean, ``values`` (n, k+1) normalized, as numpy arrays.

    Rows are split evenly over ``devices`` (default: the first device),
    each device holding all of X."""
    n = X.shape[0]
    block = min(block, n)
    devices = devices or jax.devices()[:1]
    starts = np.arange(0, n, block)
    Xs = [jax.device_put(np.asarray(X, np.float32), d) for d in devices]
    parts = []
    for i, s in enumerate(starts):
        dev = i % len(devices)
        parts.append(_knn_rows(Xs[dev], jax.device_put(np.int32(s),
                                                       devices[dev]),
                               k=k, block=block, dtype=dtype))
    idx = np.empty((n, k), np.int64)
    d2 = np.empty((n, k), np.float64)
    vals = np.empty((n, k + 1), np.float64)
    for rows, i_, dn, v in parts:
        rows = np.asarray(rows)
        idx[rows] = np.asarray(i_)
        d2[rows] = np.asarray(dn.astype(jnp.float32))
        vals[rows] = np.asarray(v.astype(jnp.float32))
    return dict(indices=idx, distances=np.sqrt(d2),
                values=vals / max(n - 1, 1))


# -- communities -----------------------------------------------------------------
def component_labels(n: int, src, dst) -> np.ndarray:
    g = coo_matrix((np.ones(len(src)), (src, dst)), shape=(n, n))
    return connected_components(g, directed=False)[1]


def dense_strong_pairs(C: np.ndarray, band: float):
    """(certain, possible) strong ties of a dense cohesion matrix, each as
    (src, dst): a tie min(c_xy, c_yx) >= tau, tau = mean(diag C) / 2, is
    certain at tau * (1 + band) and possible at tau * (1 - band)."""
    tau = float(np.mean(np.diag(C))) / 2.0
    S = np.minimum(C, C.T)
    np.fill_diagonal(S, 0.0)
    hi = np.nonzero(S >= tau * (1 + band))
    lo = np.nonzero(S >= tau * (1 - band))
    return hi, lo


def knn_strong_pairs(indices: np.ndarray, values: np.ndarray, band: float):
    """The same for the sparse k-NN layout: only mutual neighbor pairs can
    be strong; tau = mean(self values) / 2."""
    n, k = indices.shape
    tau = float(np.mean(values[:, 0])) / 2.0
    src = np.repeat(np.arange(n, dtype=np.int64), k)
    dst = indices.ravel().astype(np.int64)
    w = values[:, 1:].ravel()
    key = src * n + dst
    order = np.argsort(key, kind="stable")
    skey = key[order]
    want = dst * n + src
    pos = np.minimum(np.searchsorted(skey, want), len(skey) - 1)
    rev = np.where(skey[pos] == want, w[order][pos], 0.0)
    S = np.minimum(w, rev)
    hi = S >= tau * (1 + band)
    lo = S >= tau * (1 - band)
    return (src[hi], dst[hi]), (src[lo], dst[lo])


def partition_faults(n: int, communities, certain, possible) -> int:
    """Points placed against the reference's strong ties.

    ``communities`` (lists of point indices) must join every certain tie
    (else the points of a split are counted) and may join only what the
    possible ties connect (else the points of a merge are counted).  Ties
    within the rounding band of the threshold may go either way."""
    label = np.full(n, -1, np.int64)
    for c, members in enumerate(communities):
        label[np.asarray(members, np.int64)] = c
    if (label < 0).any():
        return int((label < 0).sum())
    lo = component_labels(n, *certain)
    hi = component_labels(n, *possible)
    faults = 0
    for a, b in ((lo, label), (label, hi)):
        # every class of a must fall in one class of b
        order = np.lexsort((b, a))
        a_s, b_s = a[order], b[order]
        first = np.r_[True, a_s[1:] != a_s[:-1]]
        head_b = b_s[np.maximum.accumulate(np.where(first, np.arange(n), 0))]
        faults += int((b_s != head_b).sum())
    return faults
