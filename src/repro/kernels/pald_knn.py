"""Pallas kernel for the sparse k-NN PaLD pipeline.

One grid axis, one pass: each grid step loads the (block, k) neighbor
distances, the (block, k, k) gathered neighbor-to-neighbor tile and the
(block, k) neighbor indices of one row block, and emits that block's
(block, k+1) sparse cohesion values.  Unlike the dense kernels there is no
cross-row reduction — the directed-pair knn formulation keeps every row's
focus sizes AND support local to its own neighborhood (``core/knn.py``
module docstring) — so focus and cohesion fuse into a single kernel with
no intermediate U/W round-trip through HBM.

The tile body is ``core.knn.knn_values_tile``, the same traced function
the blocked-jnp fallback (``kernels/ops._knn_values_jnp``) runs, so the
two impls are bit-faithful to each other by construction; the only
in-kernel addition is deriving the ``ties='ignore'`` index tiebreak from
the grid position (global row iota vs the loaded neighbor indices),
exactly as the dense square kernels do.

The gathered tile ``G`` is produced OUTSIDE the kernel (a dense-D fancy
gather or a per-chunk feature recompute, ``kernels/ops.pald_knn``): a
data-dependent gather from HBM inside a Pallas body would need per-index
DMA orchestration for an O(n * k^2) array that is small enough (205 MB at
n = 50k, k = 32) to stage in HBM anyway.

TPU alignment: Mosaic wants 128-lane last dims, so the entry point pads
the neighbor axis k up to the lane quantum (+inf distances, index 0) and
the value output up to ``_out_cols`` lanes; ``knn_values_tile`` masks the
padded columns out of the focus count and pair weights via ``k_valid``,
and the caller slices both paddings away.  Interpret mode (CPU tests)
runs unpadded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.knn import knn_values_tile
from repro.core.weights import DEFAULT_TIES, resolve_weight

__all__ = ["knn_values_pallas"]

_LANE = 128


def _out_cols(k: int, interpret: bool) -> int:
    """Lane-aligned width of the value output (k+1 columns on CPU)."""
    return k + 1 if interpret else -(-(k + 1) // _LANE) * _LANE


def _knn_kernel(dn_ref, g_ref, idx_ref, out_ref, *, block, k_valid, ties,
                n_cols):
    dn = dn_ref[...]                                  # (block, k)
    g = g_ref[...]                                    # (block, k, k)
    k = dn.shape[1]
    ow = None
    if ties.needs_index_tiebreak:
        rows = pl.program_id(0) * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, k), 0)
        ow = rows > idx_ref[...]
    vals = knn_values_tile(dn, g, ow, ties,
                           k_valid=k_valid if k_valid < k else None)
    pad = n_cols - (k + 1)
    if pad:
        vals = jnp.concatenate(
            [vals, jnp.zeros((block, pad), jnp.float32)], axis=1)
    out_ref[...] = vals


@functools.partial(jax.jit, static_argnames=("block", "k_valid", "ties",
                                             "interpret"))
def knn_values_pallas(
    dn: jnp.ndarray,       # (m, k) neighbor distances (k possibly lane-padded)
    g: jnp.ndarray,        # (m, k, k) gathered neighbor-to-neighbor tiles
    idx: jnp.ndarray,      # (m, k) int32 neighbor indices
    *,
    block: int = 128,
    k_valid: int,
    ties=DEFAULT_TIES,
    interpret: bool = False,
) -> jnp.ndarray:
    """Sparse cohesion values (m, >= k+1) — caller slices to (n, k_valid+1).

    ``m`` must be a multiple of ``block`` (padded rows carry +inf neighbor
    distances and are sliced off by the caller); ``k_valid`` is the number
    of real neighbor columns when k was lane-padded.  Columns 0..k_valid
    of the output are [self, nbr_0, ..., nbr_{k_valid-1}]; everything past
    that (padded neighbors + lane fill) is junk/zero to slice away."""
    ties = resolve_weight(ties)
    m, k = dn.shape
    assert m % block == 0 and g.shape == (m, k, k) and idx.shape == (m, k)
    n_cols = _out_cols(k, interpret)
    kernel = functools.partial(_knn_kernel, block=block, k_valid=k_valid,
                               ties=ties, n_cols=n_cols)
    return pl.pallas_call(
        kernel,
        grid=(m // block,),
        in_specs=[
            pl.BlockSpec((block, k), lambda i: (i, 0)),
            pl.BlockSpec((block, k, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((block, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, n_cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n_cols), jnp.float32),
        interpret=interpret,
        name="knn_values_pallas",
    )(dn.astype(jnp.float32), g.astype(jnp.float32), idx.astype(jnp.int32))
