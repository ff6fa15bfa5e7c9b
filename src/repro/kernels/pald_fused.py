"""Fused features→PaLD Pallas kernels: distance tiles computed in-register.

The dense kernels (``pald_focus`` / ``pald_cohesion``) consume a
materialized distance matrix — O(n^2) HBM traffic before pass 1 even
starts.  These variants take the (n, d) feature matrix instead: each grid
step loads the (block, d) feature tiles it needs, computes the
(block, block) / (block, block_z) distance tiles in VMEM via
``features.dist_tile`` (matmul-backed for sqeuclidean / euclidean / cosine,
d-streamed for manhattan), and then runs the *same* focus / cohesion tile
bodies as the dense kernels (``tile_loops``), with D[Y, Z] and D[X, Y]^T
parked in VMEM scratch so the y-loop reads rows.  ``D`` never exists in
HBM.

Grid shapes and the accumulator-residency discipline are identical to the
dense kernels (DESIGN.md §4.1); the only new cost is recomputing distance
tiles on revisit, an O(d/block) relative overhead that is far cheaper than
streaming them from HBM for any d << n.

Padding contract: feature rows are zero-padded (``features.pad_features``);
the +inf-off-diagonal / zero-diagonal semantics of ``pad_distance_matrix``
are re-imposed per tile by ``features.masked_dist_tile`` using the static
``n_valid`` and each tile's global row/col offsets — so padded points land
outside every real focus exactly as in the materialized paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.features import masked_dist_tile
from repro.core.weights import DEFAULT_TIES, resolve_weight

from .tile_loops import cohesion_tile, focus_tile

__all__ = ["focus_fused_pallas", "cohesion_fused_pallas"]


def _focus_fused_kernel(xi_ref, xj_ref, xk_ref, u_ref, dyz_ref, dyx_ref,
                        ut_ref, *, metric, n_valid, block, block_y, block_z,
                        ties):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    xoff = pl.program_id(0) * block
    yoff = pl.program_id(1) * block_y
    zoff = k * block_z
    dxz = masked_dist_tile(xi_ref[...], xk_ref[...], metric, xoff, zoff,
                           n_valid, loop_d=True)   # (bx, bz)
    dyz_ref[...] = masked_dist_tile(xj_ref[...], xk_ref[...], metric, yoff,
                                    zoff, n_valid, loop_d=True)   # (by, bz)
    dxy = masked_dist_tile(xi_ref[...], xj_ref[...], metric, xoff, yoff,
                           n_valid, loop_d=True)   # (bx, by)
    dyx_ref[...] = dxy.T

    # the tile body of pald_focus._focus_kernel
    u_ref[...] += focus_tile(dxz, dyz_ref, dyx_ref, ut_ref, ties)


@functools.partial(jax.jit, static_argnames=(
    "metric", "n_valid", "block", "block_y", "block_z", "interpret", "ties"))
def focus_fused_pallas(
    X: jnp.ndarray,            # (m, d) zero-padded features
    *,
    metric: str = "euclidean",
    n_valid: int,
    block: int = 128,
    block_y: int | None = None,
    block_z: int = 512,
    interpret: bool = False,
    ties=DEFAULT_TIES,
) -> jnp.ndarray:
    """U (m, m) local-focus sizes computed straight from feature tiles."""
    ties = resolve_weight(ties)
    m, d = X.shape
    block_y = block_y or block
    assert m % block == 0 and m % block_y == 0 and m % block_z == 0
    grid = (m // block, m // block_y, m // block_z)
    kernel = functools.partial(
        _focus_fused_kernel, metric=metric, n_valid=n_valid,
        block=block, block_y=block_y, block_z=block_z, ties=ties,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, d), lambda i, j, k: (i, 0)),     # X rows (x)
            pl.BlockSpec((block_y, d), lambda i, j, k: (j, 0)),   # X rows (y)
            pl.BlockSpec((block_z, d), lambda i, j, k: (k, 0)),   # X rows (z)
        ],
        out_specs=pl.BlockSpec((block, block_y), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_y, block_z), jnp.float32)]
        + [pltpu.VMEM((block_y, block), jnp.float32)] * 2,
        interpret=interpret,
        name="focus_fused_pallas",
    )(X.astype(jnp.float32), X.astype(jnp.float32), X.astype(jnp.float32))


def _cohesion_fused_kernel(xi_ref, xj_ref, xk_ref, w_ref, c_ref, dyz_ref,
                           dyx_ref, wt_ref, *, metric, n_valid, block,
                           block_y, block_z, ties):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    xoff = pl.program_id(0) * block
    zoff = pl.program_id(1) * block_z
    yoff = k * block_y
    dxz = masked_dist_tile(xi_ref[...], xj_ref[...], metric, xoff, zoff,
                           n_valid, loop_d=True)   # (bx, bz)
    dyz_ref[...] = masked_dist_tile(xk_ref[...], xj_ref[...], metric, yoff,
                                    zoff, n_valid, loop_d=True)   # (by, bz)
    dxy = masked_dist_tile(xi_ref[...], xk_ref[...], metric, xoff, yoff,
                           n_valid, loop_d=True)   # (bx, by)
    dyx_ref[...] = dxy.T
    wt_ref[...] = w_ref[...].T
    own_wins = None
    if ties.needs_index_tiebreak:
        # the grid owns both offsets, so the index tiebreak is an iota
        xg = xoff + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        own_wins = lambda y0, g: [xg > yoff + y0 + j  # noqa: E731
                                  for j in range(g)]

    # the tile body of pald_cohesion._cohesion_kernel
    c_ref[...] += cohesion_tile(dxz, dyz_ref, dyx_ref, wt_ref, ties, own_wins)


@functools.partial(jax.jit, static_argnames=(
    "metric", "n_valid", "block", "block_y", "block_z", "interpret", "ties"))
def cohesion_fused_pallas(
    X: jnp.ndarray,            # (m, d) zero-padded features
    W: jnp.ndarray,            # (m, m) reciprocal weights
    *,
    metric: str = "euclidean",
    n_valid: int,
    block: int = 128,
    block_y: int | None = None,
    block_z: int = 512,
    interpret: bool = False,
    ties=DEFAULT_TIES,
) -> jnp.ndarray:
    """C (m, m) cohesion from feature tiles + precomputed weights."""
    ties = resolve_weight(ties)
    m, d = X.shape
    block_y = block_y or block
    assert W.shape == (m, m)
    assert m % block == 0 and m % block_y == 0 and m % block_z == 0
    grid = (m // block, m // block_z, m // block_y)
    kernel = functools.partial(
        _cohesion_fused_kernel, metric=metric, n_valid=n_valid,
        block=block, block_y=block_y, block_z=block_z, ties=ties,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, d), lambda i, j, k: (i, 0)),     # X rows (x)
            pl.BlockSpec((block_z, d), lambda i, j, k: (j, 0)),   # X rows (z)
            pl.BlockSpec((block_y, d), lambda i, j, k: (k, 0)),   # X rows (y)
            pl.BlockSpec((block, block_y), lambda i, j, k: (i, k)),  # W[X, Y]
        ],
        out_specs=pl.BlockSpec((block, block_z), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_y, block_z), jnp.float32)]
        + [pltpu.VMEM((block_y, block), jnp.float32)] * 2,
        interpret=interpret,
        name="cohesion_fused_pallas",
    )(X.astype(jnp.float32), X.astype(jnp.float32), X.astype(jnp.float32),
      W.astype(jnp.float32))
