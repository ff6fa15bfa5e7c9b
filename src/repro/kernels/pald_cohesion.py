"""Pallas TPU kernel for PaLD pass 2: cohesion accumulation.

    C[x, z] = sum_y support_weight(D[x,z], D[y,z], D[x,y]) * W[x,y]

with W = 1/U (zero diagonal / padded entries; computed outside the kernel so
the reciprocal is done once — the paper's "precompute reciprocals" trick)
and the support contribution supplied by the resolved weight functional
shared with every other path (``core/weights.py``; the default
``ties='drop'`` is the classic strict ``(d_xz < d_yz) & (d_xz < d_xy)``).

Grid (nx, nz, ny) with the y-reduction innermost: the output block C[X, Z]
stays resident in VMEM across all y steps.  The kernel updates unit-stride
(bx, bz) rows of C — the TPU translation of the paper's "updating columns of
C instead" stride-1 optimization (their C is updated column-wise because the
z loop streams columns; our block layout makes the streamed dim contiguous).

Functionals declaring ``needs_index_tiebreak`` (the built-in ``'ignore'``)
need the global-index x>y predicate.  Two equivalent static specs:

- ``XW`` (mx, my) float32, 1.0 where global index x > global index y,
  riding the same BlockSpec as W — for callers who already hold such a
  tile (distributed shard bodies reuse their per-shard derivation);
- ``xw_offsets=(row_off, col_off)`` — the kernel derives the predicate
  per (bx, by) tile from grid position plus the static offsets via a
  row iota, so no (mx, my) tiebreak array ever materializes.  This is
  the default route for the sequential square case (offsets (0, 0)).

The y-loop (``tile_loops.cohesion_tile``) reads D[X, Y], W (and XW)
columns as rows of transposed (by, bx) scratch copies, filled once per
grid step.

VMEM = D_XZ + C_XZ + D_YZ + D_XY + W_XY + 2 scratch (+ XW_XY and its
scratch for the explicit-XW route) = 3*bx*bz + 4*bx*by (+ 2*bx*by) floats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.weights import DEFAULT_TIES, resolve_weight

from .tile_loops import cohesion_tile, cols

__all__ = ["cohesion_pallas"]


def _cohesion_kernel(dxz_ref, dyz_ref, dxy_ref, w_ref, c_ref, dyx_ref, wt_ref,
                     *, ties):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    dyx_ref[...] = dxy_ref[...].T       # (by, bx): row y = column y of D[X, Y]
    wt_ref[...] = w_ref[...].T
    c_ref[...] += cohesion_tile(dxz_ref[...], dyz_ref, dyx_ref, wt_ref, ties)


def _cohesion_kernel_xw(dxz_ref, dyz_ref, dxy_ref, w_ref, xw_ref, c_ref,
                        dyx_ref, wt_ref, xwt_ref, *, ties):
    """Index-tiebreak variant with an explicit (bx, by) tiebreak tile."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    dyx_ref[...] = dxy_ref[...].T
    wt_ref[...] = w_ref[...].T
    xwt_ref[...] = xw_ref[...].T        # 1.0 where global x index > global y
    c_ref[...] += cohesion_tile(dxz_ref[...], dyz_ref, dyx_ref, wt_ref, ties,
                                lambda y0, g: [c > 0.5 for c in
                                               cols(xwt_ref, y0, g)])


def _cohesion_kernel_iota(dxz_ref, dyz_ref, dxy_ref, w_ref, c_ref, dyx_ref,
                          wt_ref, *, ties, row_off, col_off, block_x, block_y):
    """Index-tiebreak variant deriving x>y per tile from grid position.

    Global x index of tile row r is ``row_off + i*block_x + r``; global y
    index of reduction lane y is ``col_off + k*block_y + y`` — a row iota
    plus two scalars, so no dense (mx, my) tiebreak array is ever built.
    """
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    dyx_ref[...] = dxy_ref[...].T
    wt_ref[...] = w_ref[...].T
    xg = row_off + i * block_x + jax.lax.broadcasted_iota(
        jnp.int32, (block_x, 1), 0)                             # (bx, 1)
    ybase = col_off + k * block_y
    c_ref[...] += cohesion_tile(dxz_ref[...], dyz_ref, dyx_ref, wt_ref, ties,
                                lambda y0, g: [xg > ybase + y0 + j
                                               for j in range(g)])


@functools.partial(jax.jit, static_argnames=("block_x", "block_z", "block_y",
                                             "interpret", "ties",
                                             "xw_offsets"))
def cohesion_general_pallas(
    DXZ: jnp.ndarray,  # (mx, mz)
    DYZ: jnp.ndarray,  # (my, mz)
    DXY: jnp.ndarray,  # (mx, my)
    W: jnp.ndarray,    # (mx, my)
    XW: jnp.ndarray | None = None,  # (mx, my) explicit tiebreak tile
    *,
    block_x: int = 128,
    block_z: int = 512,
    block_y: int = 128,
    interpret: bool = False,
    ties=DEFAULT_TIES,
    xw_offsets: tuple[int, int] | None = None,
) -> jnp.ndarray:
    """C (mx, mz) = sum_y support_weight(DXZ, DYZ[y], DXY[:,y]) * W[:,y].

    Rectangular form for distributed per-device compute; the square
    sequential case passes D three times.  Functionals declaring
    ``needs_index_tiebreak`` additionally require either ``XW`` (1.0 where
    global x index > global y index) or static ``xw_offsets=(row_off,
    col_off)`` global offsets from which the kernel derives the predicate
    per tile.
    """
    wfun = resolve_weight(ties)
    mx, mz = DXZ.shape
    my = DYZ.shape[0]
    assert DYZ.shape[1] == mz and DXY.shape == (mx, my) and W.shape == (mx, my)
    assert mx % block_x == 0 and mz % block_z == 0 and my % block_y == 0
    grid = (mx // block_x, mz // block_z, my // block_y)
    pair_spec = pl.BlockSpec((block_x, block_y), lambda i, j, k: (i, k))
    in_specs = [
        pl.BlockSpec((block_x, block_z), lambda i, j, k: (i, j)),  # DXZ
        pl.BlockSpec((block_y, block_z), lambda i, j, k: (k, j)),  # DYZ
        pair_spec,                                                 # DXY
        pair_spec,                                                 # W
    ]
    args = [DXZ.astype(jnp.float32), DYZ.astype(jnp.float32),
            DXY.astype(jnp.float32), W.astype(jnp.float32)]
    if wfun.needs_index_tiebreak:
        if XW is not None:
            assert XW.shape == (mx, my)
            in_specs.append(pair_spec)                             # XW
            args.append(XW.astype(jnp.float32))
            kernel = functools.partial(_cohesion_kernel_xw, ties=wfun)
        elif xw_offsets is not None:
            kernel = functools.partial(
                _cohesion_kernel_iota, ties=wfun,
                row_off=int(xw_offsets[0]), col_off=int(xw_offsets[1]),
                block_x=block_x, block_y=block_y)
        else:
            raise ValueError(f"weight {wfun.name!r} needs XW or xw_offsets "
                             "(global-index tiebreak)")
    else:
        kernel = functools.partial(_cohesion_kernel, ties=wfun)
    # transposed (by, bx) scratch tiles: D[X, Y]^T, W^T (and XW^T)
    n_scratch = len(in_specs) - 2
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_x, block_z), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mx, mz), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_y, block_x), jnp.float32)] * n_scratch,
        interpret=interpret,
        name="cohesion_pallas",
    )(*args)


def cohesion_pallas(
    D: jnp.ndarray,
    W: jnp.ndarray,
    *,
    block_x: int = 128,
    block_z: int = 512,
    block_y: int = 128,
    interpret: bool = False,
    ties=DEFAULT_TIES,
    XW: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Square cohesion matrix (un-normalized, sequential case)."""
    offs = (0, 0) if XW is None else None
    return cohesion_general_pallas(
        D, D, D, W, XW, block_x=block_x, block_z=block_z, block_y=block_y,
        interpret=interpret, ties=ties, xw_offsets=offs,
    )
