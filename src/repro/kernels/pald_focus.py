"""Pallas TPU kernel for PaLD pass 1: local-focus sizes.

    U[x, y] = sum_z (D[x,z] < D[x,y]) | (D[y,z] < D[x,y])

Grid (nx, ny, nz) with the z-reduction innermost, so the output block
U[X, Y] stays resident in VMEM across all z steps (Pallas revisiting rule),
exactly like a blocked-matmul accumulator — the TPU analogue of the paper's
"U_XY remains in fast memory through the pass" (Theorem 4.1 proof).

Inside the kernel we iterate the y dimension with a fori_loop over rows so
the live working set is (bx, bz) vectors instead of a (bx, by, bz) cube
(``tile_loops.focus_tile``); two (by, bx) scratch tiles hold D[X, Y]^T and
the U block being built, so every y-indexed read is a row read.
VMEM = D_XZ + D_YZ + D_XY + U_XY + 2 scratch = 2*bx*bz + 4*bx*by floats.
With bx=by=128, bz=512 that is 0.75 MiB, well under ~16 MiB VMEM, and all
tile shapes are (8,128)-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.weights import DEFAULT_TIES, resolve_weight

from .tile_loops import focus_tile

__all__ = ["focus_pallas"]


def _focus_kernel(dxz_ref, dyz_ref, dxy_ref, u_ref, dyx_ref, ut_ref, *, ties):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    dyx_ref[...] = dxy_ref[...].T       # (by, bx): row y = column y of D[X, Y]
    u_ref[...] += focus_tile(dxz_ref[...], dyz_ref, dyx_ref, ut_ref, ties)


@functools.partial(jax.jit, static_argnames=("block_x", "block_y", "block_z",
                                             "interpret", "ties"))
def focus_general_pallas(
    DXZ: jnp.ndarray,  # (mx, mz) distances x -> z
    DYZ: jnp.ndarray,  # (my, mz) distances y -> z
    DXY: jnp.ndarray,  # (mx, my) distances x -> y
    *,
    block_x: int = 128,
    block_y: int = 128,
    block_z: int = 512,
    interpret: bool = False,
    ties=DEFAULT_TIES,
) -> jnp.ndarray:
    """U (mx, my) = sum_z focus_weight(DXZ[x,z], DYZ[y,z], DXY[x,y]) for the
    resolved weight functional (strict membership shown above).

    The rectangular form is what the distributed (shard_map) algorithms call
    per device, with DXZ/DYZ being locally-owned / gathered row blocks.  The
    sequential square case passes the same matrix three times.
    """
    ties = resolve_weight(ties)
    mx, mz = DXZ.shape
    my = DYZ.shape[0]
    assert DYZ.shape[1] == mz and DXY.shape == (mx, my)
    assert mx % block_x == 0 and my % block_y == 0 and mz % block_z == 0
    grid = (mx // block_x, my // block_y, mz // block_z)
    return pl.pallas_call(
        functools.partial(_focus_kernel, ties=ties),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_x, block_z), lambda i, j, k: (i, k)),  # DXZ
            pl.BlockSpec((block_y, block_z), lambda i, j, k: (j, k)),  # DYZ
            pl.BlockSpec((block_x, block_y), lambda i, j, k: (i, j)),  # DXY
        ],
        out_specs=pl.BlockSpec((block_x, block_y), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mx, my), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_y, block_x), jnp.float32)] * 2,
        interpret=interpret,
        name="focus_pallas",
    )(DXZ.astype(jnp.float32), DYZ.astype(jnp.float32), DXY.astype(jnp.float32))


def focus_pallas(
    D: jnp.ndarray,
    *,
    block_xy: int = 128,
    block_z: int = 512,
    interpret: bool = False,
    ties=DEFAULT_TIES,
) -> jnp.ndarray:
    """Square local-focus size matrix (sequential case)."""
    return focus_general_pallas(
        D, D, D, block_x=block_xy, block_y=block_xy, block_z=block_z,
        interpret=interpret, ties=ties
    )
