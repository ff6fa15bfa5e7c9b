"""Triangular-schedule Pallas kernel for PaLD pass 1 (block-symmetric).

The dense focus kernel visits all nb x nb block pairs; U is symmetric, so
half that work is mirrored.  This variant enumerates only the
nb(nb+1)/2 upper-triangular block pairs — the paper's triplet-style
symmetry exploitation lifted from scalars to VMEM blocks (DESIGN.md §4.3)
— using scalar-prefetched (xb, yb) index arrays
(``pltpu.PrefetchScalarGridSpec``): grid (npairs, nz), the pair's block
coordinates come from SMEM, and the compacted (npairs, b, b) output is
mirrored into the square U with one cheap jnp scatter outside the kernel.

Cuts pass-1 comparisons from n^3 to ~n^3/2 while keeping perfectly regular
vector access — the resolution of the paper's pairwise/triplet tradeoff
at kernel level.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.weights import DEFAULT_TIES, resolve_weight

from .tile_loops import focus_tile

__all__ = ["focus_tri_pallas"]


def _focus_tri_kernel(xs_ref, ys_ref, dxz_ref, dyz_ref, dxy_ref, u_ref,
                      dyx_ref, ut_ref, *, ties):
    # xs_ref/ys_ref are scalar-prefetch refs (consumed by the index maps);
    # the kernel body itself is identical to the dense focus kernel.
    del xs_ref, ys_ref
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    dyx_ref[...] = dxy_ref[...].T       # (b, b): row y = column y of D[X, Y]
    u_ref[0] += focus_tile(dxz_ref[...], dyz_ref, dyx_ref, ut_ref, ties)


@functools.partial(jax.jit, static_argnames=("block", "block_z", "interpret",
                                             "ties"))
def focus_tri_pallas(
    D: jnp.ndarray,
    *,
    block: int = 128,
    block_z: int = 512,
    interpret: bool = False,
    ties=DEFAULT_TIES,
) -> jnp.ndarray:
    """U = local-focus sizes via the upper-triangular block schedule."""
    ties = resolve_weight(ties)
    n = D.shape[0]
    assert n % block == 0 and n % block_z == 0
    nb = n // block
    xs_np, ys_np = np.triu_indices(nb)
    npairs = xs_np.shape[0]
    xs = jnp.asarray(xs_np, jnp.int32)
    ys = jnp.asarray(ys_np, jnp.int32)
    D = D.astype(jnp.float32)

    grid = (npairs, n // block_z)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            # D[X, z-chunk]: row block from the prefetched xs
            pl.BlockSpec((block, block_z), lambda t, k, xs, ys: (xs[t], k)),
            # D[Y, z-chunk]
            pl.BlockSpec((block, block_z), lambda t, k, xs, ys: (ys[t], k)),
            # D[X, Y]
            pl.BlockSpec((block, block), lambda t, k, xs, ys: (xs[t], ys[t])),
        ],
        out_specs=pl.BlockSpec(
            (1, block, block), lambda t, k, xs, ys: (t, 0, 0)
        ),
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)] * 2,
    )
    packed = pl.pallas_call(
        functools.partial(_focus_tri_kernel, ties=ties),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((npairs, block, block), jnp.float32),
        interpret=interpret,
        name="focus_tri_pallas",
    )(xs, ys, D, D, D)

    # mirror the compacted upper-tri blocks into the square U (O(n^2) move)
    U = jnp.zeros((n, n), jnp.float32)
    U = U.at[xs[:, None, None] * block + jnp.arange(block)[None, :, None],
             ys[:, None, None] * block + jnp.arange(block)[None, None, :]
             ].set(packed)
    # lower triangle by symmetry; diagonal blocks overwrite themselves
    Ut = U.T
    tri = jnp.tril(jnp.ones((n, n), bool), -1)
    return jnp.where(tri, Ut, U)
