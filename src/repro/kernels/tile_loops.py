"""The per-tile y-loops shared by every dense, tri and fused PaLD kernel.

Mosaic lowers no dynamic slice of a loaded value, and it accepts a dynamic
lane (column) index into a ref only at multiples of 128.  So the loops read
everything that varies with the reduction index y from refs, by row:

* ``dyz_ref[y]`` is row y of D[Y, Z-chunk], a (1, bz) row;
* ``dyx_ref[y]`` is row y of the transposed pair tile D[X, Y]^T, i.e.
  column y of D[X, Y], transposed back into a (bx, 1) threshold column.

The kernels fill the transposed scratch tiles once per grid step.  Values,
comparison order and accumulation order are those of a loop that slices the
loaded tiles, so interpret-mode results are exactly what such a loop gives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.weights import focus_weight, support_weight

__all__ = ["col", "focus_tile", "cohesion_tile"]


def col(ref_t, y):
    """Column ``y`` of the tile whose transpose ``ref_t`` holds: (rows, 1)."""
    return ref_t[pl.ds(y, 1), :].T


def focus_tile(dxz, dyz_ref, dyx_ref, ut_ref, ties):
    """(bx, by) block of U: sum_z focus_weight(d_xz, d_yz, d_xy).

    Column y is summed into row y of the (by, bx) scratch ``ut_ref``."""

    def body(y, carry):
        m = focus_weight(dxz, dyz_ref[pl.ds(y, 1), :], col(dyx_ref, y), ties)
        ut_ref[pl.ds(y, 1), :] = jnp.sum(m, axis=1, keepdims=True).T
        return carry

    jax.lax.fori_loop(0, ut_ref.shape[0], body, 0)
    return ut_ref[...].T


def cohesion_tile(dxz, dyz_ref, dyx_ref, wt_ref, ties, own_wins=None):
    """(bx, bz) block of C: sum_y support_weight(d_xz, d_yz, d_xy) * W[x, y].

    ``own_wins(y)`` gives the (bx, 1) global-index tiebreak for functionals
    that declare ``needs_index_tiebreak``; ``wt_ref`` holds W[X, Y]^T."""

    def body(y, acc):
        xw = None if own_wins is None else own_wins(y)
        g = support_weight(dxz, dyz_ref[pl.ds(y, 1), :], col(dyx_ref, y),
                           ties, xw)
        return acc + g * col(wt_ref, y)

    return jax.lax.fori_loop(0, dyx_ref.shape[0], body,
                             jnp.zeros(dxz.shape, jnp.float32))
