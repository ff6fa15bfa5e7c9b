"""The per-tile y-loops shared by every dense, tri and fused PaLD kernel.

Mosaic lowers no dynamic slice of a loaded value, and it accepts a dynamic
lane (column) index into a ref only at multiples of 128.  So the loops read
everything that varies with the reduction index y from refs, by row:

* ``dyz_ref[y]`` is row y of D[Y, Z-chunk], a (1, bz) row;
* ``dyx_ref[y]`` is row y of the transposed pair tile D[X, Y]^T, i.e.
  column y of D[X, Y], transposed back into a (bx, 1) threshold column.

The kernels fill the transposed scratch tiles once per grid step.

Each loop step takes ``y_group(rows)`` consecutive y: one sublane tile (8)
wherever the tile's row count allows, so the step reads the group's
threshold (and weight) columns with one aligned (8, bx) load and one
transpose, runs the 8 y-bodies unrolled, side by side for the scheduler,
and writes the group's output rows with one aligned store.  Values,
comparison order and accumulation order (ascending y) are those of a loop
that takes one y at a time, so results are bitwise those of the per-row
loop, which tiles of other row counts still run (group of 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.weights import focus_weight, support_weight

__all__ = ["y_group", "y_loop", "cols", "sum_rows", "focus_tile",
           "cohesion_tile"]


def y_group(rows):
    """y the loop takes per step: a sublane tile (8) where it divides
    ``rows``, else 1 (the per-row loop)."""
    return 8 if rows % 8 == 0 else 1


def y_loop(rows, step, carry):
    """``step(y0, g, carry)`` for y0 = 0, g, 2g, ... < rows, g = y_group(rows)."""
    g = y_group(rows)
    return jax.lax.fori_loop(
        0, rows // g, lambda i, c: step(pl.multiple_of(i * g, g), g, c), carry)


def cols(ref_t, y0, g):
    """Columns y0 .. y0+g-1 of the tile whose transpose ``ref_t`` holds, as
    g (rows, 1) values, from one aligned (g, rows) read and one transpose."""
    block = ref_t[pl.ds(y0, g), :].T
    return [block[:, j:j + 1] for j in range(g)]


def sum_rows(x):
    """(rows, w) -> (1, w) in one fixed order: the 8-row slabs in ascending
    order, then the 8 rows of their sum.  Interpret mode otherwise sums in
    whatever order XLA's fusion of the surrounding loop step picks, which
    differs between group sizes."""
    if x.shape[0] % 8:
        return jnp.sum(x, axis=0, keepdims=True)
    p = x[0:8]
    for i in range(8, x.shape[0], 8):
        p = p + x[i:i + 8]
    return jnp.sum(p, axis=0, keepdims=True)


def focus_tile(dxz, dyz_ref, dyx_ref, ut_ref, ties):
    """(bx, by) block of U: sum_z focus_weight(d_xz, d_yz, d_xy).

    Column y is summed into row y of the (by, bx) scratch ``ut_ref``."""

    def step(y0, g, carry):
        sums = [jnp.sum(focus_weight(dxz, dyz_ref[pl.ds(y0 + j, 1), :], thr,
                                     ties), axis=1, keepdims=True)
                for j, thr in enumerate(cols(dyx_ref, y0, g))]
        ut_ref[pl.ds(y0, g), :] = jnp.concatenate(sums, axis=1).T
        return carry

    y_loop(ut_ref.shape[0], step, 0)
    return ut_ref[...].T


def cohesion_tile(dxz, dyz_ref, dyx_ref, wt_ref, ties, own_wins=None):
    """(bx, bz) block of C: sum_y support_weight(d_xz, d_yz, d_xy) * W[x, y].

    ``own_wins(y0, g)`` gives the g (bx, 1) global-index tiebreaks of
    y0 .. y0+g-1 for functionals that declare ``needs_index_tiebreak``;
    ``wt_ref`` holds W[X, Y]^T."""

    def step(y0, g, acc):
        xw = [None] * g if own_wins is None else own_wins(y0, g)
        for j, (thr, wy) in enumerate(zip(cols(dyx_ref, y0, g),
                                          cols(wt_ref, y0, g))):
            acc = acc + support_weight(dxz, dyz_ref[pl.ds(y0 + j, 1), :], thr,
                                       ties, xw[j]) * wy
        return acc

    return y_loop(dyx_ref.shape[0], step, jnp.zeros(dxz.shape, jnp.float32))
