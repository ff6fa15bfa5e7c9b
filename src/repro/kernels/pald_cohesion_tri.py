"""Triangular-schedule Pallas kernel for PaLD pass 2 (block-symmetric).

The dense cohesion kernel (pald_cohesion) runs the full (nx, nz, ny) grid:
every ordered (X, Y) block pair is visited and only the x-role update

    C[x, z] += (d_xz < d_yz) & (d_xz < d_xy) * W[x, y]

is applied.  Cohesion support is a property of the *unordered* pair, so half
of those visits redo comparisons whose outcome is determined by the mirrored
visit.  This variant is the pass-2 counterpart of ``pald_focus_tri``
(DESIGN.md §4.3): only the nb(nb+1)/2 upper-triangular (X, Y) block pairs are
enumerated — scalar-prefetched (xb, yb) index arrays via
``pltpu.PrefetchScalarGridSpec`` — and each off-diagonal visit performs BOTH
role updates:

    x-role:  C[x, z] += support_weight(d_xz, d_yz, d_xy) * W[x, y]
    y-role:  C[y, z] += support_weight(d_yz, d_xz, d_xy) * W[x, y]

with the support contribution supplied by the resolved weight functional
shared across every path (``core/weights.py``).
Before PR 3 the y-role reused the x-role's comparison through its complement
(ties -> y, i.e. ``ties='ignore'``) while diagonal blocks ran the one-sided
strict x-role (``ties='drop'``), so the schedule matched *neither* reference
on tied input — the shared helper computes both roles explicitly in the
requested mode instead, with the global block indices (already prefetched
for the index maps) providing the ``ties='ignore'`` index tiebreak.

Accumulation layout (grid = (nz, npairs), pairs innermost, x-major order):

* x-role → ``Cx`` (n, n): output block (block, block_z) at (xs[t], k).  With
  pairs sorted x-major, all visits to one Cx block are consecutive grid
  steps, so the block stays resident in VMEM and is accumulated in-kernel
  (same discipline as the dense kernel's innermost y axis).
* y-role → ``Cy`` (n, block_z * nz = n): output block (n, block_z) at
  (0, k) — the full column slab for the current z-chunk.  Its index map is
  constant in t, so it too is revisited only consecutively; rows ys[t] are
  updated in place with a dynamic row-range store.  VMEM cost n * block_z
  floats (twice, double-buffered), which bounds block_z for large n (the
  autotuner's job).

Every y-indexed read in the tile loop is a row read: D[X, Y] and W[X, Y]
are transposed into (b, b) scratch once per step, and the y-role rows are
built in a (b, bz) scratch, eight y at a time (``tile_loops``).

Diagonal blocks (xb == yb) apply the dense one-sided x-role over the full
(block, block) pair square — that already covers both orders of every
in-block pair — and skip the y-role.

C = Cx + Cy is one O(n^2) merge outside the kernel.  Comparison count drops
from 2 n^3 (dense ordered grid) to ~1.5 n^3 with half the D/W block traffic.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.weights import DEFAULT_TIES, resolve_weight, support_weight

from .tile_loops import cols, sum_rows, y_loop

__all__ = ["cohesion_tri_pallas"]


def _cohesion_tri_kernel(xs_ref, ys_ref, dxz_ref, dyz_ref, dxy_ref, w_ref,
                         cx_ref, cy_ref, dyx_ref, wt_ref, ry_ref, *, ties):
    t = pl.program_id(1)
    xb = xs_ref[t]
    yb = ys_ref[t]
    xprev = xs_ref[jnp.maximum(t - 1, 0)]

    @pl.when((t == 0) | (xb != xprev))
    def _init_cx():
        cx_ref[...] = jnp.zeros_like(cx_ref)

    @pl.when(t == 0)
    def _init_cy():
        cy_ref[...] = jnp.zeros_like(cy_ref)

    dxz = dxz_ref[...]                  # (b, bz)  D[X, z-chunk]
    dyx_ref[...] = dxy_ref[...].T       # (b, b)   row y = column y of D[X, Y]
    wt_ref[...] = w_ref[...].T          # (b, b)   row y = column y of W[X, Y]
    b = dyx_ref.shape[0]
    is_diag = xb == yb

    def step(y0, g, acc_x):
        ry = []
        for j, (thr, wy) in enumerate(zip(cols(dyx_ref, y0, g),    # (b, 1) d_xy
                                          cols(wt_ref, y0, g))):   # (b, 1) w
            y = y0 + j
            row = dyz_ref[pl.ds(y, 1), :]   # (1, bz) d_yz
            xw = yw = None
            if ties.needs_index_tiebreak:
                # global-index tiebreak from the prefetched block coordinates;
                # on diagonal blocks the one-sided x-role visits both orders of
                # every in-block pair, so xw alone implements the mode there
                xg = xb * b + jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
                yg = yb * b + y
                xw, yw = xg > yg, yg > xg
            gx = support_weight(dxz, row, thr, ties, xw)            # (b, bz)
            acc_x = acc_x + gx * wy
            # y-role: one output row, reduced over the x axis
            gy = support_weight(row, dxz, thr, ties, yw)            # (b, bz)
            ry.append(sum_rows(gy * wy))
        ry_ref[pl.ds(y0, g), :] = jnp.concatenate(ry, axis=0)
        return acc_x

    cx_ref[...] += y_loop(b, step, jnp.zeros(dxz.shape, jnp.float32))

    @pl.when(jnp.logical_not(is_diag))
    def _update_cy():
        start = yb * b
        cy_ref[pl.ds(start, b), :] += ry_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "block_z", "interpret",
                                             "ties"))
def cohesion_tri_pallas(
    D: jnp.ndarray,
    W: jnp.ndarray,
    *,
    block: int = 128,
    block_z: int = 512,
    interpret: bool = False,
    ties=DEFAULT_TIES,
) -> jnp.ndarray:
    """C (n, n) via the upper-triangular block schedule (square case only)."""
    ties = resolve_weight(ties)
    n = D.shape[0]
    assert W.shape == (n, n)
    assert n % block == 0 and n % block_z == 0
    nb = n // block
    xs_np, ys_np = np.triu_indices(nb)   # row-major: xs non-decreasing
    npairs = xs_np.shape[0]
    xs = jnp.asarray(xs_np, jnp.int32)
    ys = jnp.asarray(ys_np, jnp.int32)
    D = D.astype(jnp.float32)
    W = W.astype(jnp.float32)

    grid = (n // block_z, npairs)        # z-chunk outer, pairs inner
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            # D[X, z-chunk]
            pl.BlockSpec((block, block_z), lambda k, t, xs, ys: (xs[t], k)),
            # D[Y, z-chunk]
            pl.BlockSpec((block, block_z), lambda k, t, xs, ys: (ys[t], k)),
            # D[X, Y]
            pl.BlockSpec((block, block), lambda k, t, xs, ys: (xs[t], ys[t])),
            # W[X, Y]
            pl.BlockSpec((block, block), lambda k, t, xs, ys: (xs[t], ys[t])),
        ],
        out_specs=[
            # x-role: row block of Cx, consecutive revisits within an x-run
            pl.BlockSpec((block, block_z), lambda k, t, xs, ys: (xs[t], k)),
            # y-role: whole column slab of Cy, resident across the k-th sweep
            pl.BlockSpec((n, block_z), lambda k, t, xs, ys: (0, k)),
        ],
        # D[X, Y]^T and W[X, Y]^T for the y-loop's column reads, and the
        # y-role rows of the current pair
        scratch_shapes=[pltpu.VMEM((block, block), jnp.float32)] * 2
        + [pltpu.VMEM((block, block_z), jnp.float32)],
    )
    Cx, Cy = pl.pallas_call(
        functools.partial(_cohesion_tri_kernel, ties=ties),
        grid_spec=spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.float32),
            jax.ShapeDtypeStruct((n, n), jnp.float32),
        ],
        interpret=interpret,
        name="cohesion_tri_pallas",
    )(xs, ys, D, D, D, W)
    return Cx + Cy
