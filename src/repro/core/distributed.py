"""Distributed-memory PaLD under ``jax.shard_map``.

The paper proves sequential communication optimality (W = Theta(n^3/sqrt(M)))
and parallelizes on one shared-memory node.  This module is the
distributed-memory extension (DESIGN.md §5): the same two-pass structure
mapped onto a TPU mesh, with per-device compute delegated to the Pallas
kernel primitives (``repro.kernels.ops.focus_general`` /
``cohesion_general``) and inter-device movement expressed with
``jax.lax`` collectives so XLA can overlap compute with communication.

Strategies
----------
allgather     D row-sharded; one all-gather of D; embarrassing row-parallel.
              Comm n^2 words/device, memory n^2/device.  (OpenMP-pairwise
              analogue: every thread reads all of D.)
ring          D row-sharded; row blocks rotate via ppermute; comm n^2
              words/device but memory only O(n^2/P).  Compute of step s
              overlaps the permute for step s+1.
2d            D block-sharded over (rows x cols) mesh axes; all-gathers along
              each axis; comm ~3 n^2/sqrt(P) words/device -- the SUMMA-style
              communication-optimal schedule (distributed analogue of the
              paper's 3NL-optimal blocking).
2d+pod-stream D as 2d but the slow ``pod`` axis is *streamed*: the per-pod
              row slab rotates across pods via ppermute while both passes
              consume it chunk-by-chunk, so each word crosses the inter-pod
              link once and peak gather memory drops by the pod count
              (the NUMA-placement analogue; DESIGN.md §2).

All strategies return C row-sharded the same way D arrived, un-normalized
(the ``pald_distributed`` wrapper handles padding + 1/(n-1)).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import engine as _engine
from .weights import index_xwins as _xwins_rows

__all__ = ["pald_distributed", "pald_distributed_from_features"]


def _weights_rows(U_rows: jnp.ndarray, row_offset: jnp.ndarray, n_valid) -> jnp.ndarray:
    """W = 1/U for a row block: zero diagonal (global row == col) and padding."""
    m, n = U_rows.shape
    rows = row_offset + jnp.arange(m)
    diag = rows[:, None] == jnp.arange(n)[None, :]
    W = jnp.where(diag | (U_rows == 0), 0.0, 1.0 / jnp.where(U_rows == 0, 1.0, U_rows))
    if n_valid is not None:
        W = W * (rows[:, None] < n_valid) * (jnp.arange(n)[None, :] < n_valid)
    return W.astype(jnp.float32)


# ---------------------------------------------------------------------------
# 1-D strategies: D row-sharded over a single (flattened) axis
# ---------------------------------------------------------------------------
def _allgather_body(Dloc, *, axis, n_valid, plan):
    m = Dloc.shape[0]
    Dall = jax.lax.all_gather(Dloc, axis, tiled=True)          # (n, n)
    off = jax.lax.axis_index(axis) * m
    U = plan.focus_general(Dloc, Dall, Dloc)                   # (m, n)
    W = _weights_rows(U, off, n_valid)
    xw = (_xwins_rows(off, m, 0, Dall.shape[0])
          if plan.weight.needs_index_tiebreak else None)
    return plan.cohesion_general(Dloc, Dall, Dloc, W, xwins=xw)


def _ring_body(Dloc, *, axis, p, n_valid, plan):
    m, n = Dloc.shape
    fwd = [(j, (j + 1) % p) for j in range(p)]
    r = jax.lax.axis_index(axis)

    def owner_cols(s):
        # after s forward shifts we hold the block originally on (r - s) % p
        return ((r - s) % p) * m

    # ---- pass 1: local-focus rows ----------------------------------------
    def f_step(s, carry):
        blk, U = carry
        nxt = jax.lax.ppermute(blk, axis, fwd)                  # comm ...
        off = owner_cols(s)
        Dxy = jax.lax.dynamic_slice(Dloc, (0, off), (m, m))
        Ublk = plan.focus_general(Dloc, blk, Dxy)               # ... overlaps compute
        U = jax.lax.dynamic_update_slice(U, Ublk, (0, off))
        return nxt, U

    _, U = jax.lax.fori_loop(
        0, p, f_step, (Dloc, jnp.zeros((m, n), jnp.float32))
    )
    W = _weights_rows(U, r * m, n_valid)

    # ---- pass 2: cohesion rows --------------------------------------------
    def c_step(s, carry):
        blk, C = carry
        nxt = jax.lax.ppermute(blk, axis, fwd)
        off = owner_cols(s)
        Dxy = jax.lax.dynamic_slice(Dloc, (0, off), (m, m))
        Wxy = jax.lax.dynamic_slice(W, (0, off), (m, m))
        xw = (_xwins_rows(r * m, m, off, m)
              if plan.weight.needs_index_tiebreak else None)
        C = C + plan.cohesion_general(Dloc, blk, Dxy, Wxy, xwins=xw)
        return nxt, C

    _, C = jax.lax.fori_loop(
        0, p, c_step, (Dloc, jnp.zeros((m, n), jnp.float32))
    )
    return C


# ---------------------------------------------------------------------------
# feature-sharded 1-D strategies: X row-sharded, distances computed on-device
#
# Communicating the (n, d) feature matrix instead of the (n, n) distance
# matrix shrinks every collective by a factor of n/d: the all-gather moves
# n*d words (vs n^2) and the ring rotates (m, d) feature blocks (vs (m, n)
# distance rows).  Each device re-imposes the +inf/zero-diag padding contract
# locally via ``features.masked_dist_tile`` — padded feature rows are zeros,
# which every metric maps to a finite distance, so masking by global index
# is what keeps padded points out of real foci.
# ---------------------------------------------------------------------------
def _feat_allgather_body(Xloc, *, axis, metric, n_valid, plan):
    from .features import masked_dist_tile

    m = Xloc.shape[0]
    nv = n_valid
    Xall = jax.lax.all_gather(Xloc, axis, tiled=True)            # (n, d)
    n = Xall.shape[0]
    if nv is None:
        nv = n
    off = jax.lax.axis_index(axis) * m
    Dall = masked_dist_tile(Xall, Xall, metric, 0, 0, nv)        # (n, n) local
    Dloc = jax.lax.dynamic_slice(Dall, (off, 0), (m, n))         # own rows
    U = plan.focus_general(Dloc, Dall, Dloc)
    W = _weights_rows(U, off, n_valid)
    xw = (_xwins_rows(off, m, 0, n)
          if plan.weight.needs_index_tiebreak else None)
    return plan.cohesion_general(Dloc, Dall, Dloc, W, xwins=xw)


def _feat_ring_body(Xloc, *, axis, p, metric, n_valid, plan):
    from .features import masked_dist_tile

    m = Xloc.shape[0]
    fwd = [(j, (j + 1) % p) for j in range(p)]
    r = jax.lax.axis_index(axis)
    # the z axis of both passes needs every point's features; gathering X is
    # the one O(n d) collective (the ring itself only moves (m, d) blocks)
    Xall = jax.lax.all_gather(Xloc, axis, tiled=True)            # (n, d)
    n = Xall.shape[0]
    nv = n if n_valid is None else n_valid
    Dloc = masked_dist_tile(Xloc, Xall, metric, r * m, 0, nv)    # (m, n)

    def owner_off(s):
        return ((r - s) % p) * m

    # ---- pass 1: local-focus rows -----------------------------------------
    def f_step(s, carry):
        xblk, U = carry
        nxt = jax.lax.ppermute(xblk, axis, fwd)                  # (m, d) comm
        off = owner_off(s)
        Dblk = masked_dist_tile(xblk, Xall, metric, off, 0, nv)  # recomputed
        Dxy = jax.lax.dynamic_slice(Dloc, (0, off), (m, m))
        Ublk = plan.focus_general(Dloc, Dblk, Dxy)
        U = jax.lax.dynamic_update_slice(U, Ublk, (0, off))
        return nxt, U

    _, U = jax.lax.fori_loop(
        0, p, f_step, (Xloc, jnp.zeros((m, n), jnp.float32))
    )
    W = _weights_rows(U, r * m, n_valid)

    # ---- pass 2: cohesion rows --------------------------------------------
    def c_step(s, carry):
        xblk, C = carry
        nxt = jax.lax.ppermute(xblk, axis, fwd)
        off = owner_off(s)
        Dblk = masked_dist_tile(xblk, Xall, metric, off, 0, nv)
        Dxy = jax.lax.dynamic_slice(Dloc, (0, off), (m, m))
        Wxy = jax.lax.dynamic_slice(W, (0, off), (m, m))
        xw = (_xwins_rows(r * m, m, off, m)
              if plan.weight.needs_index_tiebreak else None)
        C = C + plan.cohesion_general(Dloc, Dblk, Dxy, Wxy, xwins=xw)
        return nxt, C

    _, C = jax.lax.fori_loop(
        0, p, c_step, (Xloc, jnp.zeros((m, n), jnp.float32))
    )
    return C


# ---------------------------------------------------------------------------
# 2-D strategy (comm-optimal), optionally streaming over the pod axis
# ---------------------------------------------------------------------------
def _2d_body(Dblk, *, row_axes, col_axis, stream_axis, n_valid, mesh_shape,
             plan):
    mr, mc = Dblk.shape
    gathered_rows = tuple(a for a in row_axes if a != stream_axis)
    # row index offset of this device's X block within the global ordering
    roff = jax.lax.axis_index(row_axes) * mr if len(row_axes) == 1 else (
        jax.lax.axis_index(row_axes[0]) * (mr * mesh_shape[row_axes[1]])
        + jax.lax.axis_index(row_axes[1]) * mr
    )
    coff = jax.lax.axis_index(col_axis) * mc

    # D rows for the local X block, all columns: gather along the column axis.
    Grow = jax.lax.all_gather(Dblk, col_axis, axis=1, tiled=True)     # (mx, n)
    n = Grow.shape[1]
    mx = mr * 1
    if stream_axis is None:
        # full gather along all row axes: slab = all rows, local col block
        slab = jax.lax.all_gather(Dblk, row_axes, axis=0, tiled=True)  # (n, mc)
        nsteps, slab_rows = 1, n
    else:
        # gather along the fast intra-pod axes only; rotate pod slabs
        slab = jax.lax.all_gather(Dblk, gathered_rows, axis=0, tiled=True)
        nsteps, slab_rows = mesh_shape[stream_axis], slab.shape[0]
    npods = nsteps
    fwd = None if stream_axis is None else [
        (j, (j + 1) % npods) for j in range(npods)
    ]
    pod_idx = 0 if stream_axis is None else jax.lax.axis_index(stream_axis)

    def slab_row_offset(s):
        if stream_axis is None:
            return jnp.int32(0)
        return ((pod_idx - s) % npods) * slab_rows

    # ---- pass 1: U[Xi, Yj] = sum_z mask, z streamed in slab chunks ---------
    # slab holds D[chunk_rows, Zj]; by symmetry slab.T = d_{y in Yj, z in chunk}
    def f_step(s, carry):
        blk, U = carry
        nxt = blk if stream_axis is None else jax.lax.ppermute(blk, stream_axis, fwd)
        zoff = slab_row_offset(s)
        dxz = jax.lax.dynamic_slice(Grow, (0, zoff), (mr, slab_rows))
        U = U + plan.focus_general(dxz, blk.T, Dblk)
        return nxt, U

    _, U = jax.lax.fori_loop(0, nsteps, f_step, (slab, jnp.zeros((mr, mc), jnp.float32)))

    # weights need full U rows: gather along the column axis (intra-pod)
    Urow = jax.lax.all_gather(U, col_axis, axis=1, tiled=True)         # (mx, n)
    Wrow = _weights_rows(Urow, roff, n_valid)

    # ---- pass 2: C[Xi, Zj] = sum_y mask * w, y streamed in slab chunks -----
    def c_step(s, carry):
        blk, C = carry
        nxt = blk if stream_axis is None else jax.lax.ppermute(blk, stream_axis, fwd)
        yoff = slab_row_offset(s)
        dxy = jax.lax.dynamic_slice(Grow, (0, yoff), (mr, slab_rows))
        w = jax.lax.dynamic_slice(Wrow, (0, yoff), (mr, slab_rows))
        xw = (_xwins_rows(roff, mr, yoff, slab_rows)
              if plan.weight.needs_index_tiebreak else None)
        C = C + plan.cohesion_general(Dblk, blk, dxy, w, xwins=xw)
        return nxt, C

    _, C = jax.lax.fori_loop(0, nsteps, c_step, (slab, jnp.zeros((mr, mc), jnp.float32)))
    return C


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def pald_distributed(
    D: jnp.ndarray,
    mesh: Mesh,
    *,
    strategy: str = "auto",
    row_axes: Sequence[str] | None = None,
    col_axis: str | None = None,
    pod_stream: bool | None = None,
    normalize: bool = True,
    impl: str | None = None,
    comm_dtype=None,
    block: int | str = "auto",
    block_z: int | str = "auto",
    ties: str | None = None,
    weight=None,
    on_error: str = "raise",
) -> jnp.ndarray:
    """Compute the PaLD cohesion matrix on a device mesh.

    Args:
        D: host/global (n, n) distance matrix; padded internally to shard
            evenly, placed according to the strategy, processed, returned
            unsharded.
        mesh: the ``jax.sharding.Mesh`` to run on.
        strategy: "allgather", "ring", "2d", or "auto" (module docstring
            has the communication/memory tradeoffs); "2d" requires a 2-D
            mesh, optionally with ``pod_stream=True`` on the slow axis.
        row_axes / col_axis: which mesh axes shard rows/columns; default
            all-but-last / last.
        pod_stream: stream the inter-pod row slab ("2d" only).
        normalize: apply the 1/(n-1) factor, like ``pald.cohesion``.
        impl: per-device kernel backend (None = backend default).
        comm_dtype: ``jnp.bfloat16`` moves/gathers distances in bf16
            (halving every collective) and compares in bf16 — PaLD
            depends only on the ORDER of distances, so this is exact
            whenever no two distances fall in the same bf16 ulp.
            Distances that collide round to an exact TIE, so the explicit
            ``ties`` mode governs them: the bf16 result equals
            single-device PaLD on the bf16-cast matrix under the same
            ``ties`` (tests/test_ties.py), instead of silently depending
            on which kernel the shard body dispatches to.  §Perf 3.
        block / block_z: per-device kernel tiles; ``"auto"`` (default)
            resolves them from the persistent tuning cache
            (``repro.tuning``), keyed by the per-device problem size.
        ties: tie-handling mode on every shard body (see
            ``pald.cohesion``); sugar for ``weight=``.
        weight: registered weight-functional name or ``WeightFunctional``
            instance (``core/weights.py``) — resolved once at dispatch
            time and threaded into every shard body, so any registered
            functional runs distributed with no per-strategy forks.
        on_error: "raise" (default) or "fallback" — with "fallback", a
            shard body whose per-device kernel fails at trace/lowering
            time degrades across the remaining impls
            (``core/resilience.guarded_general``) instead of crashing the
            whole sharded run.

    Returns:
        (n, n) float32 cohesion matrix, equal to single-device
        ``pald.cohesion(D, ties=ties)`` for any strategy.

    Raises:
        ValueError: unknown strategy/ties, or a strategy/mesh-shape
            mismatch.

    Example:
        >>> import numpy as np, jax, jax.numpy as jnp
        >>> from jax.sharding import Mesh
        >>> rng = np.random.default_rng(0); X = rng.normal(size=(16, 3))
        >>> D = np.sqrt(((X[:, None] - X[None]) ** 2).sum(-1))
        >>> mesh = Mesh(np.asarray(jax.devices()[:1]), ("dev",))
        >>> C = pald_distributed(jnp.asarray(D), mesh, strategy="ring")
        >>> C.shape
        (16, 16)
    """
    axis_names = list(mesh.axis_names)
    if row_axes is None:
        row_axes = tuple(a for a in axis_names if a != axis_names[-1])
    else:
        row_axes = tuple(row_axes)
    col_axis = col_axis or axis_names[-1]
    if strategy == "auto":
        strategy = "2d" if len(axis_names) >= 2 else "ring"
    if pod_stream is None:
        pod_stream = "pod" in axis_names and strategy == "2d"

    n0 = D.shape[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pr = 1
    for a in row_axes:
        pr *= sizes[a]
    pc = sizes[col_axis]

    if strategy in ("allgather", "ring"):
        p = pr * pc
        flat_axes = tuple(axis_names)
        quantum = p
        spec_in = P(flat_axes, None)
    else:
        quantum = pr * pc  # rows need pr | n, cols pc | n; lcm-ish via pr*pc
        spec_in = P(tuple(row_axes), col_axis)

    m = -(-n0 // quantum) * quantum
    dt = comm_dtype or jnp.float32
    Dp = jnp.full((m, m), jnp.inf, dt)
    Dp = Dp.at[:n0, :n0].set(jnp.asarray(D, dt))
    Dp = Dp.at[jnp.arange(m), jnp.arange(m)].set(0.0)
    n_valid = n0 if m != n0 else None

    # resolve every per-device knob (tiles via the tuning cache, impl, ties)
    # exactly once at dispatch (trace) time, keyed on the per-device row
    # extent; the shard bodies consume the frozen plan instead of re-threading
    # four loose kwargs.  `repro.kernels.ops` still clamps the tiles to each
    # call's actual rectangle.
    m_dev = m // (p if strategy in ("allgather", "ring") else pr)
    local_plan = _engine.plan_local(m_dev, impl=impl, ties=ties,
                                    weight=weight, block=block,
                                    block_z=block_z, on_error=on_error)

    mesh_shape = sizes
    if strategy == "allgather":
        body = functools.partial(
            _allgather_body, axis=flat_axes, n_valid=n_valid, plan=local_plan
        )
        out_spec = P(flat_axes, None)
    elif strategy == "ring":
        body = functools.partial(
            _ring_body, axis=flat_axes, p=p, n_valid=n_valid, plan=local_plan
        )
        out_spec = P(flat_axes, None)
    elif strategy == "2d":
        body = functools.partial(
            _2d_body,
            row_axes=row_axes,
            col_axis=col_axis,
            stream_axis="pod" if pod_stream else None,
            n_valid=n_valid,
            mesh_shape=mesh_shape,
            plan=local_plan,
        )
        out_spec = P(tuple(row_axes), col_axis)
    else:
        raise ValueError(f"unknown strategy {strategy!r}")

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=spec_in, out_specs=out_spec,
                      check_vma=False)
    )
    C = fn(Dp)[:n0, :n0]
    if normalize:
        C = C / max(n0 - 1, 1)
    return C


def pald_distributed_from_features(
    X: jnp.ndarray,
    mesh: Mesh,
    *,
    metric: str = "euclidean",
    strategy: str = "auto",
    normalize: bool = True,
    impl: str | None = None,
    block: int | str = "auto",
    block_z: int | str = "auto",
    ties: str | None = None,
    weight=None,
    on_error: str = "raise",
) -> jnp.ndarray:
    """Distributed PaLD straight from row-sharded feature vectors.

    X is zero-padded to shard evenly over the flattened mesh, row-sharded,
    and each device computes its distance rows locally — the only
    O(n)-scaled communication is feature movement (n*d words), an n/d-fold
    reduction over the distance-sharded strategies.

    Args:
        X: host/global (n, d) feature matrix.
        mesh: the ``jax.sharding.Mesh`` to run on (flattened over all
            axes).
        metric: one of ``features.METRICS``.
        strategy: "allgather" — one all-gather of X; each device holds
            (n, d) features and the (n, n) distances it derives (memory
            n^2/device, but comm drops from n^2 to n*d); or "ring"
            (the "auto" default) — X blocks rotate via ppermute and
            distance row slabs are recomputed per step from the (m, d)
            block in flight (memory O(n^2/P), comm 2 n*d words total).
            The full distance matrix is never communicated; ``allgather``
            is the only strategy that materializes it (per device, by
            construction).
        normalize / impl / block / block_z / ties / weight / on_error: as
            in ``pald_distributed``; ``ties``/``weight`` behave exactly
            as in ``pald.from_features``.

    Returns:
        (n, n) float32 cohesion matrix, equal to single-device
        ``pald.from_features(X, metric=metric, ties=ties)``.

    Raises:
        ValueError: unknown strategy, metric or ties mode.

    Example:
        >>> import numpy as np, jax, jax.numpy as jnp
        >>> from jax.sharding import Mesh
        >>> X = np.random.default_rng(0).normal(size=(16, 3))
        >>> mesh = Mesh(np.asarray(jax.devices()[:1]), ("dev",))
        >>> C = pald_distributed_from_features(jnp.asarray(X), mesh)
        >>> C.shape
        (16, 16)
    """
    if strategy == "auto":
        strategy = "ring"
    if strategy not in ("allgather", "ring"):
        raise ValueError(
            f"unknown feature strategy {strategy!r} "
            "(expected 'allgather' or 'ring')"
        )
    axis_names = tuple(mesh.axis_names)
    p = mesh.size
    X = jnp.asarray(X, jnp.float32)  # explicit boundary cast
    n0, d = X.shape
    m = -(-n0 // p) * p
    Xp = jnp.pad(X, ((0, m - n0), (0, 0)))
    n_valid = n0 if m != n0 else None

    local_plan = _engine.plan_local(m // p, impl=impl, ties=ties,
                                    weight=weight, block=block,
                                    block_z=block_z, on_error=on_error)

    if strategy == "allgather":
        body = functools.partial(
            _feat_allgather_body, axis=axis_names, metric=metric,
            n_valid=n_valid, plan=local_plan,
        )
    else:
        body = functools.partial(
            _feat_ring_body, axis=axis_names, p=p, metric=metric,
            n_valid=n_valid, plan=local_plan,
        )
    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(axis_names, None),
                      out_specs=P(axis_names, None), check_vma=False)
    )
    C = fn(Xp)[:n0, :n0]
    if normalize:
        C = C / max(n0 - 1, 1)
    return C
