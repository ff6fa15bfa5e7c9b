"""Mesh-sharded k-NN PaLD: the fused select→cohere pipeline under shard_map.

``core/distributed.py`` shards the DENSE two-pass algorithm; this module
shards the sparse O(n·k²) restriction (PR 5) fused with the streaming
top-k selection (PR 9) so both stages run per shard and only the (n, k+1)
sparse result is ever global.  X is row-sharded over the (flattened) mesh;
each device selects the exact k nearest neighbors for its own rows, gathers
the (m, k, d) neighbor features it needs, and runs the ``pald_knn`` tile
body (``core.knn.knn_values_tile``) locally.  The full distance matrix is
never materialized anywhere — per-device live state is one (chunk, n/pc)
distance slab at a time.

Strategies (comm figures are f32 words received per device; see
``comm_estimate`` for the model the engine/dryrun report):

allgather   one ``all_gather`` of X — (p-1)/p · n·d words — then each shard
            runs the exact single-device row-slab pipeline on its own rows.
            Simplest; per-device memory O(n·d + chunk·n).
ring        no global X copy: (m, d) feature blocks rotate via ``ppermute``
            twice (selection, then neighbor gather), 2·(p-1)/p · n·d words.
            Running (m, k) best lists are merged EXACTLY each step by a
            lexicographic ``lax.sort`` on (distance, index) pairs — the
            same total order ``_top_k_rows`` selects by, so visit order
            cannot change the result.  Peak memory O(n·d/p + chunk·n/p).
2d          (pr, pc) mesh: each device scores its row-group's rows against
            the 1/pc column slice it owns — compute n²·d/(pr·pc) per
            device — takes a partial top-k, and one k-wide ``all_gather``
            + exact merge along the column axis finishes selection;
            comm n·d + 2·(pc-1)/pc · (n/pr)·k words.

Bitwise contract: every strategy reproduces the single-device fused path
(``kernels.ops.select_cohere``) row for row — selection merges on the
composite (value, index) key that defines ``_top_k_rows``'s order, the
neighbor-to-neighbor gather recomputes ``gather_tile_from_features``'s
exact shapes, and the values stage is the shared ``knn_values_tile`` whose
reductions run over the k axes only (per-row independent).  The one caveat
is the selection contract's (``kernels/pald_topk.py``): selection is exact
on the distances it is given, and each shape's d-contraction GEMM sums in
the backend's own order, so float features can differ by an ulp between
slab shapes; integer-valued features are exact in f32 regardless, which is
what the conformance matrix pins (tests/test_distributed_knn.py).

Padded rows (n not divisible by the shard quantum) enter selection as
masked (+inf, INT32_MAX) sentinel candidates — they lose every composite-
key comparison, so real rows never see them; the junk values computed FOR
padded rows are sliced off before returning.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.tuning import autotune as _tuner

from . import knn as _knn
from .features import METRICS, dist_tile
from .resilience import fault_point, warn_once
from .weights import DEFAULT_TIES, resolve_weight

__all__ = ["STRATEGIES", "pald_knn_sharded", "comm_estimate",
           "resolve_shard_shapes"]

STRATEGIES = ("auto", "allgather", "ring", "2d")

_IMAX = 2 ** 31 - 1  # the (value, index) sentinel: loses every comparison


def _merge_pairs(v, i, k: int):
    """Exact top-k of composite (value, index) pairs along the last axis.

    ``lax.sort`` with two keys orders lexicographically ascending — the
    SAME total order ``core.knn._top_k_rows`` (stable ``lax.top_k`` on
    negated distances) selects by.  Real candidates all carry distinct
    indices, so the order is total and merging partial lists in ANY
    grouping reproduces the single-device selection bitwise."""
    sv, si = jax.lax.sort((v, i), dimension=v.ndim - 1, num_keys=2,
                          is_stable=True)
    return sv[..., :k], si[..., :k]


# ---------------------------------------------------------------------------
# shard bodies (each returns the (mloc, k) / (mloc, k+1) row-sharded triple)
# ---------------------------------------------------------------------------
def _knn_allgather_body(Xloc, *, axis, k, metric, n, chunk, tile, wfun):
    """One all_gather of X, then the exact single-device row-slab loop
    (``ops._topk_chunk`` → gather → ``knn_values_tile``) over own rows."""
    from repro.kernels import ops as _ops

    m = Xloc.shape[0]
    Xall = jax.lax.all_gather(Xloc, axis, tiled=True)       # (mtot, d)
    off0 = jax.lax.axis_index(axis) * m

    def body(j):
        off = off0 + j * chunk
        dv, di = _ops._topk_chunk(Xall, off, k=k, metric=metric,
                                  chunk=chunk, n=n, tile=tile)
        g = _knn.gather_tile_from_features(Xall[:n], di, metric)
        ow = None
        if wfun.needs_index_tiebreak:
            ow = (off + jnp.arange(chunk))[:, None] > di
        return dv, di, _knn.knn_values_tile(dv, g, ow, wfun)

    dv, di, vals = jax.lax.map(body, jnp.arange(m // chunk))
    return (dv.reshape(m, k), di.reshape(m, k), vals.reshape(m, k + 1))


def _knn_ring_body(Xloc, *, axis, p, k, metric, n, chunk, wfun):
    """Streaming selection: (m, d) feature blocks rotate via ppermute; the
    running (m, k) best list merges each step's candidates exactly on the
    (value, index) key.  A second rotation replays the blocks to gather the
    selected neighbors' features, then cohesion runs fully locally."""
    m, d = Xloc.shape
    r = jax.lax.axis_index(axis)
    fwd = [(j, (j + 1) % p) for j in range(p)]
    gids = r * m + jnp.arange(m)
    nc = m // chunk

    def sel_step(s, carry):
        blk, bv, bi = carry
        off = ((r - s) % p) * m              # global offset of blk's rows
        cols = (off + jnp.arange(m)).astype(jnp.int32)

        def row_chunk(j, st):
            bv, bi = st
            rows = jax.lax.dynamic_slice(Xloc, (j * chunk, 0), (chunk, d))
            rid = jax.lax.dynamic_slice(gids, (j * chunk,), (chunk,))
            dt = dist_tile(rows, blk, metric)               # (chunk, m)
            bad = (rid[:, None] == cols[None, :]) | (cols >= n)[None, :]
            # pre-reduce the block with the SAME stable top_k primitive
            # the single-device kernel uses: within one block, column
            # order == ascending global id, so (value, column) order is
            # (value, id) order and the kb survivors are exactly the
            # entries a full-width merge would keep (masked entries all
            # carry the identical (+inf, _IMAX) composite key).  The
            # running merge then sorts k + kb pairs instead of k + m.
            kb = min(k, m)
            cv, loc = _knn._top_k_rows(
                jnp.where(bad, -jnp.inf, -dt), kb)          # (chunk, kb)
            ci = jnp.where(jnp.isinf(cv), jnp.int32(_IMAX),
                           (off + loc).astype(jnp.int32))
            obv = jax.lax.dynamic_slice(bv, (j * chunk, 0), (chunk, k))
            obi = jax.lax.dynamic_slice(bi, (j * chunk, 0), (chunk, k))
            mv, mi = _merge_pairs(jnp.concatenate([obv, cv], axis=1),
                                  jnp.concatenate([obi, ci], axis=1), k)
            return (jax.lax.dynamic_update_slice(bv, mv, (j * chunk, 0)),
                    jax.lax.dynamic_update_slice(bi, mi, (j * chunk, 0)))

        bv, bi = jax.lax.fori_loop(0, nc, row_chunk, (bv, bi))
        return jax.lax.ppermute(blk, axis, fwd), bv, bi

    bv = jnp.full((m, k), jnp.inf, jnp.float32)
    bi = jnp.full((m, k), jnp.int32(_IMAX))
    _, bv, bi = jax.lax.fori_loop(
        0, p, lambda s, c: sel_step(s, c), (Xloc, bv, bi))

    # rotation 2: replay the blocks to collect the selected neighbors'
    # feature rows (each global index lives in exactly one block)
    def gat_step(s, carry):
        blk, Xn = carry
        off = ((r - s) % p) * m
        safe = jnp.where(bi < n, bi, 0)
        loc = safe - off
        inr = (loc >= 0) & (loc < m) & (bi < n)
        sel = blk[jnp.clip(loc, 0, m - 1)]                  # (m, k, d)
        Xn = jnp.where(inr[:, :, None], sel, Xn)
        return jax.lax.ppermute(blk, axis, fwd), Xn

    _, Xn = jax.lax.fori_loop(
        0, p, lambda s, c: gat_step(s, c),
        (Xloc, jnp.zeros((m, k, d), jnp.float32)))

    # cohesion: same (chunk, k) tiles as the single-device fused loop;
    # the gathered Xn rows equal X[bi] exactly, so the per-row g cube
    # matches gather_tile_from_features (same shapes, same zero diagonal)
    def coh(j):
        bvj = jax.lax.dynamic_slice(bv, (j * chunk, 0), (chunk, k))
        bij = jax.lax.dynamic_slice(bi, (j * chunk, 0), (chunk, k))
        Xnj = jax.lax.dynamic_slice(Xn, (j * chunk, 0, 0), (chunk, k, d))
        G = jax.vmap(lambda A: dist_tile(A, A, metric))(Xnj)
        g = jnp.where(bij[:, :, None] == bij[:, None, :], 0.0, G)
        ow = None
        if wfun.needs_index_tiebreak:
            rid = jax.lax.dynamic_slice(gids, (j * chunk,), (chunk,))
            ow = rid[:, None] > bij
        return _knn.knn_values_tile(bvj, g, ow, wfun)

    vals = jax.lax.map(coh, jnp.arange(nc)).reshape(m, k + 1)
    return bv, bi, vals


def _knn_2d_body(Xloc, *, row_axes, col_axis, k, metric, n, chunk, wfun,
                 pr, pc):
    """2-D decomposition: the (pr, pc) mesh splits the n² selection compute
    both ways.  Each device scores its row-group's (n/pr) rows against the
    strided 1/pc candidate slice it owns, takes a partial top-k, and the
    column axis all_gathers + exactly merges the k-wide partials."""
    mloc, d = Xloc.shape
    allax = (*row_axes, col_axis)
    flat = jax.lax.axis_index(allax)        # row-major flattened device id
    ci = jax.lax.axis_index(col_axis)
    gids = flat * mloc + jnp.arange(mloc)

    # one all_gather of X (needed for the neighbor gather regardless);
    # flattened axis order == global row order by the in_spec construction
    Xall = jax.lax.all_gather(Xloc, allax, tiled=True)      # (mtot, d)
    rowids = jax.lax.all_gather(gids, col_axis, tiled=True)  # contiguous
    candids = jax.lax.all_gather(gids, row_axes, tiled=True)  # strided
    Xrow = jax.lax.all_gather(Xloc, col_axis, tiled=True)    # (mr, d)
    Xcand = jax.lax.all_gather(Xloc, row_axes, tiled=True)   # (mc, d)
    mr, mc = Xrow.shape[0], Xcand.shape[0]
    kt = min(k, mc)         # each block's top-kt covers the global top-k
    cids = candids.astype(jnp.int32)

    def rchunk(j):
        rows = jax.lax.dynamic_slice(Xrow, (j * chunk, 0), (chunk, d))
        rid = jax.lax.dynamic_slice(rowids, (j * chunk,), (chunk,))
        dt = dist_tile(rows, Xcand, metric)                 # (chunk, mc)
        bad = (rid[:, None] == cids[None, :]) | (cids >= n)[None, :]
        # stable top_k pre-reduction (see the ring body): the gathered
        # candidate blocks arrive in ascending flat-device order, so
        # ``cids`` is strictly increasing and (value, column) order is
        # (value, id) order — the kt survivors match a full-width sort
        cv, loc = _knn._top_k_rows(jnp.where(bad, -jnp.inf, -dt), kt)
        civ = jnp.where(jnp.isinf(cv), jnp.int32(_IMAX), cids[loc])
        return cv, civ

    pv, pi = jax.lax.map(rchunk, jnp.arange(mr // chunk))
    pv, pi = pv.reshape(mr, kt), pi.reshape(mr, kt)
    # merge the pc partial lists (disjoint candidate sets) exactly
    av = jax.lax.all_gather(pv, col_axis, axis=1, tiled=True)  # (mr, pc*kt)
    ai = jax.lax.all_gather(pi, col_axis, axis=1, tiled=True)
    dv, di = _merge_pairs(av, ai, k)

    # this device's original rows sit at column-position ci in the slab
    dvo = jax.lax.dynamic_slice(dv, (ci * mloc, 0), (mloc, k))
    dio = jax.lax.dynamic_slice(di, (ci * mloc, 0), (mloc, k))

    def coh(j):
        dvj = jax.lax.dynamic_slice(dvo, (j * chunk, 0), (chunk, k))
        dij = jax.lax.dynamic_slice(dio, (j * chunk, 0), (chunk, k))
        g = _knn.gather_tile_from_features(Xall[:n], dij, metric)
        ow = None
        if wfun.needs_index_tiebreak:
            rid = jax.lax.dynamic_slice(gids, (j * chunk,), (chunk,))
            ow = rid[:, None] > dij
        return _knn.knn_values_tile(dvj, g, ow, wfun)

    vals = jax.lax.map(coh, jnp.arange(mloc // chunk)).reshape(mloc, k + 1)
    return dvo, dio, vals


# ---------------------------------------------------------------------------
# shapes + communication model (consumed by engine.explain and dryrun_pald)
# ---------------------------------------------------------------------------
def resolve_shard_shapes(n: int, *, p: int, chunk: int) -> tuple[int, int, int]:
    """(chunk, quantum, m_padded): the one place the padding math lives.

    ``chunk`` is clamped to the per-shard row count so the slab loop always
    has at least one full tile; the global quantum is ``p * chunk`` so
    every shard's row count is a chunk multiple."""
    chunk = max(1, min(int(chunk), -(-n // p)))
    quantum = p * chunk
    m = -(-n // quantum) * quantum
    return chunk, quantum, m


def comm_estimate(strategy: str, *, n: int, d: int, k: int, p: int,
                  pr: int | None = None, pc: int | None = None) -> dict:
    """Per-device communication model of the sharded knn pipeline.

    Words are f32 words RECEIVED per device (ppermute/all_gather payloads;
    int32 index words count as one word).  Every strategy moves O(n·d)
    feature words — never the O(n²) distance matrix — matching the
    module docstring's ``comm n·d`` claim and the source paper's
    communication-optimality analysis; the 2d strategy adds the
    O((n/pr)·k) selection-merge term.

    Returns a dict with ``per_device_words``, ``total_words`` (sum over
    devices), and the per-collective breakdown.
    """
    if strategy == "auto":
        strategy = "2d" if (pr or 0) > 0 and (pc or 0) > 1 else "ring"
    mloc = -(-n // p)
    if strategy == "allgather":
        parts = {"allgather_x": (p - 1) * mloc * d}
    elif strategy == "ring":
        parts = {"ring_select_x": (p - 1) * mloc * d,
                 "ring_gather_x": (p - 1) * mloc * d}
    elif strategy == "2d":
        pr = pr or 1
        pc = pc or p
        mr = -(-n // pr)
        kt = min(k, pr * mloc)
        parts = {"allgather_x": (p - 1) * mloc * d,
                 "allgather_ids": (p - 1) * mloc + (pc - 1) * mloc
                 + (pr - 1) * mloc,
                 "rowcand_slabs": (pc - 1) * mloc * d + (pr - 1) * mloc * d,
                 "merge_partials": 2 * (pc - 1) * mr * kt}
    else:
        raise ValueError(f"unknown strategy {strategy!r} "
                         f"(expected one of {STRATEGIES[1:]})")
    per_dev = int(sum(parts.values()))
    return {"strategy": strategy, "p": p,
            "per_device_words": per_dev,
            "per_device_bytes": 4 * per_dev,
            "total_words": per_dev * p,
            "breakdown": {kk: int(v) for kk, v in parts.items()}}


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------
def pald_knn_sharded(
    X: jnp.ndarray,
    mesh: Mesh,
    *,
    k: int,
    metric: str = "euclidean",
    strategy: str = "auto",
    normalize: bool = True,
    ties=None,
    weight=None,
    block: int | str = "auto",
    tile: int | str = "auto",
    on_error: str = "raise",
) -> tuple["_knn.NeighborGraph", jnp.ndarray]:
    """Mesh-sharded fused select→cohere k-NN PaLD from features.

    Args:
        X: host/global (n, d) feature matrix (cast to float32 once).
        mesh: the ``jax.sharding.Mesh`` to run on.  1-D strategies flatten
            every axis; "2d" uses all-but-last as row axes and the last as
            the column (selection-split) axis.
        k: neighborhood size (clamped to n-1, like ``select_cohere``).
        metric: one of ``features.METRICS``.
        strategy: "allgather" / "ring" / "2d", or "auto" — "2d" on a
            multi-axis mesh, "ring" otherwise (mirrors
            ``pald_distributed``'s convention).  See the module docstring
            for the comm/memory trade.
        normalize: divide values by (n-1) (the public-API default).
        ties / weight: the weight-functional knob, exactly as in
            ``pald.from_features`` (``ties`` sugar over ``weight``).
        block: rows per selection slab per shard; "auto" resolves via the
            mesh-keyed ``pald_topk:k<k>:d<d>:p<p>`` tuning pass (falling
            back to the single-device cell on a miss).
        tile: tile-min prefilter width (allgather strategy only — ring/2d
            stream column blocks instead of prefiltering); "auto" = tuned.
        on_error: "raise" propagates any sharded failure; "fallback"
            degrades to the single-device fused pipeline
            (``kernels.ops.select_cohere``) with identical semantics,
            warning once (``resilience.DegradationWarning``).

    Returns:
        (graph, values): the exact ``NeighborGraph`` (n, k) and the
        (n, k+1) sparse cohesion values (column 0 = self) — bitwise equal
        to single-device ``select_cohere(X, k=..., ...)`` per the module
        contract.

    Raises:
        ValueError: unknown strategy/metric, or "2d" on a 1-axis mesh.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r} "
                         f"(expected one of {STRATEGIES})")
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r} (one of {METRICS})")
    axes = tuple(mesh.axis_names)
    if strategy == "auto":
        strategy = "2d" if len(axes) >= 2 else "ring"
    if strategy == "2d" and len(axes) < 2:
        raise ValueError("strategy '2d' needs a mesh with >= 2 axes "
                         f"(got axes {axes}); use 'allgather' or 'ring'")
    wfun = resolve_weight(weight if weight is not None
                          else (ties if ties is not None else DEFAULT_TIES))

    X = jnp.asarray(X, jnp.float32)
    n0, d = X.shape
    k = min(int(k), max(n0 - 1, 0))
    if k <= 0:
        return (_knn.NeighborGraph(jnp.zeros((n0, 0), jnp.int32),
                                   jnp.zeros((n0, 0), jnp.float32)),
                jnp.zeros((n0, 1), jnp.float32))

    p = mesh.devices.size
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pr = math.prod(sizes[a] for a in axes[:-1]) if len(axes) >= 2 else 1
    pc = sizes[axes[-1]]

    if block == "auto" or tile == "auto":
        rb, rt = _tuner.resolve_blocks(n0, "pald_topk", impl="jnp", d=d,
                                       k=k, p=p)
        block = rb if block == "auto" else block
        tile = rt if tile == "auto" else tile
    chunk, _, m = resolve_shard_shapes(n0, p=p, chunk=int(block))

    fault_point("distributed_knn.dispatch", strategy=strategy, p=p, k=k,
                metric=metric)

    def run_sharded():
        Xp = jnp.pad(X, ((0, m - n0), (0, 0)))
        if strategy == "allgather":
            body = functools.partial(
                _knn_allgather_body, axis=axes, k=k, metric=metric, n=n0,
                chunk=chunk, tile=int(tile), wfun=wfun)
        elif strategy == "ring":
            body = functools.partial(
                _knn_ring_body, axis=axes, p=p, k=k, metric=metric, n=n0,
                chunk=chunk, wfun=wfun)
        else:
            body = functools.partial(
                _knn_2d_body, row_axes=axes[:-1], col_axis=axes[-1], k=k,
                metric=metric, n=n0, chunk=chunk, wfun=wfun, pr=pr, pc=pc)
        fault_point("distributed_knn.body", strategy=strategy, p=p,
                    mesh=tuple(mesh.devices.shape))
        spec = P(axes, None)
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=spec,
            out_specs=(spec, spec, spec), check_vma=False))
        Xs = jax.device_put(Xp, NamedSharding(mesh, spec))
        dv, di, vals = fn(Xs)
        return dv[:n0], di[:n0], vals[:n0]

    if on_error == "fallback":
        try:
            dv, di, vals = run_sharded()
        except Exception as exc:  # noqa: BLE001 — the guard's whole job
            from repro.kernels import ops as _ops

            warn_once(("distributed-knn", strategy, tuple(mesh.devices.shape)),
                      f"sharded knn pipeline (strategy={strategy!r}, mesh="
                      f"{tuple(mesh.devices.shape)}) failed "
                      f"({type(exc).__name__}: {exc}); degraded to the "
                      "single-device fused path with identical semantics")
            graph, vals = _ops.select_cohere(
                X, k=k, metric=metric, block=chunk, tile=int(tile)
                if strategy == "allgather" else "auto", impl="jnp",
                ties=wfun, normalize=normalize)
            return graph, vals
    else:
        dv, di, vals = run_sharded()
    if normalize:
        vals = vals / max(n0 - 1, 1)
    return _knn.NeighborGraph(di, dv), vals
