"""jit'd wrappers around the PaLD Pallas kernels.

On TPU the kernels lower to Mosaic; on CPU (this container) either
``interpret=True`` Pallas execution (bit-faithful to the kernel body, used by
tests) or a vectorized jnp fallback with identical semantics (used for speed
in distributed CPU runs) is selected via ``impl=``.

The *general* (rectangular) forms are the primitives that both the sequential
square algorithm and the shard_map distributed algorithms call per device:

    focus_general(DXZ, DYZ, DXY)        -> U (mx, my)
    cohesion_general(DXZ, DYZ, DXY, W)  -> C (mx, mz)

The square sequential forms additionally support ``schedule="tri"`` — the
upper-triangular block schedules (pald_focus_tri / pald_cohesion_tri,
DESIGN.md §4.3) that halve the block-pair visits of both passes.

Block sizes accept ``"auto"``: resolved through the persistent autotuner
cache (``repro.tuning``), falling back to size-aware defaults on a miss.
Dims that don't divide by the chosen tile are padded up to the next tile
multiple (+inf distances / zero weights, exact by construction) instead of
silently degrading to tiny divisor blocks.

Every entry point takes ``ties`` — a mode string, a registered weight
functional name, or a ``WeightFunctional`` instance (``core/weights.py``);
all impls of one functional agree entry-wise, on tied input included.  The
rectangular ``cohesion_general`` form needs the caller to supply the
global-index tiebreak of ``needs_index_tiebreak`` functionals either as an
explicit ``xwins`` array (distributed callers own traced offsets) or as
static ``xw_offsets`` it derives per tile; the square and fused forms
derive it themselves.

Every stage of the eager pipelines runs inside a profiler span
(``jax.profiler.TraceAnnotation``) named ``<layer>.<stage>``:
``pipeline.pad``, ``pipeline.weights``, ``pipeline.gather_cube``,
``pipeline.scatter_dense``, ``pipeline.finish``, and ``kernel.<kernel>``
around each Pallas kernel call.  A span records nothing unless a profiler
trace is running; in a trace, every device program a stage launches can be
put down to it.  Under a caller's ``jax.jit`` (or ``shard_map``) these
functions run once, while tracing, so the spans mark tracing only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.resilience import fault_point
from repro.core.weights import (DEFAULT_TIES, focus_weight, index_xwins,
                                resolve_weight, support_weight)
from repro.tuning import autotune as _tuner

from .pald_cohesion import cohesion_general_pallas, cohesion_pallas  # noqa: F401
from .pald_cohesion_tri import cohesion_tri_pallas  # noqa: F401
from .pald_focus import focus_general_pallas, focus_pallas  # noqa: F401
from .pald_focus_tri import focus_tri_pallas  # noqa: F401
from .pald_fused import cohesion_fused_pallas, focus_fused_pallas  # noqa: F401
from .ref import weights_ref

__all__ = [
    "pald",
    "pald_tri",
    "pald_fused",
    "pald_knn",
    "knn_values",
    "knn_cube_layout",
    "topk_select",
    "select_cohere",
    "focus",
    "cohesion_from_weights",
    "focus_general",
    "cohesion_general",
    "on_tpu",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _default_impl() -> str:
    return "pallas" if on_tpu() else "jnp"


def _pick_block(m: int, want: int) -> int:
    """Largest divisor of m that is <= want (block shapes must tile exactly)."""
    b = min(want, m)
    while m % b:
        b -= 1
    return b


def _block_and_pad(m: int, want: int) -> tuple[int, int]:
    """Tile size and padded extent for one dim.

    Shrinking to a divisor of m is fine when the divisor stays reasonable,
    but for prime-ish m it collapses to block=1 — a catastrophic grid (m^2
    steps where there should be (m/want)^2).  In that case pad m up to the
    next multiple of ``want`` and keep the requested tile.
    """
    want = max(min(want, m), 1)
    b = _pick_block(m, want)
    if b == m or b >= max(want // 2, 8):
        return b, m
    return want, -(-m // want) * want


def _pad2(a: jnp.ndarray, mr: int, mc: int, value: float) -> jnp.ndarray:
    r, c = a.shape
    if (r, c) == (mr, mc):
        return a
    return jnp.pad(a, ((0, mr - r), (0, mc - c)), constant_values=value)


def _resolve_blocks(n: int, pass_: str, block, block_z, impl: str,
                    ties=DEFAULT_TIES) -> tuple[int, int]:
    """Turn "auto" block requests into concrete tiles via the tuning cache.

    The weight functional joins the cache key for non-default choices —
    the tile bodies differ (extra equality masks / tiebreak input /
    transcendentals), so their optima may too (``:t-``/``:w-`` key parts).
    """
    if block == "auto" or block_z == "auto":
        rb, rbz = _tuner.resolve_blocks(n, pass_, impl=impl, ties=ties)
        block = rb if block == "auto" else block
        block_z = rbz if block_z == "auto" else block_z
    return int(block), int(block_z)


# --------------------------------------------------------------------------
# jnp fallback with identical semantics to the kernels (z/y-chunked).
# --------------------------------------------------------------------------
# The fallback materializes an (mx, my, chunk) comparison cube per step; at
# production block sizes (6400x6400 on the 2-D distributed schedule) a fixed
# 512-chunk is a 20 GiB buffer.  Cap the bool cube at 512 MiB instead (its
# f32-cast sibling in the cohesion einsum is then <= 2 GiB) — the chunk
# adapts down as blocks grow (PaLD §Perf iteration).
_CUBE_BUDGET = 512 << 20


def _adaptive_chunk(mx: int, my: int, mz: int, want: int) -> int:
    cap = max(_CUBE_BUDGET // max(mx * my, 1), 8)
    return _pick_block(mz, min(want, cap))


@functools.partial(jax.jit, static_argnames=("chunk", "ties"))
def _focus_general_jnp(DXZ, DYZ, DXY, *, chunk: int = 512,
                       ties: str = DEFAULT_TIES):
    mx, mz = DXZ.shape
    c = _adaptive_chunk(mx, DYZ.shape[0], mz, chunk)

    def body(acc, blks):
        dxz, dyz = blks  # (mx, c), (my, c)
        m = focus_weight(dxz[:, None, :], dyz[None, :, :], DXY[:, :, None], ties)
        return acc + jnp.sum(m, axis=-1, dtype=jnp.float32), None

    xs = (
        DXZ.reshape(mx, mz // c, c).transpose(1, 0, 2),
        DYZ.reshape(DYZ.shape[0], mz // c, c).transpose(1, 0, 2),
    )
    U, _ = jax.lax.scan(body, jnp.zeros(DXY.shape, jnp.float32), xs)
    return U


@functools.partial(jax.jit, static_argnames=("chunk", "ties", "xw_offsets"))
def _cohesion_general_jnp(DXZ, DYZ, DXY, W, XW=None, *, chunk: int = 128,
                          ties=DEFAULT_TIES, xw_offsets=None):
    wfun = resolve_weight(ties)
    my = DYZ.shape[0]
    mx, mz = DXZ.shape
    c = _adaptive_chunk(mx, mz, my, chunk)

    def chunked(A):  # (mx, my) -> per-scan-step (mx, c) slabs
        return A.reshape(A.shape[0], my // c, c).transpose(1, 0, 2)

    def body(acc, blks):
        dyz, dxy, w, xw, yoff = blks  # (c, mz), (mx, c), (mx, c), (mx, c)|-, ()
        own = None
        if wfun.needs_index_tiebreak:
            if xw_offsets is not None:
                # derive the (mx, c) tiebreak chunk from static global
                # offsets — the square case never materializes it whole
                own = index_xwins(xw_offsets[0], mx,
                                  xw_offsets[1] + yoff, c)[:, :, None]
            else:
                own = xw[:, :, None]
        g = support_weight(DXZ[:, None, :], dyz[None, :, :], dxy[:, :, None],
                           wfun, own)
        return acc + jnp.einsum("xyz,xy->xz", g, w), None

    if wfun.needs_index_tiebreak and xw_offsets is None:
        if XW is None:
            raise ValueError(f"weight {wfun.name!r} needs XW "
                             "(global-index tiebreak)")
        xw_chunks = chunked(XW)
    else:
        # dummy zero-size leaf keeps the scan structure mode-independent
        xw_chunks = jnp.zeros((my // c, mx, 0), jnp.bool_)
    xs = (DYZ.reshape(my // c, c, -1), chunked(DXY), chunked(W), xw_chunks,
          jnp.arange(my // c, dtype=jnp.int32) * c)
    C, _ = jax.lax.scan(body, jnp.zeros((DXZ.shape[0], DXZ.shape[1]), jnp.float32), xs)
    return C


# --------------------------------------------------------------------------
# jnp fallbacks for the upper-triangular block schedules (square case).
# Same tile bodies as the tri kernels: both role updates go through the
# shared tie predicate, with the block coordinates providing the
# ties='ignore' global-index tiebreak.
# --------------------------------------------------------------------------
def _tri_pairs(nb: int):
    import numpy as np
    xs, ys = np.triu_indices(nb)
    return jnp.asarray(xs, jnp.int32), jnp.asarray(ys, jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "ties"))
def _focus_tri_jnp(D, *, block: int = 128, ties=DEFAULT_TIES):
    n = D.shape[0]
    nb = n // block
    xs, ys = _tri_pairs(nb)

    def body(i, U):
        xb, yb = xs[i], ys[i]
        Dx = jax.lax.dynamic_slice(D, (xb * block, 0), (block, n))
        Dy = jax.lax.dynamic_slice(D, (yb * block, 0), (block, n))
        Dxy = jax.lax.dynamic_slice_in_dim(Dx, yb * block, block, axis=1)
        m = focus_weight(Dx[:, None, :], Dy[None, :, :], Dxy[:, :, None], ties)
        blk = jnp.sum(m, axis=-1, dtype=jnp.float32)
        U = jax.lax.dynamic_update_slice(U, blk, (xb * block, yb * block))
        return jax.lax.dynamic_update_slice(U, blk.T, (yb * block, xb * block))

    npairs = int(xs.shape[0])
    return jax.lax.fori_loop(0, npairs, body, jnp.zeros((n, n), jnp.float32))


@functools.partial(jax.jit, static_argnames=("block", "ties"))
def _cohesion_tri_jnp(D, W, *, block: int = 128, ties=DEFAULT_TIES):
    """Both role updates per upper-triangular block pair.

    The y-role is expressed in the same row-major orientation as the x-role
    (roles swapped through the symmetry of D and W), so both einsums reduce
    over the middle axis — the matmul-friendly layout XLA lowers best.  Both
    roles evaluate the shared tie predicate in the requested mode (the
    pre-PR3 complement trick hard-coded ties->y off-diagonal and strict
    comparisons on the diagonal, matching neither reference on tied input).
    Diagonal blocks skip the y-role computation entirely (lax.cond): the
    one-sided x-role already covers both orders of every in-block pair.
    """
    wfun = resolve_weight(ties)
    n = D.shape[0]
    nb = n // block
    xs, ys = _tri_pairs(nb)

    def body(i, C):
        xb, yb = xs[i], ys[i]
        Dx = jax.lax.dynamic_slice(D, (xb * block, 0), (block, n))
        Dy = jax.lax.dynamic_slice(D, (yb * block, 0), (block, n))
        Dxy = jax.lax.dynamic_slice_in_dim(Dx, yb * block, block, axis=1)
        Wxy = jax.lax.dynamic_slice(W, (xb * block, yb * block), (block, block))
        xw = yw = None
        if wfun.needs_index_tiebreak:
            xw = index_xwins(xb * block, block, yb * block, block)[:, :, None]
            yw = index_xwins(yb * block, block, xb * block, block)[:, :, None]
        gx = support_weight(Dx[:, None, :], Dy[None, :, :], Dxy[:, :, None],
                            ties, xw)
        add_x = jnp.einsum("xyz,xy->xz", gx, Wxy)

        def y_role(_):
            gy = support_weight(Dy[:, None, :], Dx[None, :, :],
                                Dxy.T[:, :, None], ties, yw)
            return jnp.einsum("yxz,yx->yz", gy, Wxy.T)

        add_y = jax.lax.cond(
            xb == yb, lambda _: jnp.zeros((block, n), jnp.float32), y_role, None
        )
        rx = jax.lax.dynamic_slice(C, (xb * block, 0), (block, n))
        C = jax.lax.dynamic_update_slice(C, rx + add_x, (xb * block, 0))
        ry = jax.lax.dynamic_slice(C, (yb * block, 0), (block, n))
        return jax.lax.dynamic_update_slice(C, ry + add_y, (yb * block, 0))

    npairs = int(xs.shape[0])
    return jax.lax.fori_loop(0, npairs, body, jnp.zeros((n, n), jnp.float32))


def _pad_square_tri(D, W, q: int):
    """Pad square inputs to a multiple of the tile quantum q (inf distances,
    zero weights: padded points never contribute to real entries)."""
    n = D.shape[0]
    m = -(-n // q) * q
    if m == n:
        return D, W, n
    Dp = _pad2(D.astype(jnp.float32), m, m, jnp.inf)
    Dp = Dp.at[jnp.arange(n, m), jnp.arange(n, m)].set(0.0)
    Wp = None if W is None else _pad2(W.astype(jnp.float32), m, m, 0.0)
    return Dp, Wp, n


# --------------------------------------------------------------------------
# jnp fallback for the fused features->cohesion pipeline.  Per (xb, yb) block
# pair, the (block, m) distance row slabs are recomputed from (block, d)
# feature slices — O(d/block) relative overhead — so the full (m, m) D matrix
# never exists as a value; only (block, m) slabs are live inside the loops.
# --------------------------------------------------------------------------
def _dist_slab(X, off, block, metric, n_valid):
    """Masked (block, m) distance rows starting at global row ``off``."""
    from repro.core.features import masked_dist_tile

    Xa = jax.lax.dynamic_slice(X, (off, 0), (block, X.shape[1]))
    return masked_dist_tile(Xa, X, metric, off, 0, n_valid)


def _fused_z_chunk(m: int, block: int, block_z: int) -> int:
    """z-chunk of the fused comparison cubes: the requested block_z, shrunk
    to the same 512 MiB cube budget the general jnp fallbacks honor, and to
    a divisor of m (slabs tile exactly)."""
    cap = max(_CUBE_BUDGET // max(block * block, 1), 8)
    return _pick_block(m, max(min(block_z, cap), 1))


@functools.partial(jax.jit,
                   static_argnames=("metric", "block", "block_z", "n_valid",
                                    "ties"))
def _focus_fused_jnp(X, *, metric: str, block: int, block_z: int, n_valid: int,
                     ties=DEFAULT_TIES):
    m = X.shape[0]
    nb = m // block
    cz = _fused_z_chunk(m, block, block_z)

    def outer(xb, U):
        Dx = _dist_slab(X, xb * block, block, metric, n_valid)

        def inner(yb, U):
            Dy = _dist_slab(X, yb * block, block, metric, n_valid)
            Dxy = jax.lax.dynamic_slice(Dx, (0, yb * block), (block, block))

            def zstep(zb, acc):
                dxc = jax.lax.dynamic_slice(Dx, (0, zb * cz), (block, cz))
                dyc = jax.lax.dynamic_slice(Dy, (0, zb * cz), (block, cz))
                msk = focus_weight(dxc[:, None, :], dyc[None, :, :],
                                   Dxy[:, :, None], ties)
                return acc + jnp.sum(msk, axis=-1, dtype=jnp.float32)

            blk = jax.lax.fori_loop(0, m // cz, zstep,
                                    jnp.zeros((block, block), jnp.float32))
            return jax.lax.dynamic_update_slice(U, blk, (xb * block, yb * block))

        return jax.lax.fori_loop(0, nb, inner, U)

    return jax.lax.fori_loop(0, nb, outer, jnp.zeros((m, m), jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("metric", "block", "block_z", "n_valid",
                                    "ties"))
def _cohesion_fused_jnp(X, W, *, metric: str, block: int, block_z: int,
                        n_valid: int, ties=DEFAULT_TIES):
    wfun = resolve_weight(ties)
    m = X.shape[0]
    nb = m // block
    cz = _fused_z_chunk(m, block, block_z)

    def outer(xb, C):
        Dx = _dist_slab(X, xb * block, block, metric, n_valid)

        def inner(yb, acc):
            Dy = _dist_slab(X, yb * block, block, metric, n_valid)
            Dxy = jax.lax.dynamic_slice(Dx, (0, yb * block), (block, block))
            Wxy = jax.lax.dynamic_slice(W, (xb * block, yb * block), (block, block))
            xw = None
            if wfun.needs_index_tiebreak:  # every ordered block pair visited
                xw = index_xwins(xb * block, block, yb * block, block)[:, :, None]

            def zstep(zb, acc):
                dxc = jax.lax.dynamic_slice(Dx, (0, zb * cz), (block, cz))
                dyc = jax.lax.dynamic_slice(Dy, (0, zb * cz), (block, cz))
                g = support_weight(dxc[:, None, :], dyc[None, :, :],
                                   Dxy[:, :, None], ties, xw)
                addc = jnp.einsum("xyz,xy->xz", g, Wxy)
                acc_c = jax.lax.dynamic_slice(acc, (0, zb * cz), (block, cz))
                return jax.lax.dynamic_update_slice(acc, acc_c + addc, (0, zb * cz))

            return jax.lax.fori_loop(0, m // cz, zstep, acc)

        add = jax.lax.fori_loop(0, nb, inner, jnp.zeros((block, m), jnp.float32))
        row = jax.lax.dynamic_slice(C, (xb * block, 0), (block, m))
        return jax.lax.dynamic_update_slice(C, row + add, (xb * block, 0))

    return jax.lax.fori_loop(0, nb, outer, jnp.zeros((m, m), jnp.float32))


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------
def focus_general(DXZ, DYZ, DXY, *, block=128, block_z=512,
                  impl: str | None = None, ties=DEFAULT_TIES):
    ties = resolve_weight(ties)
    impl = impl or _default_impl()
    fault_point("ops.focus_general", impl=impl, ties=ties.name)
    block, block_z = _resolve_blocks(max(DXZ.shape), "focus", block, block_z,
                                     impl, ties)
    if impl == "jnp":
        return _focus_general_jnp(DXZ, DYZ, DXY, chunk=block_z, ties=ties)
    (mx, mz), my = DXZ.shape, DYZ.shape[0]
    bx, mxp = _block_and_pad(mx, block)
    by, myp = _block_and_pad(my, block)
    bz, mzp = _block_and_pad(mz, block_z)
    with TraceAnnotation("pipeline.pad"):
        padded = (_pad2(DXZ, mxp, mzp, jnp.inf),
                  _pad2(DYZ, myp, mzp, jnp.inf),
                  _pad2(DXY, mxp, myp, jnp.inf))
    with TraceAnnotation("kernel.focus"):
        U = focus_general_pallas(
            *padded, block_x=bx, block_y=by, block_z=bz,
            interpret=impl == "interpret", ties=ties,
        )
    with TraceAnnotation("pipeline.finish"):
        return U[:mx, :my]


def cohesion_general(DXZ, DYZ, DXY, W, *, block=128, block_z=512,
                     impl: str | None = None, ties=DEFAULT_TIES,
                     xwins=None, xw_offsets=None):
    """For ``needs_index_tiebreak`` functionals (``ties='ignore'``) the
    rectangular form needs the global-index tiebreak — either ``xwins``
    (mx, my) bool, "global index of x > global index of y", for
    distributed callers whose row identities are data (traced offsets);
    or static ``xw_offsets`` = (row_off, col_off) global offsets, from
    which the tiebreak is derived per tile/chunk and never materialized
    whole (the square sequential case passes (0, 0))."""
    ties = resolve_weight(ties)
    impl = impl or _default_impl()
    fault_point("ops.cohesion_general", impl=impl, ties=ties.name)
    block, block_z = _resolve_blocks(max(DXZ.shape), "cohesion", block, block_z,
                                     impl, ties)
    if ties.needs_index_tiebreak and xwins is None and xw_offsets is None:
        raise ValueError(f"weight {ties.name!r} needs xwins or xw_offsets "
                         "(global-index tiebreak)")
    if impl == "jnp":
        XW = offs = None
        if ties.needs_index_tiebreak:
            XW, offs = xwins, (None if xwins is not None else tuple(xw_offsets))
        return _cohesion_general_jnp(DXZ, DYZ, DXY, W, XW, chunk=block,
                                     ties=ties, xw_offsets=offs)
    (mx, mz), my = DXZ.shape, DYZ.shape[0]
    bx, mxp = _block_and_pad(mx, block)
    by, myp = _block_and_pad(my, block)
    bz, mzp = _block_and_pad(mz, block_z)
    XW = offs = None
    with TraceAnnotation("pipeline.pad"):
        if ties.needs_index_tiebreak:
            if xwins is not None:
                # pad with 0 ("x does not win"): padded pairs carry zero weight
                XW = _pad2(xwins.astype(jnp.float32), mxp, myp, 0.0)
            else:
                # per-tile in-kernel derivation from the static global offsets
                offs = (int(xw_offsets[0]), int(xw_offsets[1]))
        padded = (_pad2(DXZ, mxp, mzp, jnp.inf),
                  _pad2(DYZ, myp, mzp, jnp.inf),
                  _pad2(DXY, mxp, myp, jnp.inf),
                  _pad2(W, mxp, myp, 0.0))
    with TraceAnnotation("kernel.cohesion"):
        C = cohesion_general_pallas(
            *padded, XW, block_x=bx, block_z=bz, block_y=by,
            interpret=impl == "interpret", ties=ties, xw_offsets=offs,
        )
    with TraceAnnotation("pipeline.finish"):
        return C[:mx, :mz]


def focus(D, *, block=128, block_z=512, impl: str | None = None,
          schedule: str = "dense", ties=DEFAULT_TIES):
    """schedule='tri' uses the upper-triangular scalar-prefetch kernel
    (pald_focus_tri): ~half the comparisons of the dense grid, same
    result.  Only meaningful for the square (sequential) case."""
    ties = resolve_weight(ties)
    if schedule == "tri":
        impl = impl or ("pallas" if on_tpu() else "jnp")
        n = D.shape[0]
        block, block_z = _resolve_blocks(n, "focus_tri", block, block_z, impl,
                                         ties)
        block, block_z = min(block, n), min(block_z, n)
        if impl == "jnp":
            with TraceAnnotation("pipeline.pad"):
                Dp, _, n0 = _pad_square_tri(D, None, block)
            return _focus_tri_jnp(Dp, block=block, ties=ties)[:n0, :n0]
        # pad to the largest tile, then shrink tiles to divisors of the
        # padded size (keeps the quantum bounded — never an lcm blow-up)
        with TraceAnnotation("pipeline.pad"):
            Dp, _, n0 = _pad_square_tri(D, None, max(block, block_z))
        m = Dp.shape[0]
        block, block_z = _pick_block(m, block), _pick_block(m, block_z)
        with TraceAnnotation("kernel.focus_tri"):
            U = focus_tri_pallas(
                Dp, block=block, block_z=block_z,
                interpret=impl == "interpret", ties=ties,
            )
        with TraceAnnotation("pipeline.finish"):
            return U[:n0, :n0]
    return focus_general(D, D, D, block=block, block_z=block_z, impl=impl,
                         ties=ties)


def cohesion_from_weights(D, W, *, block=128, block_z=512, impl: str | None = None,
                          schedule: str = "dense", ties=DEFAULT_TIES):
    """Pass 2 from precomputed reciprocal weights W = 1/U.

    schedule='tri' enumerates only the upper-triangular block pairs and
    applies both role updates per visit (pald_cohesion_tri).  The square
    case derives the index tiebreak per tile itself (``xw_offsets=(0, 0)``
    — the dense (n, n) tiebreak is never materialized)."""
    ties = resolve_weight(ties)
    if schedule == "tri":
        impl = impl or ("pallas" if on_tpu() else "jnp")
        n = D.shape[0]
        block, block_z = _resolve_blocks(n, "cohesion_tri", block, block_z,
                                         impl, ties)
        block, block_z = min(block, n), min(block_z, n)
        if impl == "jnp":
            with TraceAnnotation("pipeline.pad"):
                Dp, Wp, n0 = _pad_square_tri(D, W, block)
            return _cohesion_tri_jnp(Dp, Wp, block=block, ties=ties)[:n0, :n0]
        with TraceAnnotation("pipeline.pad"):
            Dp, Wp, n0 = _pad_square_tri(D, W, max(block, block_z))
        m = Dp.shape[0]
        block, block_z = _pick_block(m, block), _pick_block(m, block_z)
        with TraceAnnotation("kernel.cohesion_tri"):
            C = cohesion_tri_pallas(
                Dp, Wp, block=block, block_z=block_z,
                interpret=impl == "interpret", ties=ties,
            )
        with TraceAnnotation("pipeline.finish"):
            return C[:n0, :n0]
    offs = (0, 0) if ties.needs_index_tiebreak else None
    return cohesion_general(D, D, D, W, block=block, block_z=block_z, impl=impl,
                            ties=ties, xw_offsets=offs)


def pald(
    D,
    *,
    block=128,
    block_z=512,
    normalize: bool = False,
    n_valid=None,
    impl: str | None = None,
    schedule: str = "dense",
    ties=DEFAULT_TIES,
):
    """Full PaLD via the kernel pipeline (inputs padded internally as needed).

    impl: 'pallas' (TPU), 'interpret' (CPU bit-faithful kernel execution),
    'jnp' (vectorized fallback), or None for backend default.
    schedule: 'dense' runs the full rectangular grids; 'tri' dispatches to
    the fused upper-triangular pipeline (``pald_tri``).
    ties: weight functional (name or instance) shared by both passes
    (core/weights.py).
    """
    if schedule == "tri":
        return pald_tri(D, block=block, block_z=block_z, normalize=normalize,
                        n_valid=n_valid, impl=impl, ties=ties)
    impl = impl or ("pallas" if on_tpu() else "interpret")
    U = focus(D, block=block, block_z=block_z, impl=impl, ties=ties)
    with TraceAnnotation("pipeline.weights"):
        W = weights_ref(U, n_valid)
    C = cohesion_from_weights(D, W, block=block, block_z=block_z, impl=impl,
                              ties=ties)
    if normalize:
        with TraceAnnotation("pipeline.finish"):
            C = C / (D.shape[0] - 1)
    return C


def pald_fused(
    X,
    *,
    metric: str = "euclidean",
    block=128,
    block_z=512,
    normalize: bool = False,
    impl: str | None = None,
    ties=DEFAULT_TIES,
):
    """Fused features→cohesion pipeline: X (n, d) -> C (n, n).

    Distance tiles are computed on the fly from (block, d) feature tiles —
    inside the Pallas kernels on TPU (``pald_fused.py``), inside the block
    loops of the jnp fallback on CPU — so the full (n, n) distance matrix is
    never materialized.  Feature rows are zero-padded to the tile quantum;
    the +inf/zero-diagonal padding contract is re-imposed per tile from the
    static ``n_valid``.

    ``block="auto"`` resolves tiles through the tuning cache under the
    ``pald_fused`` pass, keyed by (n, d).
    """
    from repro.core.features import pad_features

    ties = resolve_weight(ties)
    impl = impl or ("pallas" if on_tpu() else "jnp")
    fault_point("ops.pald_fused", impl=impl, ties=ties.name)
    X = jnp.asarray(X, jnp.float32)
    n, d = X.shape
    block, block_z, _ = _tuner.resolve_fused_tiles(n, d, block, block_z,
                                                   impl=impl, ties=ties)
    if impl == "jnp":
        with TraceAnnotation("pipeline.pad"):
            Xp, n0 = pad_features(X, block)
        U = _focus_fused_jnp(Xp, metric=metric, block=block, block_z=block_z,
                             n_valid=n0, ties=ties)
        with TraceAnnotation("pipeline.weights"):
            W = weights_ref(U, n0 if Xp.shape[0] != n0 else None)
        C = _cohesion_fused_jnp(Xp, W, metric=metric, block=block,
                                block_z=block_z, n_valid=n0, ties=ties)
    else:
        from .pald_fused import cohesion_fused_pallas, focus_fused_pallas

        with TraceAnnotation("pipeline.pad"):
            Xp, n0 = pad_features(X, max(block, block_z))
            if impl == "pallas" and d % 128:
                # zero feature columns are exact no-ops for every metric; pad d
                # to the lane quantum so Mosaic gets aligned (block, d) tiles
                Xp = jnp.pad(Xp, ((0, 0), (0, 128 - d % 128)))
        m = Xp.shape[0]
        block, block_z = _pick_block(m, block), _pick_block(m, block_z)
        interp = impl == "interpret"
        with TraceAnnotation("kernel.focus_fused"):
            U = focus_fused_pallas(Xp, metric=metric, n_valid=n0, block=block,
                                   block_z=block_z, interpret=interp,
                                   ties=ties)
        with TraceAnnotation("pipeline.weights"):
            W = weights_ref(U, n0 if m != n0 else None)
        with TraceAnnotation("kernel.cohesion_fused"):
            C = cohesion_fused_pallas(Xp, W, metric=metric, n_valid=n0,
                                      block=block, block_z=block_z,
                                      interpret=interp, ties=ties)
    with TraceAnnotation("pipeline.finish"):
        C = C[:n, :n]
        if normalize:
            C = C / max(n - 1, 1)
    return C


def pald_tri(
    D,
    *,
    block=128,
    block_z=512,
    normalize: bool = False,
    n_valid=None,
    impl: str | None = None,
    ties=DEFAULT_TIES,
):
    """Fused tri-schedule pipeline: tri-focus -> precomputed-reciprocal
    weights -> tri-cohesion.  Both passes visit only the nb(nb+1)/2
    upper-triangular block pairs (paper Algorithm 2 at block granularity,
    DESIGN.md §4.3); padding to the tile multiple happens once here.
    """
    ties = resolve_weight(ties)
    impl = impl or ("pallas" if on_tpu() else "interpret")
    fault_point("ops.pald_tri", impl=impl, ties=ties.name)
    n_in = D.shape[0]
    bf, bzf = _resolve_blocks(n_in, "focus_tri", block, block_z, impl, ties)
    bc, bzc = _resolve_blocks(n_in, "cohesion_tri", block, block_z, impl, ties)
    bf, bzf = min(bf, n_in), min(bzf, n_in)
    bc, bzc = min(bc, n_in), min(bzc, n_in)
    # one pipeline-level pad to the largest requested tile, then shrink each
    # tile to a divisor of the padded size (bounded quantum, no lcm blow-up)
    tiles = (bf, bc) if impl == "jnp" else (bf, bc, bzf, bzc)
    with TraceAnnotation("pipeline.pad"):
        Dp, _, _ = _pad_square_tri(D, None, max(tiles))
    m = Dp.shape[0]
    bf, bc = _pick_block(m, bf), _pick_block(m, bc)
    bzf, bzc = _pick_block(m, bzf), _pick_block(m, bzc)
    nv = n_valid if n_valid is not None else (n_in if Dp.shape[0] != n_in else None)
    if impl == "jnp":
        U = _focus_tri_jnp(Dp, block=bf, ties=ties)
        with TraceAnnotation("pipeline.weights"):
            W = weights_ref(U, nv)
        C = _cohesion_tri_jnp(Dp, W, block=bc, ties=ties)
    else:
        interp = impl == "interpret"
        with TraceAnnotation("kernel.focus_tri"):
            U = focus_tri_pallas(Dp, block=bf, block_z=bzf, interpret=interp,
                                 ties=ties)
        with TraceAnnotation("pipeline.weights"):
            W = weights_ref(U, nv)
        with TraceAnnotation("kernel.cohesion_tri"):
            C = cohesion_tri_pallas(Dp, W, block=bc, block_z=bzc,
                                    interpret=interp, ties=ties)
    with TraceAnnotation("pipeline.finish"):
        C = C[:n_in, :n_in]
        if normalize:
            C = C / (n_in - 1)
    return C


# --------------------------------------------------------------------------
# sparse k-NN pipeline (O(n * k^2) cohesion; core/knn.py has the semantics).
# The jnp fallback streams the gathered (block, k, k) neighbor tiles chunk
# by chunk (O(block * k^2) live); the Pallas path stages the full gathered
# cube in HBM (O(n * k^2)) and lets the kernel iterate (block, k) tiles.
# The cube is one program that walks the padded rows in chunks, so the
# (rows, k, d) feature gather each chunk stages stays within
# ``_CUBE_STAGE_BYTES`` whatever d is.
# --------------------------------------------------------------------------
from repro.core import knn as _knn  # noqa: E402

_CUBE_STAGE_BYTES = 1 << 30


def _gather_tiles(x, idxc, kind: str, metric: str):
    if kind == "distance":
        return _knn.gather_tile_from_distances(x, idxc)
    return _knn.gather_tile_from_features(x, idxc, metric)


def knn_cube_layout(n: int, k: int, d: int | None = None, *,
                    block: int | str = "auto", impl: str | None = None,
                    ties=DEFAULT_TIES) -> dict:
    """How ``knn_values`` stages the Pallas path's neighbor cube.

    For an (n, k) graph over (n, d) features, or over a distance matrix
    (``d=None``): the knn row ``block`` (``"auto"`` resolves as
    ``knn_values`` does), the ``padded_rows`` m, the ``rows`` of each
    chunk (whole blocks, the chunks as even as whole blocks allow), the
    number of ``chunks``, and ``staged_bytes``, the f32 bytes one chunk
    gathers: (rows, k, d) features, or its own (rows, k, k) tile from a
    distance matrix.  One block a chunk where a single block already
    exceeds the budget."""
    if block == "auto":
        block, _ = _tuner.resolve_blocks(n, "pald_knn", impl=impl or
                                         _default_impl(),
                                         ties=resolve_weight(ties), k=k)
    block = max(min(int(block), n), 1)
    m = -(-n // block) * block
    per_row = 4 * k * (k if d is None else d)
    blocks = m // block
    fit = max(_CUBE_STAGE_BYTES // (block * per_row), 1)   # blocks a chunk
    chunks = -(-blocks // fit)
    rows = -(-blocks // chunks) * block
    return dict(block=block, padded_rows=m, rows=rows,
                chunks=-(-m // rows), staged_bytes=rows * per_row)


@functools.partial(jax.jit,
                   static_argnames=("kind", "metric", "rows", "kp"))
def _neighbor_cube(x, dn_p, idx_p, *, kind: str, metric: str, rows: int,
                   kp: int):
    """The Pallas path's operands in one program: the (m, kp) lane-padded
    neighbor distances and indices, and the (m, kp, kp) neighbor cube.

    Chunk i gathers rows [r0, r0 + rows) with r0 = min(i * rows, m - rows):
    the last chunk ends at m and recomputes the rows it shares with the one
    before it, to the same bits.  Each chunk is the tile ``_gather_tiles``
    gives for its rows, written into the zeroed lane-padded cube."""
    m, k = idx_p.shape

    def chunk(i, g):
        r0 = jnp.minimum(i * rows, m - rows)
        idxc = jax.lax.dynamic_slice_in_dim(idx_p, r0, rows, 0)
        return jax.lax.dynamic_update_slice(
            g, _gather_tiles(x, idxc, kind, metric), (r0, 0, 0))

    g = jax.lax.fori_loop(0, -(-m // rows), chunk,
                          jnp.zeros((m, kp, kp), jnp.float32))
    return _pad2(dn_p, m, kp, jnp.inf), _pad2(idx_p, m, kp, 0), g


@functools.partial(jax.jit,
                   static_argnames=("kind", "metric", "block", "ties"))
def _knn_values_jnp(x, dn_p, idx_p, *, kind: str, metric: str, block: int,
                    ties=DEFAULT_TIES):
    """Blocked-jnp fallback: lax.map over row chunks of the padded graph;
    each chunk gathers its own (block, k, k) tile and runs the shared
    ``knn_values_tile`` body."""
    wfun = resolve_weight(ties)
    m, k = dn_p.shape
    offs = jnp.arange(m // block) * block

    def chunk(off):
        dnc = jax.lax.dynamic_slice(dn_p, (off, 0), (block, k))
        idxc = jax.lax.dynamic_slice(idx_p, (off, 0), (block, k))
        g = _gather_tiles(x, idxc, kind, metric)
        ow = None
        if wfun.needs_index_tiebreak:
            ow = (off + jnp.arange(block))[:, None] > idxc
        return _knn.knn_values_tile(dnc, g, ow, wfun)

    return jax.lax.map(chunk, offs).reshape(m, k + 1)


def knn_values(
    x,
    graph: "_knn.NeighborGraph",
    *,
    kind: str = "distance",
    metric: str = "euclidean",
    block: int | str = "auto",
    impl: str | None = None,
    ties=DEFAULT_TIES,
) -> jnp.ndarray:
    """Sparse (n, k+1) cohesion values for a prebuilt neighbor graph.

    Args:
        x: the gather source the graph was built from — the (n, n)
            distance matrix (``kind="distance"``) or the (n, d) feature
            matrix (``kind="features"``; neighbor-to-neighbor tiles are
            recomputed from features so D never materializes).
        graph: ``core.knn.NeighborGraph`` over the same ``x``.
        block: row-tile size; ``"auto"`` resolves via the tuning cache
            under the ``pald_knn:k<k>`` pass.
        impl: 'pallas' (TPU), 'interpret' (bit-faithful kernel on CPU) or
            'jnp' (vectorized fallback, the CPU speed path); None =
            backend default.
        ties: weight functional (name or instance) shared with every
            other path (``core/weights.py``).

    Returns:
        (n, k+1) float32 values, column 0 = self support, un-normalized.
    """
    ties = resolve_weight(ties)
    impl = impl or _default_impl()
    fault_point("ops.knn_values", impl=impl, ties=ties.name)
    x = jnp.asarray(x, jnp.float32)
    n, k = graph.indices.shape
    if k == 0:  # n == 1 (or an explicit empty graph): no pairs, no support
        return jnp.zeros((n, 1), jnp.float32)
    cube = knn_cube_layout(n, k, x.shape[1] if kind == "features" else None,
                           block=block, impl=impl, ties=ties)
    block, m = cube["block"], cube["padded_rows"]
    with TraceAnnotation("pipeline.pad"):
        dn_p = _pad2(graph.distances.astype(jnp.float32), m, k, jnp.inf)
        idx_p = _pad2(graph.indices, m, k, 0)
    if impl == "jnp":
        vals = _knn_values_jnp(x, dn_p, idx_p, kind=kind, metric=metric,
                               block=block, ties=ties)
        with TraceAnnotation("pipeline.finish"):
            return vals[:n]
    from .pald_knn import knn_values_pallas

    with TraceAnnotation("pipeline.gather_cube"):
        # lane-pad the neighbor axis around the real-k gather (a pre-pad
        # gather would stage and recompute a (kp/k)^2-times-larger cube):
        # +inf pair distances, index 0, zero gathered distances — the
        # kernel masks every padded column out of the focus count and pair
        # weights via k_valid
        kp = k if impl == "interpret" else -(-k // 128) * 128
        dn_p, idx_p, g = _neighbor_cube(x, dn_p, idx_p, kind=kind,
                                        metric=metric, rows=cube["rows"],
                                        kp=kp)
    with TraceAnnotation("kernel.knn_values"):
        vals = knn_values_pallas(dn_p, g, idx_p, block=block, k_valid=k,
                                 ties=ties, interpret=impl == "interpret")
    with TraceAnnotation("pipeline.finish"):
        return vals[:n, :k + 1]


def pald_knn(
    x,
    *,
    k: int,
    kind: str = "distance",
    metric: str = "euclidean",
    block: int | str = "auto",
    impl: str | None = None,
    ties=DEFAULT_TIES,
    normalize: bool = False,
    row_chunk: int = 1024,
    graph: "_knn.NeighborGraph | None" = None,
) -> tuple["_knn.NeighborGraph", jnp.ndarray]:
    """Full sparse k-NN PaLD: neighbor selection + sparse cohesion values.

    Args:
        x: (n, n) distances (``kind="distance"``) or (n, d) features
            (``kind="features"`` — D is never materialized: selection is
            row-chunked and cohesion tiles are recomputed from features).
        k: neighborhood size; clamped to n-1.  NOTE: unlike the engine
            executor behind ``pald.cohesion(method="knn")``, this entry
            point always runs the sparse machinery, even at k = n-1 — the
            executor short-circuits that case to the exact dense path.
        graph: optional prebuilt NeighborGraph (skips selection — useful
            when scoring multiple tie modes on one neighborhood).
        normalize: divide values by (n-1), matching the dense pipelines.
        (Other knobs: see ``knn_values``.)

    Returns:
        (graph, values): the NeighborGraph used and the (n, k+1) sparse
        cohesion values (column 0 = self).  ``core.knn.scatter_dense``
        expands them to the dense (n, n) C; ``core.knn.communities``
        consumes them directly.

    Example:
        >>> import jax.numpy as jnp
        >>> D = jnp.asarray([[0., 1., 4.], [1., 0., 2.], [4., 2., 0.]])
        >>> g, vals = pald_knn(D, k=1)
        >>> vals.shape
        (3, 2)
    """
    ties = resolve_weight(ties)
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    k = min(int(k), max(n - 1, 0))
    if graph is None:
        if kind == "distance":
            graph = _knn.knn_from_distances(x, k)
        elif kind == "features":
            graph = _knn.knn_from_features(x, k, metric=metric,
                                           row_chunk=row_chunk)
        else:
            raise ValueError(f"unknown kind {kind!r} "
                             "(expected 'distance' or 'features')")
    vals = knn_values(x, graph, kind=kind, metric=metric, block=block,
                      impl=impl, ties=ties)
    if normalize:
        with TraceAnnotation("pipeline.finish"):
            vals = vals / max(n - 1, 1)
    return graph, vals


# --------------------------------------------------------------------------
# streaming neighbor selection (ROADMAP item 3).  Three impl families, all
# bitwise-identical to core.knn._top_k_rows on the masked distances:
#
#   pallas / interpret  kernels/pald_topk.py — (block, d) feature tiles,
#                       in-register distance tiles folded into a running
#                       (block, k) best-list by composite-key bitonic merge;
#                       neither D nor full score rows ever hit HBM.
#   jnp                 blocked-jnp fallback: one jit, lax.map over row
#                       slabs.  Strategy per slab from ``tile``:
#                       tile >= n  -> direct full-width stable lax.top_k;
#                       tile <  n  -> exact tile-min prefilter (per-tile
#                       minima over the EXACT distances pick k candidate
#                       tiles per row, the final top-k runs over the k*tile
#                       gathered columns).  Exactness: if element e were
#                       wrongly excluded, >= k tiles beat e's tile — tiles
#                       earlier in index order beat it tie-safely (their
#                       candidates have smaller indices), later tiles by
#                       strictly smaller minima — so the true top-k always
#                       survives the gather, tie-break included.  The proof
#                       needs the sqrt'd (exact) distances: per-tile minima
#                       over d^2 can invert across the sqrt rounding.
#   chunked             terminal degradation rung: unfused per-slab
#                       dist_tile -> host sync -> row-chunked lax.top_k,
#                       no fused machinery on the failure path.
#
# ``tile`` ("auto") and the slab size ``block`` resolve via the tuning
# cache pass ``pald_topk:k<k>:d<d>``: the optimum is k- and d-dependent
# (the prefilter amortizes the full-width top_k re-scan, which XLA:CPU
# makes data-dependent — clustered rows branch-predict ~2-3x faster than
# random ones), with the block_z slot of the record holding ``tile``.
# --------------------------------------------------------------------------
from .pald_topk import next_pow2 as _next_pow2  # noqa: E402
from .pald_topk import topk_pallas  # noqa: E402


def _topk_chunk(Xp, off, *, k: int, metric: str, chunk: int, n: int,
                tile: int):
    """One (chunk, n) selection slab -> ((chunk, k) dist, (chunk, k) idx)."""
    from repro.core.features import dist_tile

    X = Xp[:n]
    rows = jax.lax.dynamic_slice(Xp, (off, 0), (chunk, Xp.shape[1]))
    Dr = dist_tile(rows, X, metric)                       # (chunk, n)
    gids = off + jnp.arange(chunk)
    self_ = gids[:, None] == jnp.arange(n)[None, :]
    if tile >= n or tile < 1:                             # direct strategy
        return _knn._top_k_rows(jnp.where(self_, -jnp.inf, -Dr), k)
    Dr = jnp.where(self_, jnp.inf, Dr)
    nt = -(-n // tile)
    Drp = jnp.pad(Dr, ((0, 0), (0, nt * tile - n)),
                  constant_values=jnp.inf)
    M = jnp.min(Drp.reshape(chunk, nt, tile), axis=2)     # (chunk, nt)
    kt = min(k, nt)
    _, tids = jax.lax.top_k(-M, kt)
    # ascending tile ids keep gathered columns in global index order, so
    # the stable top_k below reproduces the lower-index-first tiebreak
    tids = jnp.sort(tids, axis=1)
    cols = (tids[:, :, None] * tile +
            jnp.arange(tile)[None, None, :]).reshape(chunk, kt * tile)
    Dg = jnp.take_along_axis(Drp, cols, axis=1)
    negv, p = jax.lax.top_k(-Dg, k)
    return -negv, jnp.take_along_axis(cols, p, axis=1).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "chunk", "n", "tile"))
def _topk_select_jnp(Xp, *, k: int, metric: str, chunk: int, n: int,
                     tile: int):
    offs = jnp.arange(Xp.shape[0] // chunk) * chunk
    return jax.lax.map(
        functools.partial(_topk_chunk, Xp, k=k, metric=metric, chunk=chunk,
                          n=n, tile=tile), offs)          # (nc, chunk, k)


@functools.partial(jax.jit, static_argnames=("metric", "k", "n"))
def _topk_slab_chunked(rows, X, off, *, metric: str, k: int, n: int):
    """One rung slab: dist_tile -> mask -> stable lax.top_k (row-chunked).

    ``off`` is traced (one compilation per slab SHAPE, not per offset)."""
    from repro.core.features import dist_tile

    Dr = dist_tile(rows, X, metric)
    gids = off + jnp.arange(rows.shape[0])
    self_ = gids[:, None] == jnp.arange(n)[None, :]
    return _knn._top_k_rows(jnp.where(self_, -jnp.inf, -Dr), k)


def _topk_select_chunked(X, k: int, *, metric: str, row_chunk: int = 256):
    """Terminal degradation rung: unfused host-driven slabs.

    Each slab is an independent jit (distances -> top_k) synced to host
    before the next starts — no lax.map, no fused program, the smallest
    machinery that can still answer.  Bitwise equals the direct jnp
    strategy (identical per-row ops; chunking never changes a row)."""
    n = X.shape[0]
    out_d, out_i = [], []
    tracing = isinstance(X, jax.core.Tracer)
    for off in range(0, n, row_chunk):
        rows = X[off:off + min(row_chunk, n - off)]
        dv, di = _topk_slab_chunked(rows, X, jnp.int32(off), metric=metric,
                                    k=k, n=n)
        if not tracing:
            jax.block_until_ready(dv)
        out_d.append(dv)
        out_i.append(di)
    return jnp.concatenate(out_d), jnp.concatenate(out_i)


def _knn_from_distances_chunked(D, k: int, *, row_chunk: int = 1024):
    """Row-chunked lax.top_k over a materialized D (distance-kind rung).

    Bitwise equals ``core.knn.knn_from_distances`` — same per-row mask and
    stable top_k, slab at a time instead of one full-matrix call."""
    D = jnp.asarray(D, jnp.float32)
    n = D.shape[0]
    tracing = isinstance(D, jax.core.Tracer)
    out_d, out_i = [], []
    for off in range(0, n, row_chunk):
        rows = D[off:off + min(row_chunk, n - off)]
        gids = off + jnp.arange(rows.shape[0])
        self_ = gids[:, None] == jnp.arange(n)[None, :]
        dv, di = _knn._top_k_rows(jnp.where(self_, -jnp.inf, -rows), k)
        if not tracing:
            jax.block_until_ready(dv)
        out_d.append(dv)
        out_i.append(di)
    return _knn.NeighborGraph(jnp.concatenate(out_i), jnp.concatenate(out_d))


def _resolve_topk_tiles(n: int, d: int, k: int, block, tile,
                        impl: str) -> tuple[int, int]:
    """Turn "auto" selection knobs into (row slab, tile) via the cache."""
    if block == "auto" or tile == "auto":
        rb, rt = _tuner.resolve_blocks(n, "pald_topk", impl=impl, d=d, k=k)
        block = rb if block == "auto" else block
        tile = rt if tile == "auto" else tile
    return max(min(int(block), max(n, 1)), 1), int(tile)


def topk_select(
    X,
    k: int,
    *,
    metric: str = "euclidean",
    impl: str | None = None,
    block: int | str = "auto",
    tile: int | str = "auto",
) -> "_knn.NeighborGraph":
    """Streaming neighbor selection: (n, d) features -> NeighborGraph.

    The selection counterpart of ``knn_values``: one entry point, every
    impl bitwise-identical to ``core.knn._top_k_rows`` on the self-masked
    distances (stable lower-index-first tie-break included).

    Args:
        X: (n, d) feature matrix (cast to float32 once).
        k: neighborhood size, ``0 <= k <= n-1``.
        metric: one of ``features.METRICS``.
        impl: 'pallas' (TPU) / 'interpret' — the streaming Pallas kernel
            (``kernels/pald_topk.py``); 'jnp' — the blocked-jnp fallback
            (direct or tile-min-prefiltered, see module comment);
            'chunked' — the terminal degradation rung (unfused per-slab
            ``lax.top_k`` with host syncs).  None = backend default.
        block: rows per selection slab (the kernel's row tile); "auto"
            resolves via the ``pald_topk:k<k>:d<d>`` tuning-cache pass.
        tile: jnp strategy knob — column tile width of the tile-min
            prefilter; ``tile >= n`` means direct full-width top_k.  For
            the Pallas impls this is the candidate tile ``block_z``
            (rounded to a power of two).  "auto" resolves with ``block``.

    Returns:
        ``core.knn.NeighborGraph`` — indices/distances (n, k).

    Raises:
        ValueError: unknown metric/impl, or ``k > n-1``.
    """
    impl = impl or _default_impl()
    if impl not in ("pallas", "interpret", "jnp", "chunked"):
        raise ValueError(
            f"unknown impl {impl!r} (expected 'pallas', 'interpret', "
            "'jnp' or 'chunked')")
    fault_point("ops.topk_select", impl=impl, metric=metric)
    X = jnp.asarray(X, jnp.float32)
    n, d = X.shape
    if k > max(n - 1, 0):
        raise ValueError(f"k={k} exceeds the n-1={n - 1} available neighbors")
    if k <= 0:
        return _knn.NeighborGraph(jnp.zeros((n, 0), jnp.int32),
                                  jnp.zeros((n, 0), jnp.float32))
    block, tile = _resolve_topk_tiles(n, d, k, block, tile, impl)
    if impl == "chunked":
        dv, di = _topk_select_chunked(X, k, metric=metric, row_chunk=block)
        return _knn.NeighborGraph(di, dv)
    if impl == "jnp":
        chunk = block
        m = -(-n // chunk) * chunk
        with TraceAnnotation("pipeline.pad"):
            Xp = jnp.pad(X, ((0, m - n), (0, 0)))
        dv, di = _topk_select_jnp(Xp, k=k, metric=metric, chunk=chunk, n=n,
                                  tile=tile)
        with TraceAnnotation("pipeline.finish"):
            return _knn.NeighborGraph(di.reshape(m, k)[:n],
                                      dv.reshape(m, k)[:n])
    # pallas / interpret: power-of-two candidate tile >= next_pow2(k),
    # rows padded to a multiple of both tiles (masked off via n_valid)
    kp = _next_pow2(k)
    bz = max(_next_pow2(min(int(tile), max(n, 1))), kp)
    bz = min(bz, _next_pow2(n))
    blk = 1
    while blk * 2 <= max(int(block), 1):
        blk *= 2                      # row tile rounded down to a pow2
    blk = min(blk, _next_pow2(n))
    q = max(blk, bz)                  # both pow2: lcm == max
    m = -(-n // q) * q
    with TraceAnnotation("pipeline.pad"):
        Xp = jnp.pad(X, ((0, m - n), (0, 0)))
    with TraceAnnotation("kernel.topk"):
        dv, di = topk_pallas(Xp, k=k, metric=metric, n_valid=n, block=blk,
                             block_z=bz, interpret=impl == "interpret")
    with TraceAnnotation("pipeline.finish"):
        return _knn.NeighborGraph(di[:n], dv[:n])


# --------------------------------------------------------------------------
# fused select -> cohere: the single-program sparse pipeline
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("k", "metric", "chunk", "n",
                                             "tile", "ties"))
def _select_cohere_jnp(Xp, *, k: int, metric: str, chunk: int, n: int,
                       tile: int, ties=DEFAULT_TIES):
    """One jit for the whole sparse pipeline: each row slab is selected,
    gathered and scored inside the same lax.map step, so the freshly
    selected (chunk, k) neighbor values/indices feed the ``pald_knn`` tile
    body (``core.knn.knn_values_tile``) directly — no NeighborGraph, no
    intermediate HBM round-trip between the stages."""
    wfun = resolve_weight(ties)
    offs = jnp.arange(Xp.shape[0] // chunk) * chunk

    def body(off):
        dv, di = _topk_chunk(Xp, off, k=k, metric=metric, chunk=chunk, n=n,
                             tile=tile)
        g = _knn.gather_tile_from_features(Xp[:n], di, metric)
        ow = None
        if wfun.needs_index_tiebreak:
            ow = (off + jnp.arange(chunk))[:, None] > di
        return dv, di, _knn.knn_values_tile(dv, g, ow, wfun)

    return jax.lax.map(body, offs)


def select_cohere(
    X,
    *,
    k: int,
    metric: str = "euclidean",
    block: int | str = "auto",
    tile: int | str = "auto",
    cohere_block: int | str = "auto",
    impl: str | None = None,
    select: str | None = None,
    ties=DEFAULT_TIES,
    normalize: bool = False,
) -> tuple["_knn.NeighborGraph", jnp.ndarray]:
    """Fused streaming selection -> sparse cohesion from features.

    The from_features knn pipeline in one pass: neighbor selection (see
    ``topk_select``) feeds the ``pald_knn`` tile body without a host-side
    ``NeighborGraph`` in between.  On the jnp impl both stages trace into
    ONE jit — selection, the neighbor-to-neighbor feature gather and
    ``knn_values_tile`` share each lax.map step, so only one (block, n)
    distance slab is ever live.  On the Pallas impls the streaming
    selection kernel's (m, k) device outputs feed the cohesion kernel
    directly.  Bitwise equals the two-stage ``knn_from_features`` ->
    ``pald_knn`` composition for every weight functional (identical
    selection, identical tile body, chunking never changes a row).

    Args:
        X: (n, d) features.
        k: neighborhood size (clamped to n-1).
        block / tile: selection knobs (see ``topk_select``).
        cohere_block: row tile of the standalone cohesion pass — used only
            when selection and cohesion cannot fuse into one program
            (Pallas impls, 'chunked' selection); "auto" = ``pald_knn``
            cache.
        impl: cohesion impl ('pallas'/'interpret'/'jnp'); None = default.
        select: selection impl override; None = follow ``impl``.
        ties: weight functional; normalize: divide values by (n-1).

    Returns:
        (graph, values) — the selected NeighborGraph (returned for
        downstream analysis; built AFTER the fused compute) and the
        (n, k+1) sparse cohesion values (column 0 = self).
    """
    ties = resolve_weight(ties)
    impl = impl or _default_impl()
    sel = select or ("jnp" if impl == "jnp" else impl)
    fault_point("ops.select_cohere", impl=impl, select=sel, ties=ties.name)
    X = jnp.asarray(X, jnp.float32)
    n, d = X.shape
    k = min(int(k), max(n - 1, 0))
    if k <= 0:
        return (_knn.NeighborGraph(jnp.zeros((n, 0), jnp.int32),
                                   jnp.zeros((n, 0), jnp.float32)),
                jnp.zeros((n, 1), jnp.float32))
    if sel == "jnp" and impl == "jnp":
        block, tile = _resolve_topk_tiles(n, d, k, block, tile, sel)
        chunk = block
        m = -(-n // chunk) * chunk
        with TraceAnnotation("pipeline.pad"):
            Xp = jnp.pad(X, ((0, m - n), (0, 0)))
        fault_point("ops.topk_select", impl=sel, metric=metric)
        dv, di, vals = _select_cohere_jnp(Xp, k=k, metric=metric,
                                          chunk=chunk, n=n, tile=tile,
                                          ties=ties)
        with TraceAnnotation("pipeline.finish"):
            graph = _knn.NeighborGraph(di.reshape(m, k)[:n],
                                       dv.reshape(m, k)[:n])
            vals = vals.reshape(m, k + 1)[:n]
    else:
        # two kernels back-to-back: device arrays flow straight through
        graph = topk_select(X, k, metric=metric, impl=sel, block=block,
                            tile=tile)
        vals = knn_values(X, graph, kind="features", metric=metric,
                          block=cohere_block, impl=impl, ties=ties)
    if normalize:
        with TraceAnnotation("pipeline.finish"):
            vals = vals / max(n - 1, 1)
    return graph, vals


# --------------------------------------------------------------------------
# engine executors: the kernel-pipeline cells of the dispatch registry
# (repro.core.engine).  Each receives one unbatched item plus the resolved
# plan; the plan's tiles/impl/ties were fixed once at plan() time, so these
# bodies never consult the tuning cache themselves.
# --------------------------------------------------------------------------
from repro.core import engine as _engine  # noqa: E402  (registry import)


def _kernel_exec(D, plan, pipeline):
    with TraceAnnotation("pipeline.pad"):
        Dp, n0 = _engine.pad_distance_matrix(D, plan.block)  # f32 boundary
    nv = jnp.asarray(n0) if Dp.shape[0] != n0 else None
    kz = {} if plan.block_z is None else {"block_z": plan.block_z}
    C = pipeline(Dp, block=plan.block, n_valid=nv, impl=plan.impl,
                 ties=plan.weight, **kz)
    with TraceAnnotation("pipeline.finish"):
        C = C[:n0, :n0]
        return C / max(n0 - 1, 1) if plan.normalize else C


@_engine.register_executor("distance", "kernel", "dense")
def _exec_kernel_dense(D, plan):
    return _kernel_exec(D, plan, pald)


@_engine.register_executor("distance", "kernel", "tri")
def _exec_kernel_tri(D, plan):
    return _kernel_exec(D, plan, pald_tri)


@_engine.register_executor("features", "fused", "dense")
def _exec_fused(X, plan):
    return pald_fused(X, metric=plan.metric, block=plan.block,
                      block_z=plan.block_z, normalize=plan.normalize,
                      impl=plan.impl, ties=plan.weight)


# -- sparse k-NN cells ------------------------------------------------------
# At k >= n-1 every point is every other point's neighbor: the restriction
# is the identity, and gathering the (n, n-1, n-1) neighbor cube would be
# strictly more work than the dense computation it reproduces.  The
# executors therefore run the exact dense path there — which also makes
# `cohesion(D, method="knn", k=n-1)` agree with `method="dense"` bitwise,
# the anchor of the knn→dense convergence contract (test_conformance.py).
# ``ops.pald_knn`` itself never short-circuits, so the sparse machinery
# stays testable at full k.
def _knn_dense_fallback(D, plan):
    return _engine.get_executor("distance", "dense", "dense")(D, plan)


def _dense_from_sparse(graph, vals, plan):
    """The dense (n, n) C users get from a knn cell's sparse values."""
    with TraceAnnotation("pipeline.scatter_dense"):
        C = _knn.scatter_dense(graph, vals)
    with TraceAnnotation("pipeline.finish"):
        n = C.shape[0]
        return C / max(n - 1, 1) if plan.normalize else C


@_engine.register_executor("distance", "knn", "dense")
def _exec_knn_distance(D, plan):
    D = jnp.asarray(D, jnp.float32)
    n = D.shape[0]
    if plan.k >= n - 1:
        return _knn_dense_fallback(D, plan)
    graph = None
    if plan.select == "chunked":
        # terminal selection rung: row-chunked lax.top_k over D's slabs
        graph = _knn_from_distances_chunked(D, plan.k)
    graph, vals = pald_knn(D, k=plan.k, kind="distance", block=plan.block,
                           impl=plan.impl, ties=plan.weight, graph=graph)
    return _dense_from_sparse(graph, vals, plan)


@_engine.register_executor("features", "knn", "dense")
def _exec_knn_features(X, plan):
    """The fused select->cohere cell: selection streams straight into the
    pald_knn tile body (``select_cohere``) — no host-side NeighborGraph
    between the stages, no (n, n) intermediate ever."""
    X = jnp.asarray(X, jnp.float32)
    n = X.shape[0]
    if plan.k >= n - 1:
        from repro.core.features import cdist_reference

        return _knn_dense_fallback(cdist_reference(X, metric=plan.metric),
                                   plan)
    if getattr(plan, "mesh", None) is not None:
        from repro.core import distributed_knn as _dknn

        graph, vals = _dknn.pald_knn_sharded(
            X, plan.mesh, k=plan.k, metric=plan.metric,
            strategy=plan.strategy or "auto", normalize=False,
            weight=plan.weight, block=plan.select_block or "auto",
            tile=plan.select_tile if plan.select_tile is not None
            else "auto", on_error="raise")
        return _dense_from_sparse(graph, vals, plan)
    graph, vals = select_cohere(
        X, k=plan.k, metric=plan.metric,
        block=plan.select_block or "auto",
        tile=plan.select_tile if plan.select_tile is not None else "auto",
        cohere_block=plan.block, impl=plan.impl, select=plan.select,
        ties=plan.weight)
    return _dense_from_sparse(graph, vals, plan)
