"""Execution-plan engine: resolve once, run anywhere.

The paper's speedups come from picking the right variant per problem size
(blocked pairwise vs. block-symmetric triplet vs. tuned kernel tiles), but
that choice used to be re-derived in four places: ``core/pald.py`` branched
on method, every ``kernels/ops`` entry point re-resolved blocks/impl/padding,
``core/features.py`` had its own batch layer, and ``core/distributed.py``
re-threaded impl+ties into every shard body.  This module centralizes ALL of
that (DESIGN.md §11):

``plan(x, kind=...) -> PaldPlan``
    Performs every resolution exactly once — auto-method via the tuning
    cache, ``block="auto"`` via ``tuning.resolve_blocks``, impl defaults per
    pipeline, knob validation (``schedule="tri"`` off-kernel, ``block_z`` on
    a non-kernel path, ``z_chunk`` off-dense, ...), and input shape/value
    checks — and returns a frozen, reusable plan.

``PaldPlan.execute(x)``
    The single dispatch path: looks the resolved ``(kind, method, schedule)``
    up in the EXECUTOR REGISTRY and runs it.  Batched input (``(B, n, n)``
    distances or ``(B, n, d)`` features) is handled here, once, for every
    method — chunked ``jax.vmap`` bounded by the plan's ``batch=`` knob —
    so the Pallas tri pipeline batches exactly like the dense jnp paths.

``register_executor(kind, method, schedule)``
    How ``core/pairwise``, ``core/triplet`` and ``kernels/ops`` contribute
    their callables; alternative backends (a partitioned-kNN local depth, a
    generalized-PaLD variant) plug in the same way without touching the
    facades.

``PaldPlan.explain()``
    The resolved dict — method/tiles with cache provenance, padded shape,
    estimated VMEM per grid step — for debuggability and bench provenance.

``pald.cohesion`` / ``pald.from_features`` are thin facades over
``plan(...).execute(x)``; they contain no method branching.

Profiler spans (``jax.profiler.TraceAnnotation``) mark the stages of a call
in a trace: ``engine.plan`` (the whole resolution, tuning cache included),
``engine.validate`` (the input checks of ``execute``) and
``engine.execute`` (the executor dispatch, parent of the ``pipeline.*`` and
``kernel.*`` spans of ``kernels/ops``).  A span records nothing unless a
trace is running.  Under a caller's ``jax.jit`` they mark tracing only.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.tuning import autotune as _tuner

from . import resilience as _res
from .weights import (DEFAULT_TIES, WeightFunctional, registered_weights,
                      resolve_weight, validate_ties)

__all__ = [
    "PaldPlan",
    "plan",
    "plan_local",
    "register_executor",
    "get_executor",
    "available_executors",
    "pad_distance_matrix",
    "run_batched",
]

DISTANCE_METHODS = ("dense", "pairwise", "triplet", "kernel", "knn")
FEATURE_METHODS = ("fused",) + DISTANCE_METHODS
SCHEDULES = ("dense", "tri")

# methods whose executors take an impl= knob (kernel pipelines); the pure-jnp
# blocked paths have exactly one implementation, so an explicit impl request
# there is a caller error, not something to drop silently
_IMPL_METHODS = ("kernel", "fused", "knn")


def pad_distance_matrix(
    D: jnp.ndarray, block: int, *, dtype=jnp.float32
) -> tuple[jnp.ndarray, int]:
    """Pad D to a multiple of ``block`` with +inf off-diagonal, 0 diagonal.

    Padded points are infinitely far from everything: they never enter a real
    pair's local focus (inf < d is false) and every real z is inside a padded
    pair's focus but contributes to padded rows of C only.

    The input is cast to ``dtype`` (float32 by default) *here*, before any
    blocked arithmetic — this is the pipeline's one explicit downcast point;
    nothing downstream changes precision again.
    """
    D = jnp.asarray(D, dtype)
    n = D.shape[0]
    m = -(-n // block) * block
    if m == n:
        return D, n
    P = jnp.full((m, m), jnp.inf, D.dtype)
    P = P.at[:n, :n].set(D)
    P = P.at[jnp.arange(m), jnp.arange(m)].set(0.0)
    return P, n


# ---------------------------------------------------------------------------
# executor registry
# ---------------------------------------------------------------------------
_EXECUTORS: dict[tuple[str, str, str], Callable] = {}


def register_executor(kind: str, method: str, schedule: str = "dense"):
    """Decorator: contribute the executor for one (kind, method, schedule)
    cell.  The callable receives ``(x, plan)`` with ``x`` one UNBATCHED item
    (a (n, n) distance matrix or (n, d) feature matrix, any float dtype) and
    owns the full per-item pipeline: cast, pad, compute, slice, normalize.
    It must be traceable (plan.execute vmaps it for batched input)."""

    def deco(fn):
        _EXECUTORS[(kind, method, schedule)] = fn
        return fn

    return deco


def _load_contributors() -> None:
    """Import the modules that register the default executors.  Deferred so
    importing the engine (or core.pald) stays cheap and cycle-free; the
    kernels package in particular is only pulled in on first kernel use."""
    from repro.core import pairwise, triplet  # noqa: F401
    from repro.kernels import ops  # noqa: F401


def get_executor(kind: str, method: str, schedule: str) -> Callable:
    key = (kind, method, schedule)
    if key not in _EXECUTORS:
        _load_contributors()
    if key not in _EXECUTORS:
        raise KeyError(
            f"no executor registered for {key}; known cells: "
            f"{sorted(_EXECUTORS)}"
        )
    return _EXECUTORS[key]


def available_executors() -> list[tuple[str, str, str]]:
    """All registered (kind, method, schedule) cells (contributors loaded)."""
    _load_contributors()
    return sorted(_EXECUTORS)


def run_batched(fn, x, plan: "PaldPlan", batch: int | None = None):
    """The engine's uniform batch layer: run executor ``fn`` over ``x``.

    2-D input goes straight through; 3-D input is vmapped in chunks of
    ``batch`` items (None = the whole batch in one compiled call).
    Chunking is a pure re-partition of the same computation — results are
    bitwise-equal for any chunk size (asserted in test_conformance.py),
    which is what makes the OOM batch-halving retry in ``core/resilience``
    a value-preserving degradation.

    Shared by ``PaldPlan.execute`` and the degradation-chain steps so a
    fallback attempt batches exactly like the primary attempt did.
    """
    if x.ndim == 2:
        return fn(x, plan)
    B = x.shape[0]
    eff = B if batch is None else min(batch, B)
    _res.fault_point("engine.batch", batch=eff, n=plan.n, kind=plan.kind,
                     method=plan.method, impl=plan.impl)
    single = lambda xi: fn(xi, plan)  # noqa: E731
    if eff >= B:
        return jax.vmap(single)(x)
    chunks = [jax.vmap(single)(x[s:s + eff]) for s in range(0, B, eff)]
    return jnp.concatenate(chunks, axis=0)


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PaldPlan:
    """Frozen result of one resolution pass: everything an executor needs.

    Build with ``plan(...)`` (or ``plan_local`` for distributed shard
    bodies); never mutate — a plan is safe to reuse across calls and across
    threads for any input matching its item shape.
    """

    kind: str                     # "distance" | "features"
    method: str                   # resolved (never "auto")
    schedule: str                 # "dense" | "tri"
    impl: str | None              # kernel/fused impl; None = one-impl path
    block: int | None             # None for the un-blocked dense method
    block_z: int | None           # z tile; None = executor default
    z_chunk: int | None           # dense-method z streaming chunk
    ties: str
    metric: str | None            # features kind only
    normalize: bool
    batch: int | None             # vmap chunk bound for batched input
    check: bool                   # deep input validation on execute
    n: int                        # per-item point count
    d: int | None                 # feature dimension (features kind)
    k: int | None = None          # neighborhood size (knn method only)
    on_error: str = "raise"       # "raise" | "fallback" (degradation chain)
    # knn selection stage (features kind): impl override and its tiles.
    # select=None follows impl; "chunked" is the terminal degradation rung
    # (row-chunked lax.top_k).  select_tile >= n disables the tile-min
    # prefilter (direct slab top_k); see kernels/ops.topk_select.
    select: str | None = None
    select_block: int | None = None   # rows per selection slab
    select_tile: int | None = None    # tile-min prefilter width
    select_source: str = "n/a"        # provenance (explain)
    # mesh-sharded knn (features kind, core/distributed_knn.py): the device
    # mesh the fused select->cohere pipeline shards over, and the resolved
    # shard strategy ('allgather'/'ring'/'2d').  None = single device.
    mesh: Any = None
    strategy: str | None = None
    # the resolved weight functional (core/weights.py); ``ties`` above is its
    # name, kept as the stable string surface for explain()/fault contexts.
    weight: WeightFunctional | None = None
    # provenance (explain)
    method_source: str = "explicit"
    block_source: str = "explicit"
    # structured degradation events appended by core/resilience when
    # on_error="fallback" degrades an execution; surfaced in explain().
    # init=False keeps the frozen plan hashable/replace()-safe: derived
    # plans start with a fresh empty log while the guard records on the
    # plan the caller holds.
    _events: list = dataclasses.field(
        default_factory=list, init=False, compare=False, repr=False)

    # -- execution ---------------------------------------------------------
    def execute(self, x) -> jnp.ndarray:
        """Run the planned pipeline on ``x`` — one item or a batch.

        ``x``: (n, n) / (B, n, n) distances, or (n, d) / (B, n, d) features,
        matching the plan's item shape.  Batching is uniform across every
        (method, schedule) cell: items are vmapped in chunks of ``batch=``
        (None = whole batch in one compiled call), which bounds peak memory
        at ``batch * n^2`` floats regardless of the underlying executor.

        With ``on_error="fallback"`` a failing execution degrades instead
        of raising: OOM on the batched call retries with halved ``batch``
        (re-chunking is bitwise-equal), any other executor failure walks
        the cell's degradation chain (``core/resilience``) re-executing
        with identical ties/normalize semantics.  Every degradation is
        recorded in ``explain()["degradations"]``.
        """
        x = jnp.asarray(x)
        with jax.profiler.TraceAnnotation("engine.validate"):
            _check_input(x, self)
        with jax.profiler.TraceAnnotation("engine.execute"):
            if self.on_error == "fallback":
                return _res.execute_plan(self, x)
            _res.fault_point("engine.execute", kind=self.kind,
                             method=self.method, schedule=self.schedule,
                             impl=self.impl)
            fn = get_executor(self.kind, self.method, self.schedule)
            return run_batched(fn, x, self, self.batch)

    # -- distributed shard-body primitives ---------------------------------
    # The shard bodies in core/distributed.py call the rectangular kernel
    # forms per step; threading the plan instead of four loose knobs keeps
    # the resolution in one place (and in explain()).
    def focus_general(self, DXZ, DYZ, DXY) -> jnp.ndarray:
        from repro.kernels import ops as _kops

        def call(impl):
            return _kops.focus_general(DXZ, DYZ, DXY, block=self.block,
                                       block_z=self.block_z, impl=impl,
                                       ties=self.weight)

        if self.on_error == "fallback":
            return _res.guarded_general(self, "focus_general", call)
        return call(self.impl)

    def cohesion_general(self, DXZ, DYZ, DXY, W, *, xwins=None,
                         xw_offsets=None) -> jnp.ndarray:
        from repro.kernels import ops as _kops

        def call(impl):
            return _kops.cohesion_general(DXZ, DYZ, DXY, W, block=self.block,
                                          block_z=self.block_z, impl=impl,
                                          ties=self.weight, xwins=xwins,
                                          xw_offsets=xw_offsets)

        if self.on_error == "fallback":
            return _res.guarded_general(self, "cohesion_general", call)
        return call(self.impl)

    # -- introspection -----------------------------------------------------
    @property
    def padded_n(self) -> int:
        """Per-item extent after the engine-level pad to a block multiple
        (the kernel pipelines may pad further for their z tiles)."""
        if self.block is None:
            return self.n
        return -(-self.n // self.block) * self.block

    def _shard_rows(self) -> int | None:
        """Per-shard padded row count of a mesh plan (None off the mesh)."""
        if self.mesh is None:
            return None
        from repro.core import distributed_knn as _dknn

        p = self.mesh.devices.size
        chunk = self.select_block or 1
        _, _, m = _dknn.resolve_shard_shapes(self.n, p=p, chunk=chunk)
        return m // p

    def _comm_estimate(self) -> dict | None:
        """Per-device comm model of a mesh plan (None off the mesh)."""
        if self.mesh is None:
            return None
        from repro.core import distributed_knn as _dknn

        import math as _math
        shape = tuple(self.mesh.devices.shape)
        p = self.mesh.devices.size
        pr = _math.prod(shape[:-1]) if len(shape) >= 2 else 1
        return _dknn.comm_estimate(
            self.strategy or "auto", n=self.n, d=self.d or 1,
            k=self.k or 1, p=p, pr=pr, pc=shape[-1])

    def explain(self) -> dict[str, Any]:
        """The resolved plan as a plain dict — the debuggability surface.

        Returns:
            Dict with STABLE keys (bench provenance rows and debug logs
            rely on them): the resolved ``kind`` / ``method`` /
            ``schedule`` / ``impl`` / ``block`` / ``block_z`` /
            ``z_chunk`` / ``ties`` / ``weight`` / ``weight_properties`` /
            ``metric`` / ``normalize`` /
            ``batch`` / ``n`` / ``d`` / ``k`` / ``on_error`` (plus
            ``degradations``, the guarded-execution event log), the knn
            selection-stage report ``select`` / ``select_block`` /
            ``select_tile`` / ``select_source`` (None / "n/a" off the
            knn method), the mesh-sharding report ``mesh`` /
            ``mesh_axes`` / ``strategy`` / ``shard_rows`` /
            ``comm_estimate`` (device-mesh shape, resolved strategy,
            per-shard padded rows and the per-device communication model
            of ``core/distributed_knn.py``; all None off the mesh), the
            ``padded_n`` /
            ``padded_shape`` the executor will see, ``method_source`` and
            ``block_source`` provenance strings ("explicit",
            "cache:<key>", "nearest:<key>", "default", ...), the
            fully-qualified ``executor`` callable, and
            ``est_vmem_bytes_per_step`` (a planning aid, not a promise).

        Example:
            >>> from repro.core import pald
            >>> info = pald.plan(n=256, method="triplet", block=64).explain()
            >>> info["method"], info["block"], info["padded_n"]
            ('triplet', 64, 256)
        """
        fn = get_executor(self.kind, self.method, self.schedule)
        return {
            "kind": self.kind,
            "method": self.method,
            "schedule": self.schedule,
            "impl": self.impl,
            "block": self.block,
            "block_z": self.block_z,
            "z_chunk": self.z_chunk,
            "ties": self.ties,
            "weight": self.weight.name if self.weight else self.ties,
            "weight_properties": (self.weight.properties()
                                  if self.weight else None),
            "metric": self.metric,
            "normalize": self.normalize,
            "batch": self.batch,
            "n": self.n,
            "d": self.d,
            "k": self.k,
            "padded_n": self.padded_n,
            "padded_shape": ((self.padded_n, self.padded_n)
                             if self.kind == "distance"
                             else (self.padded_n, self.d)),
            "on_error": self.on_error,
            "select": self.select,
            "select_block": self.select_block,
            "select_tile": self.select_tile,
            "select_source": self.select_source,
            "mesh": (tuple(self.mesh.devices.shape)
                     if self.mesh is not None else None),
            "mesh_axes": (tuple(self.mesh.axis_names)
                          if self.mesh is not None else None),
            "strategy": self.strategy,
            "shard_rows": self._shard_rows(),
            "comm_estimate": self._comm_estimate(),
            "method_source": self.method_source,
            "block_source": self.block_source,
            "executor": f"{fn.__module__}.{fn.__qualname__}",
            "est_vmem_bytes_per_step": _est_vmem_per_step(self),
            # structured degradation events recorded by guarded execution
            # (on_error="fallback"): dicts with cell / cause / error /
            # fallback / retries, in occurrence order.  Empty on a plan
            # that never degraded.
            "degradations": list(self._events),
        }


def _est_vmem_per_step(p: PaldPlan) -> int | None:
    """Rough f32 bytes resident per grid step (per fori step for the jnp
    paths).  A planning aid — tile residency of the dominant pass-2 body,
    not a promise about XLA's actual allocation."""
    if p.block is None:  # un-blocked dense: (n, n, z_chunk) comparison cube
        zc = p.z_chunk or p.n
        return 4 * p.n * p.n * zc
    b = p.block
    m = p.padded_n
    if p.method == "knn":
        # (b, k, k) gathered tile + (b, k, k) comparison cube + (b, k) rows
        kk = p.k or 1
        est = 4 * (2 * b * kk * kk + 3 * b * kk + b * (kk + 1))
        if p.kind == "features" and p.select_block:
            # fused select->cohere: one (select_block, n) distance slab
            # is live per map step alongside the cohesion tiles
            est += 4 * p.select_block * p.n
        return est
    if p.method in ("pairwise", "triplet"):
        # (b, b, n) support cube + two (b, n) row slabs
        return 4 * (b * b * m + 2 * b * m)
    bz = p.block_z or min(512, m)
    d_ = p.d or 0
    tiles = 2 * b * bz + 2 * b * b + b * bz        # dxz, dyz, dxy, w, out
    if p.method == "fused":
        tiles += 2 * b * max(d_, 1)                # feature tiles
    if p.schedule == "tri":
        tiles += m * bz                            # resident Cy column slab
    return 4 * tiles


# ---------------------------------------------------------------------------
# input validation
# ---------------------------------------------------------------------------
def _item_shape_checks(x, p: PaldPlan) -> None:
    if x.ndim not in (2, 3):
        what = ("D must be (n, n) or (B, n, n)" if p.kind == "distance"
                else "X must be (n, d) or (B, n, d)")
        raise ValueError(f"{what}, got shape {tuple(x.shape)}")
    if p.kind == "distance" and x.shape[-1] != x.shape[-2]:
        raise ValueError(
            f"distance matrix must be square, got shape {tuple(x.shape)}")
    expect = (p.n, p.n) if p.kind == "distance" else (p.n, p.d)
    if tuple(x.shape[-2:]) != expect:
        raise ValueError(
            f"input item shape {tuple(x.shape[-2:])} does not match the "
            f"plan's {expect}; build a new plan for a new problem size")


def _check_input(x, p: PaldPlan) -> None:
    """Cheap always-on checks plus the opt-in deep ones (``check=True``).

    Value checks only run on concrete arrays — under jit/vmap tracing the
    values don't exist yet, and shape checks are all that can (and need to)
    fire there.  Note the flip side: an eager call on a device array that a
    previous async computation is still producing must SYNC on the O(n)
    diagonal fetch before dispatching, costing host-side overlap (never
    correctness).  A latency-critical pipeline that wants fully async
    dispatch should wrap the call in ``jax.jit`` — traced execution skips
    the value checks by construction.
    """
    _item_shape_checks(x, p)
    if isinstance(x, jax.core.Tracer) or p.kind != "distance":
        if p.check and not isinstance(x, jax.core.Tracer):
            if not bool(jnp.isfinite(x).all()):
                raise ValueError("features contain non-finite entries "
                                 "(nan/inf); PaLD needs finite coordinates")
        return
    # always-on O(n) check: a nonzero (or nan) diagonal means the input is
    # not a self-distance matrix — every padding and focus invariant assumes
    # d(x, x) == 0
    diag = np.asarray(jnp.diagonal(x, axis1=-2, axis2=-1))
    if not np.all(diag == 0.0):
        raise ValueError(
            "distance matrix diagonal must be exactly 0 "
            f"(got max |diag| = {np.nanmax(np.abs(diag))!r}; nan counts as "
            "nonzero); pass distances with d(x, x) = 0")
    if not p.check:
        return
    xv = np.asarray(x)
    if not np.isfinite(xv).all():
        raise ValueError("distance matrix contains non-finite entries "
                         "(nan/inf)")
    if (xv < 0).any():
        raise ValueError("distance matrix contains negative entries; "
                         "PaLD consumes the order of nonnegative distances")
    if not np.array_equal(xv, np.swapaxes(xv, -1, -2)):
        raise ValueError("distance matrix is not symmetric (exact equality "
                         "is required: PaLD compares d_xz against d_zx's "
                         "role symmetrically)")


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------
def _shape_of(x, n, d, kind):
    if x is not None:
        shape = tuple(np.shape(x))
        if len(shape) not in (2, 3):
            what = ("D must be (n, n) or (B, n, n)" if kind == "distance"
                    else "X must be (n, d) or (B, n, d)")
            raise ValueError(f"{what}, got shape {shape}")
        item = shape[-2:]
        if kind == "distance":
            if item[0] != item[1]:
                raise ValueError(
                    f"distance matrix must be square, got shape {shape}")
            return item[0], None
        return item[0], item[1]
    if n is None:
        raise ValueError("plan() needs either an input array or n=")
    if kind == "features" and d is None:
        raise ValueError("plan(kind='features') needs d= when no array "
                         "is given")
    return int(n), None if kind == "distance" else int(d)


def _resolve_weight_knob(ties, weight) -> WeightFunctional:
    """Resolve the ``ties=``/``weight=`` knob pair to ONE functional.

    ``ties=`` is sugar for the three built-in modes; ``weight=`` accepts any
    registered name or ``WeightFunctional`` instance.  Both given and
    resolving to different functionals is a contradiction (rejected, like
    every other knob pair); both None means the default (``'drop'``).
    """
    if weight is None:
        if ties is None:
            return resolve_weight(DEFAULT_TIES)
        validate_ties(ties)
        return resolve_weight(ties)
    w = resolve_weight(weight)
    if ties is not None:
        validate_ties(ties)
        tie_name = getattr(ties, "name", ties)
        if tie_name != w.name:
            raise ValueError(
                f"contradictory ties={tie_name!r} and weight={w.name!r}; "
                "ties= is sugar for the built-in modes — drop it, or pass "
                f"the matching one (registered weight functionals: "
                f"{registered_weights()})")
    return w


def _default_kernel_impl(method: str) -> str:
    """Backend-default impl per pipeline (mirrors kernels/ops): the fused
    and knn paths prefer the vectorized jnp fallback off-TPU (they exist
    for large n, where interpret-mode kernel emulation is prohibitive),
    the D-consuming kernel pipeline prefers bit-faithful interpret
    execution."""
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        return "pallas"
    return "jnp" if method in ("fused", "knn") else "interpret"


@functools.partial(jax.profiler.annotate_function, name="engine.plan")
def plan(
    x=None,
    *,
    kind: str = "distance",
    n: int | None = None,
    d: int | None = None,
    method: str = "auto",
    schedule: str = "dense",
    block: int | str | None = None,
    block_z: int | str | None = None,
    z_chunk: int | None = None,
    metric: str | None = None,
    normalize: bool = True,
    impl: str | None = None,
    ties: str | None = None,
    weight=None,
    batch: int | None = None,
    check: bool = False,
    k: int | None = None,
    on_error: str = "raise",
    select: str | None = None,
    select_block: int | str | None = None,
    select_tile: int | str | None = None,
    mesh=None,
    strategy: str | None = None,
) -> PaldPlan:
    """Resolve every knob exactly once and return a frozen ``PaldPlan``.

    ``x`` (or ``n=``/``d=``) fixes the per-item problem size the resolution
    is keyed on.  ``kind`` selects the input contract: ``"distance"`` (a
    precomputed (n, n) matrix — ``pald.cohesion``) or ``"features"`` ((n, d)
    vectors — ``pald.from_features``).  All remaining knobs have the same
    meaning as on the facades; validation rejects contradictions instead of
    silently dropping knobs (``schedule='tri'`` off the kernel pipeline,
    ``block_z``/``impl`` on a path that has no such degree of freedom,
    ``z_chunk`` off the dense method, unknown metrics/methods/tie modes,
    contradictory ``ties=``/``weight=``).
    ``ties=`` is sugar for the three built-in weight functionals;
    ``weight=`` accepts any registered functional name or
    ``WeightFunctional`` instance (``core/weights.py``) and generalizes the
    contribution algebra on every cell with zero kernel forks.
    ``on_error`` selects the failure semantics: ``"raise"`` (default)
    propagates the first executor failure unchanged, ``"fallback"`` walks
    the cell's degradation chain (``core/resilience``) and records every
    degradation in ``explain()["degradations"]``.
    ``select=`` / ``select_block=`` / ``select_tile=`` configure the knn
    SELECTION stage (features kind): the impl of the streaming top-k
    ('pallas'/'interpret'/'jnp'/'chunked'; None follows ``impl``), the
    rows per selection slab, and the tile-min prefilter width (>= n
    disables it); "auto"/None resolve via the ``pald_topk:k<k>:d<d>``
    tuning-cache pass.  On kind='distance' only ``select='chunked'`` (the
    row-chunked ``lax.top_k`` terminal rung) is meaningful.
    ``mesh=`` / ``strategy=`` shard the fused select->cohere knn pipeline
    across a ``jax.sharding.Mesh`` (``core/distributed_knn.py``): rows of X
    are sharded over all mesh axes and features rotate by ``strategy``
    ('allgather', 'ring', or '2d'; 'auto'/None picks '2d' on a >= 2-axis
    mesh, 'ring' otherwise).  Only kind='features' with method='knn'
    accepts a mesh, the result stays bitwise-equal to the single-device
    fused path, and ``explain()`` reports the mesh shape, per-shard rows,
    and a per-device comm estimate.

    One deliberate exception: ``block=`` is accepted AND ignored by
    ``method='dense'`` (the un-blocked path has no tile), so the common
    "sweep every method with one shared block argument" idiom stays valid —
    ``explain()['block']`` is ``None`` there, making the drop visible.
    """
    weight = _resolve_weight_knob(ties, weight)
    ties = weight.name
    if kind not in ("distance", "features"):
        raise ValueError(f"unknown kind {kind!r} "
                         "(expected 'distance' or 'features')")
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}")
    if on_error not in _res.ON_ERROR_MODES:
        raise ValueError(f"unknown on_error {on_error!r} (expected one of "
                         f"{_res.ON_ERROR_MODES}): 'raise' propagates the "
                         "first executor failure, 'fallback' walks the "
                         "degradation chain")
    n, d = _shape_of(x, n, d, kind)
    if batch is not None and batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")

    if kind == "features":
        from .features import METRICS

        metric = metric or "euclidean"
        if metric not in METRICS:
            raise ValueError(
                f"unknown metric {metric!r} (expected one of {METRICS})")
        allowed = FEATURE_METHODS
    else:
        if metric is not None:
            raise ValueError("metric= only applies to kind='features' "
                             "(a distance matrix already fixed it)")
        allowed = DISTANCE_METHODS

    # -- method ------------------------------------------------------------
    # Path-specific knobs PIN the auto method (the way an explicit tri
    # schedule always has) instead of letting the tuning cache decide and
    # then validating against its answer — otherwise whether a knob is legal
    # would flip with the input size and with another machine's cache state.
    method_source = "explicit"
    if method == "auto":
        if schedule == "tri":
            # an explicit tri request pins the kernel pipeline (the only
            # method with a tri schedule)
            method, method_source = "kernel", "schedule=tri"
        elif k is not None:
            # a neighborhood size is a knn request on either kind — the
            # sparse approximation must be opted into, never auto-selected
            if z_chunk is not None:
                raise ValueError(
                    "k= pins method='knn' but z_chunk= pins method='dense'; "
                    "pass an explicit method")
            method, method_source = "knn", "k"
        elif kind == "features":
            method, method_source = "fused", "default"
        elif z_chunk is not None:
            if impl is not None or block_z not in (None, "auto"):
                raise ValueError(
                    "z_chunk= pins method='dense' but impl=/block_z= pin "
                    "the kernel pipeline; pass an explicit method")
            method, method_source = "dense", "z_chunk"
        elif impl is not None or block_z not in (None, "auto"):
            # an explicit z TILE (or impl) is a kernel-pipeline request;
            # block_z="auto" is not — "auto" means "pick for me", which on a
            # path without a z tile legitimately resolves to "no tile", so
            # it must not override the measured method crossover
            method, method_source = "kernel", "impl/block_z"
        else:
            method, method_source = _tuner.method_for_ex(n)
    if method not in allowed:
        raise ValueError(f"unknown method {method!r} for kind={kind!r} "
                         f"(expected one of {('auto',) + allowed})")
    if schedule == "tri" and method != "kernel":
        raise ValueError(
            f"schedule='tri' is only available for method='kernel' (the "
            f"Pallas upper-triangular pipeline), got method={method!r}; "
            f"pass method='kernel' or drop schedule=")

    # -- neighborhood size (knn only) ---------------------------------------
    if method == "knn":
        if k is None:
            raise ValueError(
                "method='knn' needs k= (neighborhood size, 1 <= k <= n-1); "
                "at k = n-1 the result equals the dense methods exactly")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        k = min(int(k), max(n - 1, 0))
    elif k is not None:
        raise ValueError(
            f"k= is only valid with method='knn' (got method={method!r}); "
            "the dense/pairwise/triplet/kernel paths always rank every "
            "point against every other — drop k=, or pass method='knn'")

    # -- selection stage (knn only) -----------------------------------------
    if method != "knn" and (select is not None or select_block is not None
                            or select_tile is not None):
        raise ValueError(
            "select=/select_block=/select_tile= configure the knn neighbor "
            f"selection stage (got method={method!r}); drop them, or pass "
            "method='knn'")
    if select not in (None, "pallas", "interpret", "jnp", "chunked"):
        raise ValueError(
            f"unknown select {select!r} (expected 'pallas', 'interpret', "
            "'jnp' or 'chunked')")
    if kind == "distance" and select not in (None, "chunked"):
        raise ValueError(
            f"select={select!r} needs kind='features' (the streaming "
            "selection impls consume feature tiles); on a distance matrix "
            "only the row-chunked rung select='chunked' applies")
    if kind == "distance" and (select_block is not None
                               or select_tile is not None):
        raise ValueError(
            "select_block=/select_tile= only apply to kind='features' "
            "(they tile the feature-space selection slabs)")

    # -- mesh sharding (features knn only) ----------------------------------
    if strategy is not None and mesh is None:
        raise ValueError(
            f"strategy={strategy!r} configures the mesh-sharded knn "
            "pipeline; pass mesh= (a jax.sharding.Mesh) alongside it")
    if mesh is not None:
        from . import distributed_knn as _dknn

        if kind != "features" or method != "knn":
            raise ValueError(
                "mesh= shards the fused select->cohere knn pipeline and "
                f"needs kind='features' with method='knn' (got kind={kind!r}"
                f", method={method!r}); drop mesh=, or pass k= to request "
                "the knn method on feature input")
        if batch is not None:
            raise ValueError(
                "mesh= plans run one item at a time (the device mesh is the "
                "parallel axis); drop batch=")
        if strategy is not None and strategy not in _dknn.STRATEGIES:
            raise ValueError(
                f"unknown strategy {strategy!r} (expected one of "
                f"{_dknn.STRATEGIES})")
        axes = tuple(mesh.axis_names)
        if strategy in (None, "auto"):
            strategy = "2d" if len(axes) >= 2 else "ring"
        if strategy == "2d" and len(axes) < 2:
            raise ValueError(
                "strategy='2d' needs a mesh with >= 2 axes (row x column "
                f"split), got axes={axes}; use 'ring' or 'allgather'")

    # -- impl --------------------------------------------------------------
    if method in _IMPL_METHODS:
        impl = impl or _default_kernel_impl(method)
    elif impl is not None:
        # silently dropping an explicit request would let a caller believe
        # it exercised a path it didn't
        raise ValueError(
            f"impl={impl!r} is only configurable for the kernel/fused/knn "
            f"pipelines; method={method!r} has exactly one implementation")

    # -- per-method knob surface -------------------------------------------
    if z_chunk is not None and method != "dense":
        raise ValueError(
            f"z_chunk= only applies to method='dense' (the blocked paths "
            f"stream z by block_z tiles), got method={method!r}; drop "
            f"z_chunk= or pass method='dense'")
    if method == "dense":
        if block_z not in (None, "auto"):
            raise ValueError("block_z= does not apply to method='dense' "
                             "(it has no z tile; use z_chunk=)")
        return PaldPlan(
            kind=kind, method=method, schedule=schedule, impl=None,
            block=None, block_z=None, z_chunk=z_chunk, ties=ties,
            weight=weight,
            metric=metric, normalize=normalize, batch=batch, check=check,
            n=n, d=d, on_error=on_error, method_source=method_source,
            block_source="n/a",
        )
    if method in ("pairwise", "triplet"):
        if block_z not in (None, "auto"):
            raise ValueError(
                f"block_z= does not apply to method={method!r} (the "
                "pure-jnp blocked paths stream the full z axis per block "
                "pair)")
        # block_z="auto" resolves to "no z tile" here — a valid resolution,
        # not a dropped knob; explain() shows block_z=None with no z
        # provenance, and no tuning-cache scan is wasted on it
        block_z = None
    if method == "knn":
        if block_z not in (None, "auto"):
            raise ValueError(
                "block_z= does not apply to method='knn' (the third axis "
                "is the k neighbors themselves); tune block=, the row tile")
        block_z = None

    # -- tiles -------------------------------------------------------------
    block_source = "explicit"
    if block is None:
        # Mosaic tiles depend on n (aligned z tiles, the tri slab's VMEM),
        # so the pallas kernel pipeline resolves them like fused/knn do
        auto = method in ("fused", "knn") or impl == "pallas"
        block = "auto" if auto else 128
        block_source = "default"
    if method == "knn":
        if block == "auto":
            block, _, src = _tuner.resolve_blocks_ex(
                n, "pald_knn", ties=weight, k=k, impl=impl)
            block_source = src
        block = max(min(int(block), max(n, 1)), 1)
        sel_source = "n/a"
        sb = st = None
        if kind == "features":
            # selection-stage tiles resolve once here (pald_topk pass) so
            # the executor never consults the cache and explain() reports
            # the exact slab/tile the fused select->cohere will run
            sb = "auto" if select_block is None else select_block
            st = "auto" if select_tile is None else select_tile
            sel_source = "explicit"
            if sb == "auto" or st == "auto":
                rb, rt, sel_source = _tuner.resolve_blocks_ex(
                    n, "pald_topk", d=d, k=k, impl=(select or impl),
                    p=(int(mesh.devices.size) if mesh is not None else None))
                sb = rb if sb == "auto" else sb
                st = rt if st == "auto" else st
            sb = max(min(int(sb), max(n, 1)), 1)
            st = max(min(int(st), max(n, 1)), 1)
        return PaldPlan(
            kind=kind, method=method, schedule=schedule, impl=impl,
            block=block, block_z=None, z_chunk=None, ties=ties,
            weight=weight,
            metric=metric, normalize=normalize, batch=batch, check=check,
            n=n, d=d, k=k, on_error=on_error, method_source=method_source,
            block_source=block_source, select=select, select_block=sb,
            select_tile=st, select_source=sel_source,
            mesh=mesh, strategy=(strategy if mesh is not None else None),
        )
    if method == "fused":
        # one authority for the fused tile defaults, shared with
        # kernels/ops.pald_fused (tuning.resolve_fused_tiles) — the plan can
        # never drift from what the kernel entry point would compute
        was_auto = block == "auto"
        block, block_z, src = _tuner.resolve_fused_tiles(
            n, d, block, block_z, impl=impl, ties=weight)
        if src is not None:
            # provenance tracks the *block* tile; an explicit block with an
            # auto block_z must not claim the user's tile came from the cache
            block_source = src if was_auto else f"{block_source}; z:{src}"
    elif block == "auto" or block_z == "auto":
        pass_ = "pald_tri" if schedule == "tri" else "pald"
        rb, rbz, src = _tuner.resolve_blocks_ex(n, pass_, ties=weight,
                                                impl=impl)
        block_source = src if block == "auto" else f"{block_source}; z:{src}"
        block = rb if block == "auto" else block
        if method == "kernel" and block_z in (None, "auto"):
            block_z = rbz
    block = int(block)
    block_z = None if block_z is None else int(block_z)

    return PaldPlan(
        kind=kind, method=method, schedule=schedule, impl=impl,
        block=block, block_z=block_z, z_chunk=None, ties=ties,
        weight=weight,
        metric=metric, normalize=normalize, batch=batch, check=check,
        n=n, d=d, on_error=on_error, method_source=method_source,
        block_source=block_source,
    )


def plan_local(
    n: int,
    *,
    impl: str | None = None,
    ties: str | None = None,
    weight=None,
    block: int | str = "auto",
    block_z: int | str = "auto",
    on_error: str = "raise",
) -> PaldPlan:
    """Plan for the rectangular per-device bodies of ``core/distributed``.

    ``n`` is the per-device row extent the tiles are keyed on.  The shard
    bodies consume the plan through ``plan.focus_general`` /
    ``plan.cohesion_general``; ``impl=None`` keeps the kernels' own backend
    default (jnp off-TPU — the vectorized fallback, which is what the
    collectives overlap against).
    """
    weight = _resolve_weight_knob(ties, weight)
    ties = weight.name
    if on_error not in _res.ON_ERROR_MODES:
        raise ValueError(f"unknown on_error {on_error!r} (expected one of "
                         f"{_res.ON_ERROR_MODES})")
    block_source = "explicit"
    if block == "auto" or block_z == "auto":
        rb, rbz, src = _tuner.resolve_blocks_ex(max(int(n), 1), "cohesion",
                                                impl=impl)
        block = rb if block == "auto" else block
        block_z = rbz if block_z == "auto" else block_z
        block_source = src
    return PaldPlan(
        kind="distance", method="kernel", schedule="dense", impl=impl,
        block=int(block), block_z=int(block_z), z_chunk=None, ties=ties,
        weight=weight,
        metric=None, normalize=False, batch=None, check=False,
        n=max(int(n), 1), d=None, on_error=on_error,
        method_source="shard-body", block_source=block_source,
    )


# ---------------------------------------------------------------------------
# built-in executors: the features->materialized-D compositions.  The fused
# path and all distance paths are contributed by their home modules; these
# cells are pure composition, so they live with the registry.
# ---------------------------------------------------------------------------
def _materialize_then(schedule: str):
    def _exec(X, p: PaldPlan):
        from .features import cdist_reference

        D = cdist_reference(X, metric=p.metric)
        return get_executor("distance", p.method, schedule)(D, p)

    return _exec


for _m in DISTANCE_METHODS:
    if _m != "knn":  # features-knn never materializes D; kernels/ops owns it
        register_executor("features", _m, "dense")(_materialize_then("dense"))
register_executor("features", "kernel", "tri")(_materialize_then("tri"))
del _m
