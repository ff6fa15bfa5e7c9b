"""Guarded execution: degradation chains, OOM-aware retries, fault points.

The engine's speed comes from picking tuned variants per problem size —
which means a result can now depend on a persistent JSON cache, on
backend-specific Pallas kernels, and on memory-hungry batched vmaps, any of
which can fail at runtime.  A long sharded run or a serving process must
degrade, not crash: this module is the robustness substrate (DESIGN.md §13)
that every scale-out consumer builds on.

Three public surfaces:

``on_error="raise" | "fallback"`` (a ``pald.plan`` knob)
    ``"raise"`` (default) keeps the exact pre-existing behavior: the first
    executor failure propagates unchanged.  ``"fallback"`` walks a
    registered DEGRADATION CHAIN for the plan's ``(kind, method, schedule)``
    cell — impl degradation (pallas → interpret → jnp) first, then
    method-level degradation onto the blocked/un-blocked jnp paths, then the
    entry-wise numpy reference oracle — re-executing with identical
    ``ties``/``normalize`` semantics at every step.  The knn cells degrade
    across impls and end on the ``select:chunked`` rung — row-chunked
    ``lax.top_k`` selection feeding jnp cohesion — never onto a dense
    method (no other path shares their sparse semantics).

OOM-aware batched execution
    In fallback mode, a ``RESOURCE_EXHAUSTED`` failure of the chunked-vmap
    batch layer retries with a halved ``batch`` (down to 1) before touching
    the chain at all — chunked execution is a pure re-chunking of the same
    computation (bitwise-equal, asserted in test_conformance.py), so this
    degradation never changes values.

Structured degradation events
    Every retry/fallback appends an event dict (cell, cause, fallback used,
    retry count) to the plan, surfaced via ``plan.explain()["degradations"]``
    and a once-per-cause ``warnings.warn(DegradationWarning)`` so a serving
    log shows each failure class exactly once instead of per-request spam.

The FAULT-POINT substrate at the bottom is the injection surface the test
harness (``repro.testing.faults``) arms: named call sites threaded through
the engine dispatch, the kernel entry points and the feature front-end that
are zero-cost no-ops until a test registers a ``FaultRule``.
"""
from __future__ import annotations

import dataclasses
import threading
import warnings
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "ON_ERROR_MODES",
    "DegradationWarning",
    "FallbackExhausted",
    "FallbackUnavailable",
    "FaultRule",
    "Step",
    "arm",
    "disarm",
    "fault_point",
    "is_oom",
    "simulated_oom",
    "chain_for",
    "register_chain",
    "execute_plan",
    "guarded_general",
    "warn_once",
    "reset_warnings",
]

ON_ERROR_MODES = ("raise", "fallback")

# impl preference order of the degradation walk (the issue/DESIGN contract:
# pallas -> interpret -> jnp); entries that cannot run on this backend or
# that already failed are skipped at walk time, not at registration time.
IMPL_ORDER = ("pallas", "interpret", "jnp")


class DegradationWarning(UserWarning):
    """A guarded execution degraded (fallback taken / batch halved)."""


class FallbackExhausted(RuntimeError):
    """Every step of a degradation chain failed.

    Raised only with ``on_error="fallback"``; chained from the ORIGINAL
    executor failure so the root cause stays on the traceback.
    """


class FallbackUnavailable(RuntimeError):
    """A chain step cannot run in this context (e.g. the numpy reference
    oracle under jit/vmap tracing); treated as a failed step, walk
    continues."""


# ---------------------------------------------------------------------------
# OOM detection
# ---------------------------------------------------------------------------
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "out of memory", "Out of memory",
                "OutOfMemory")


def is_oom(exc: BaseException) -> bool:
    """Does this exception look like a memory-exhaustion failure?

    Matched on the message, not the type: XLA surfaces OOM as
    ``XlaRuntimeError: RESOURCE_EXHAUSTED ...`` (a type that cannot be
    constructed portably), host allocators as ``MemoryError`` or
    "out of memory" strings, and the fault harness as ``simulated_oom()``.
    """
    if isinstance(exc, MemoryError):
        return True
    text = f"{type(exc).__name__}: {exc}"
    return any(marker in text for marker in _OOM_MARKERS)


def simulated_oom(detail: str = "simulated") -> RuntimeError:
    """An exception that ``is_oom`` recognizes, for fault injection."""
    return RuntimeError(f"RESOURCE_EXHAUSTED: out of memory ({detail})")


# ---------------------------------------------------------------------------
# once-per-cause warnings
# ---------------------------------------------------------------------------
_WARNED: set = set()
_WARN_LOCK = threading.Lock()


def warn_once(key, message: str) -> None:
    """``warnings.warn(DegradationWarning)`` at most once per ``key``.

    A degraded serving path re-executes the same fallback per request;
    the log should record the failure class once, not once per call.
    """
    with _WARN_LOCK:
        if key in _WARNED:
            return
        _WARNED.add(key)
    warnings.warn(message, DegradationWarning, stacklevel=3)


def reset_warnings() -> None:
    """Forget which causes already warned (test isolation)."""
    with _WARN_LOCK:
        _WARNED.clear()


def _event(*, cell, cause: str, error: BaseException | None,
           fallback: str | None, retries: int, **extra) -> dict:
    evt = {
        "cell": tuple(cell),
        "cause": cause,
        "error": None if error is None else f"{type(error).__name__}: {error}",
        "fallback": fallback,
        "retries": retries,
    }
    evt.update(extra)
    return evt


# ---------------------------------------------------------------------------
# degradation chains
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Step:
    """One rung of a degradation chain.

    ``run(x, plan, batch)`` must re-execute the plan's computation with
    IDENTICAL ties/normalize semantics (degradation may change speed and
    floating-point association, never meaning).  ``batch`` carries the
    possibly-already-halved vmap chunk bound into the step.
    """

    label: str
    run: Callable[[Any, Any, Any], Any]


_CHAINS: dict[tuple, list] = {}  # (kind, method, schedule) -> [Step, ...]


def register_chain(kind: str, method: str, schedule: str,
                   steps: list) -> None:
    """Override the degradation chain for one (kind, method, schedule) cell.

    The default chains (built lazily by ``chain_for``) cover every built-in
    cell; alternative backends that ``register_executor`` new cells register
    their fallback story the same way.
    """
    _CHAINS[(kind, method, schedule)] = list(steps)


def _dispatch_derived(derived_plan, x, batch):
    """Run a derived plan through the engine's uniform batch layer."""
    from repro.core import engine as _engine

    fn = _engine.get_executor(derived_plan.kind, derived_plan.method,
                              derived_plan.schedule)
    return _engine.run_batched(fn, x, derived_plan, batch)


def _impl_step(impl: str) -> Step:
    def run(x, plan, batch):
        fault_point("resilience.step", step=f"impl:{impl}", kind=plan.kind,
                    method=plan.method, schedule=plan.schedule, impl=impl)
        return _dispatch_derived(
            dataclasses.replace(plan, impl=impl, mesh=None, strategy=None),
            x, batch)

    return Step(f"impl:{impl}", run)


def _method_step(method: str) -> Step:
    def run(x, plan, batch):
        fault_point("resilience.step", step=f"method:{method}",
                    kind=plan.kind, method=method, schedule="dense",
                    impl=None)
        block = plan.block if isinstance(plan.block, int) else 128
        derived = dataclasses.replace(
            plan, method=method, schedule="dense", impl=None,
            block=None if method == "dense" else block,
            block_z=None, z_chunk=None,
        )
        return _dispatch_derived(derived, x, batch)

    return Step(f"method:{method}", run)


def _select_step() -> Step:
    """Terminal rung of the knn cells: jnp cohesion with 'chunked'
    selection — unfused per-slab distances reduced by a row-chunked
    ``lax.top_k`` with host syncs between slabs (kernels/ops), the
    smallest machinery that still answers with identical semantics."""
    def run(x, plan, batch):
        fault_point("resilience.step", step="select:chunked", kind=plan.kind,
                    method=plan.method, schedule=plan.schedule, impl="jnp")
        derived = dataclasses.replace(plan, impl="jnp", select="chunked",
                                      mesh=None, strategy=None)
        return _dispatch_derived(derived, x, batch)

    return Step("select:chunked", run)


def _mesh_off_step() -> Step:
    """First rung of a mesh-sharded knn plan: re-enter the single-device
    fused select->cohere path.  The sharded bodies are bitwise-equal to the
    fused kernel by construction, so dropping the mesh degrades locality
    and wall-clock, never values."""
    def run(x, plan, batch):
        fault_point("resilience.step", step="mesh:single-device",
                    kind=plan.kind, method=plan.method,
                    schedule=plan.schedule, impl=plan.impl)
        derived = dataclasses.replace(plan, mesh=None, strategy=None)
        return _dispatch_derived(derived, x, batch)

    return Step("mesh:single-device", run)


def _reference_step() -> Step:
    def run(x, plan, batch):
        fault_point("resilience.step", step="reference", kind=plan.kind,
                    method=plan.method, schedule=plan.schedule, impl=None)
        if isinstance(x, jax.core.Tracer):
            raise FallbackUnavailable(
                "the numpy reference oracle needs concrete values; "
                "unavailable under jit/vmap tracing")
        from repro.core import reference as _reference
        from repro.core.weights import TIE_MODES

        # The numpy oracle only speaks the built-in tie modes.  For any
        # other registered weight functional, the terminal rung is the
        # un-blocked jnp einsum oracle (kernels/ref.py), which consumes
        # the SAME functional the failed executor did — a rescue must
        # never change the contribution algebra mid-request.
        builtin = plan.ties in TIE_MODES

        def one(xi):
            if plan.kind == "features":
                from repro.core.features import cdist_reference

                Di = np.asarray(
                    cdist_reference(jnp.asarray(xi, jnp.float32),
                                    metric=plan.metric))
            else:
                Di = np.asarray(xi)
            if builtin:
                C = _reference.pald_pairwise_reference(
                    Di, ties=plan.ties, normalize=plan.normalize)
            else:
                from repro.kernels import ref as _ref

                Dj = jnp.asarray(Di, jnp.float32)
                U = _ref.focus_ref(Dj, ties=plan.weight)
                C = _ref.cohesion_ref(Dj, _ref.weights_ref(U),
                                      ties=plan.weight)
                if plan.normalize:
                    C = C / max(Dj.shape[0] - 1, 1)
            return np.asarray(C, np.float32)

        xv = np.asarray(x)
        out = one(xv) if xv.ndim == 2 else np.stack([one(xi) for xi in xv])
        return jnp.asarray(out, jnp.float32)

    return Step("reference", run)


def _default_chain(plan) -> list:
    """pallas → interpret → jnp → blocked jnp methods → reference.

    Entries equal to the plan's own (failed) impl are skipped, as is
    ``pallas`` off-TPU (it cannot succeed there, so attempting it would
    only add latency to an already-failing call).  The knn cells walk the
    impls and then end on ``select:chunked`` — the row-chunked
    ``lax.top_k`` selection rung with jnp cohesion — rather than any
    dense method: no other registered path shares their sparse O(n·k²)
    semantics, and silently answering with the exact dense result would
    change cost by orders of magnitude mid-request.
    """
    steps: list[Step] = []
    if getattr(plan, "mesh", None) is not None:
        # a failed mesh cell rescues onto ONE device first — same impl,
        # same tiles, bitwise-identical answer, no collectives in the way
        steps.append(_mesh_off_step())
    if plan.method in ("kernel", "fused", "knn"):
        on_tpu = jax.default_backend() == "tpu"
        for impl in IMPL_ORDER:
            if impl == plan.impl:
                continue
            if impl == "pallas" and not on_tpu:
                continue
            steps.append(_impl_step(impl))
        if plan.method == "kernel":
            steps.append(_method_step("triplet"))
            steps.append(_method_step("dense"))
        elif plan.method == "fused":
            steps.append(_method_step("dense"))
        elif plan.method == "knn":
            if not (plan.impl == "jnp" and plan.select == "chunked"):
                steps.append(_select_step())
    elif plan.method in ("pairwise", "triplet"):
        steps.append(_method_step("dense"))
    if plan.method != "knn":
        steps.append(_reference_step())
    return steps


def chain_for(plan) -> list:
    """The degradation chain for a plan's cell: registered override if one
    exists, else the default built from the cell's method class."""
    key = (plan.kind, plan.method, plan.schedule)
    if key in _CHAINS:
        return list(_CHAINS[key])
    return _default_chain(plan)


# ---------------------------------------------------------------------------
# guarded execution (the on_error="fallback" path of PaldPlan.execute)
# ---------------------------------------------------------------------------
def _oom_floor_note(plan, cell, exc) -> None:
    plan._events.append(_event(
        cell=cell, cause="oom-floor", error=exc, fallback=None, retries=0,
        batch=1))
    warn_once(("oom-floor", cell),
              f"PaLD {cell}: still RESOURCE_EXHAUSTED at the batch retry "
              f"floor (batch=1); walking the degradation chain")


def _run_with_oom_retries(run, x, plan, batch, cell, label):
    """Call ``run(x, batch)``, halving ``batch`` on OOM down to 1.

    Returns (result, batch) so the caller can keep the degraded bound for
    subsequent attempts.  Non-OOM failures (and OOM at the floor, or on
    unbatched input where there is nothing to halve) propagate.
    """
    while True:
        try:
            return run(x, batch), batch
        except Exception as exc:  # noqa: BLE001 — the guard's whole job
            if not is_oom(exc) or x.ndim != 3:
                raise
            current = batch if batch is not None else int(x.shape[0])
            if current <= 1:
                _oom_floor_note(plan, cell, exc)
                raise
            batch = max(current // 2, 1)
            plan._events.append(_event(
                cell=cell, cause="oom", error=exc, fallback=None,
                retries=1, batch=batch))
            warn_once(("oom", cell),
                      f"PaLD {cell}: RESOURCE_EXHAUSTED on the batched "
                      f"call; retrying with batch={batch}")


def execute_plan(plan, x):
    """The fallback-mode execution path behind ``PaldPlan.execute``.

    Primary attempt first (with OOM-aware batch halving), then the
    degradation chain, each step under the same OOM guard.  The first step
    that succeeds records a degradation event and returns; exhaustion
    raises ``FallbackExhausted`` chained from the original failure.
    """
    from repro.core import engine as _engine

    cell = (plan.kind, plan.method, plan.schedule)
    batch = plan.batch

    def primary(xi, b):
        fault_point("engine.execute", kind=plan.kind, method=plan.method,
                    schedule=plan.schedule, impl=plan.impl)
        fn = _engine.get_executor(*cell)
        return _engine.run_batched(fn, xi, plan, b)

    try:
        result, _ = _run_with_oom_retries(primary, x, plan, batch, cell,
                                          "primary")
        return result
    except Exception as exc:  # noqa: BLE001 — the guard's whole job
        original = exc

    attempts: list[tuple[str, BaseException]] = [
        (f"primary({plan.impl or plan.method})", original)]
    for step in chain_for(plan):
        try:
            with jax.profiler.TraceAnnotation("resilience.step"):
                result, batch = _run_with_oom_retries(
                    lambda xi, b, s=step: s.run(xi, plan, b), x, plan,
                    batch, cell, step.label)
        except Exception as step_exc:  # noqa: BLE001
            attempts.append((step.label, step_exc))
            continue
        extra = {}
        if getattr(plan, "mesh", None) is not None:
            # record WHICH mesh cell failed so explain()["degradations"]
            # pins the rescue to a concrete (mesh shape, strategy) pair
            extra["mesh"] = tuple(plan.mesh.devices.shape)
            extra["strategy"] = plan.strategy
        plan._events.append(_event(
            cell=cell, cause="executor-failure", error=original,
            fallback=step.label, retries=len(attempts), **extra))
        warn_once(("fallback", cell, step.label),
                  f"PaLD {cell}: primary executor failed "
                  f"({type(original).__name__}: {original}); degraded to "
                  f"{step.label} — results keep identical "
                  f"ties/normalize semantics")
        return result

    tried = ", ".join(f"{label}: {type(e).__name__}" for label, e in attempts)
    raise FallbackExhausted(
        f"every fallback failed for cell {cell}: primary raised "
        f"{type(original).__name__}: {original}; degradation chain "
        f"attempted [{tried}]") from original


# ---------------------------------------------------------------------------
# guarded rectangular primitives (the distributed shard-body consumer)
# ---------------------------------------------------------------------------
def guarded_general(plan, what: str, call: Callable[[str | None], Any]):
    """Impl-degradation guard for ``plan.focus_general``/``cohesion_general``.

    The shard bodies call the rectangular kernels at trace time, so a
    Pallas lowering/compile failure is catchable here; the walk retries
    ``call`` with each remaining impl of ``IMPL_ORDER``.  The terminal
    reference oracle is NOT in this chain — these calls always run under
    ``shard_map`` tracing, where only traceable impls can answer.
    """
    cell = (plan.kind, plan.method, plan.schedule)
    effective = plan.impl or (
        "pallas" if jax.default_backend() == "tpu" else "jnp")
    try:
        return call(plan.impl)
    except Exception as exc:  # noqa: BLE001 — the guard's whole job
        original = exc
    attempts = [(f"impl:{effective}", original)]
    for impl in IMPL_ORDER:
        if impl == effective:
            continue
        if impl == "pallas" and jax.default_backend() != "tpu":
            continue
        try:
            result = call(impl)
        except Exception as step_exc:  # noqa: BLE001
            attempts.append((f"impl:{impl}", step_exc))
            continue
        plan._events.append(_event(
            cell=cell, cause=f"{what}-failure", error=original,
            fallback=f"impl:{impl}", retries=len(attempts)))
        warn_once((what, cell, impl),
                  f"PaLD shard body {what}: impl {effective!r} failed "
                  f"({type(original).__name__}); degraded to impl={impl!r}")
        return result
    tried = ", ".join(f"{label}: {type(e).__name__}" for label, e in attempts)
    raise FallbackExhausted(
        f"every fallback failed for shard-body {what} on cell {cell}: "
        f"degradation chain attempted [{tried}]") from original


# ---------------------------------------------------------------------------
# fault points (the injection substrate; armed only by repro.testing.faults)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class FaultRule:
    """One armed fault.  Matching is AND over the given criteria:

    ``site``      substring of the fault-point name ("" matches all);
    ``match``     exact equality on context kwargs (e.g. impl="interpret");
    ``pred``      arbitrary predicate over (site=..., **ctx) — e.g. trip
                  only when the batch chunk exceeds a simulated memory cap;
    ``nth``       1-based matching-call index at which tripping starts
                  (nth=3: the first two matching calls pass untouched);
    ``times``     maximum number of trips (None = every matching call).

    ``exc`` is a zero-arg factory so each trip raises a fresh exception.
    """

    exc: Callable[[], BaseException]
    site: str = ""
    match: dict | None = None
    pred: Callable[..., bool] | None = None
    nth: int = 1
    times: int | None = None
    calls: int = 0
    trips: int = 0


_RULES: list[FaultRule] = []
_RULES_LOCK = threading.Lock()


def arm(rule: FaultRule) -> FaultRule:
    with _RULES_LOCK:
        _RULES.append(rule)
    return rule


def disarm(rule: FaultRule) -> None:
    with _RULES_LOCK:
        if rule in _RULES:
            _RULES.remove(rule)


def fault_point(site: str, **ctx) -> None:
    """A named, normally-inert injection site.

    Threaded through the engine dispatch (``engine.execute``,
    ``engine.batch``), every kernel entry point in ``repro.kernels.ops``,
    the feature front-end and each degradation-chain step.  Zero-cost when
    nothing is armed (one falsy check); when a ``FaultRule`` matches, the
    rule's exception is raised exactly as a real failure at that site
    would be.
    """
    if not _RULES:
        return
    with _RULES_LOCK:
        rules = list(_RULES)
    for rule in rules:
        if rule.site and rule.site not in site:
            continue
        if rule.match and any(ctx.get(k) != v for k, v in rule.match.items()):
            continue
        if rule.pred is not None and not rule.pred(site=site, **ctx):
            continue
        rule.calls += 1
        if rule.calls < rule.nth:
            continue
        if rule.times is not None and rule.trips >= rule.times:
            continue
        rule.trips += 1
        raise rule.exc()
