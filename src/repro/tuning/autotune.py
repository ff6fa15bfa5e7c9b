"""Candidate-grid block-size autotuner with a persistent JSON cache.

The paper's headline constant factors come from cache blocking with *tuned*
block sizes, and the optimum moves with n, the pass, and the backend — yet
every kernel entry point used to hard-code ``block=128, block_z=512``.  This
module is the single source of truth instead (DESIGN.md §Tuning):

* a JSON on-disk cache keyed by ``(backend, impl, n, pass)`` holding the
  measured-best ``(block, block_z)`` plus the full timing grid;
* ``resolve_blocks`` — the cheap consumer behind ``block="auto"`` in
  ``core.pald``, ``kernels.ops`` and ``core.distributed``: exact cache hit,
  else nearest-n hit (log-space) for the same key prefix, else a size-aware
  heuristic.  Never measures; always fast enough to call at trace time.
* ``tune`` — the producer: times a candidate grid for one ``(n, pass, impl)``
  cell and records the winner.  Driven by ``benchmarks/hillclimb.py blocks``
  so tuning results persist instead of being printed and forgotten.
* ``tune_methods`` / ``method_for`` — the same pattern one level up:
  measured method crossovers (dense vs triplet vs kernel) replacing the old
  hard-coded ``n <= 256`` heuristic in ``pald.cohesion(method="auto")``.

Cache location: ``$REPRO_TUNE_CACHE`` or ``~/.cache/repro_pald/blocktune.json``.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
import warnings
from typing import Iterable, Sequence

import numpy as np

try:  # POSIX only; the save lock degrades to plain atomic writes without it
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX platform
    fcntl = None

_CACHE_ENV = "REPRO_TUNE_CACHE"
_MEM: dict[str, tuple[float, dict]] = {}  # abspath -> (mtime, data)
_QUARANTINE_WARNED: set[str] = set()  # abspaths that already warned

# passes understood by `tune`; each maps to one kernel-pipeline entry point
PASSES = ("focus", "cohesion", "focus_tri", "cohesion_tri", "pald",
          "pald_tri", "pald_fused", "pald_knn", "pald_topk")


# the three built-in tie modes (mirrors core/weights.TIE_MODES; duplicated
# here because importing repro.core from this module would cycle through
# repro.core.__init__ -> engine -> repro.tuning at import time)
_TIE_MODES = ("drop", "split", "ignore")


def _pass_key(pass_: str, d: int | None, ties=None,
              k: int | None = None, p: int | None = None) -> str:
    """Feature-fused cells depend on the feature dimension too: the optimal
    tile moves with d (the in-register distance compute scales with it), so
    d joins the cache key as a ``:d<d>`` suffix on the pass name.  The
    sparse knn pass depends on the neighborhood size the same way (the
    (block, k, k) tile scales with k^2), keyed ``:k<k>``.  Non-default
    weight functionals change the tile bodies (extra equality masks for
    'split', the index-tiebreak input for 'ignore', transcendentals for the
    smooth families), so they get their own cells: the built-in tie modes
    keep their legacy ``:t-<mode>`` suffix (existing caches stay valid, and
    the default 'drop' keeps the bare key), every other functional — by
    registered name or instance — gets ``:w-<name>`` so autotuned tiles
    never leak across functionals.

    The selection pass is keyed ``pald_topk:k<k>:d<d>`` — k first (it
    bounds the best-list/network width, the stronger lever) — and takes
    no ties suffix: neighbor selection is weight-independent.  Mesh-sharded
    selection appends ``:p<p>`` (the device count): the optimal row slab
    shrinks with the per-shard row count, so tiles tuned on one mesh shape
    never leak onto another; a ``:p`` miss falls back to the single-device
    cell of the same (k, d) before the size heuristic."""
    if pass_ == "pald_topk":
        if k is not None:
            pass_ = f"{pass_}:k{int(k)}"
        if d is not None:
            pass_ = f"{pass_}:d{int(d)}"
        if p is not None and int(p) > 1:
            pass_ = f"{pass_}:p{int(p)}"
        return pass_
    if d is not None:
        pass_ = f"{pass_}:d{int(d)}"
    if k is not None:
        pass_ = f"{pass_}:k{int(k)}"
    name = getattr(ties, "name", ties)
    if name and name != "drop":
        tag = "t-" if name in _TIE_MODES else "w-"
        pass_ = f"{pass_}:{tag}{name}"
    return pass_


def cache_path(path: str | None = None) -> str:
    if path:
        return path
    env = os.environ.get(_CACHE_ENV)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro_pald",
                        "blocktune.json")


def _key(backend: str, impl: str, n: int, pass_: str) -> str:
    return f"{backend}|{impl}|{int(n)}|{pass_}"


def _split_key(key: str) -> tuple[str, str, int, str]:
    backend, impl, n, pass_ = key.split("|")
    return backend, impl, int(n), pass_


def _quarantine(p: str, exc: Exception) -> str | None:
    """Move a corrupt cache aside to ``<path>.corrupt-<ts>`` and warn once.

    A truncated/garbled JSON must not be silently treated as an empty
    cache forever — the corrupt bytes are preserved for inspection, the
    path starts fresh, and the one warning names both."""
    dest = f"{p}.corrupt-{time.strftime('%Y%m%dT%H%M%S')}"
    try:
        os.replace(p, dest)
    except OSError:  # racing writer already replaced it; nothing to move
        dest = None
    if p not in _QUARANTINE_WARNED:
        _QUARANTINE_WARNED.add(p)
        where = f"; corrupt file preserved at {dest}" if dest else ""
        warnings.warn(
            f"tuning cache {p} is corrupt ({type(exc).__name__}: {exc}); "
            f"starting a fresh cache{where}", stacklevel=3)
    return dest


def _read_cache_file(p: str) -> dict:
    """One fresh read of the cache file (no mtime memo): {} when missing,
    quarantine + {} when corrupt."""
    try:
        with open(p) as f:
            data = json.load(f)
        if not isinstance(data, dict):
            raise ValueError(
                f"expected a JSON object of records, got "
                f"{type(data).__name__}")
    except OSError:
        return {}
    except ValueError as exc:
        _quarantine(p, exc)
        return {}
    return data


def load_cache(path: str | None = None) -> dict:
    p = os.path.abspath(cache_path(path))
    try:
        mtime = os.path.getmtime(p)
    except OSError:
        return {}
    hit = _MEM.get(p)
    if hit and hit[0] == mtime:
        return hit[1]
    data = _read_cache_file(p)
    try:  # the quarantine may have moved the file away
        _MEM[p] = (os.path.getmtime(p), data)
    except OSError:
        _MEM.pop(p, None)
    return data


@contextlib.contextmanager
def _save_lock(p: str, timeout: float):
    """Exclusive advisory lock on ``<path>.lock`` for the save RMW cycle.

    Yields True when the lock is held.  On a non-POSIX platform (no fcntl)
    or when ``timeout`` expires (a peer died holding the lock, or is
    tuning a pathologically slow cell) the save proceeds UNLOCKED with a
    warning — losing a peer's concurrent entry beats deadlocking the
    tuner.  The sidecar (never the data file) is locked so the atomic
    ``os.replace`` of the data never invalidates anyone's lock fd."""
    if fcntl is None:
        yield False
        return
    with open(p + ".lock", "w") as lf:
        deadline = time.monotonic() + timeout
        while True:
            try:
                fcntl.flock(lf, fcntl.LOCK_EX | fcntl.LOCK_NB)
                break
            except OSError:
                if time.monotonic() >= deadline:
                    warnings.warn(
                        f"could not lock tuning cache {p} within {timeout}s; "
                        "saving without the lock (a concurrent writer's "
                        "entry may be lost)", stacklevel=4)
                    yield False
                    return
                time.sleep(0.02)
        try:
            yield True
        finally:
            fcntl.flock(lf, fcntl.LOCK_UN)


def save_entry(backend: str, impl: str, n: int, pass_: str, record: dict,
               path: str | None = None, *, lock_timeout: float = 10.0) -> str:
    """Merge one record into the cache (atomic write); returns the key.

    The read-modify-write cycle runs under an ``fcntl`` lock and re-reads
    the file fresh inside it, so two concurrent tuners (e.g. parallel
    ``hillclimb`` processes) merge instead of losing each other's rows.
    """
    p = os.path.abspath(cache_path(path))
    key = _key(backend, impl, n, pass_)
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    with _save_lock(p, lock_timeout):
        data = _read_cache_file(p)  # fresh under the lock: merge, not clobber
        data[key] = record
        tmp = f"{p}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, p)
    _MEM[p] = (os.path.getmtime(p), data)
    return key


def lookup(backend: str, impl: str, n: int, pass_: str,
           path: str | None = None) -> dict | None:
    return load_cache(path).get(_key(backend, impl, n, pass_))


def lookup_nearest(backend: str, impl: str, n: int, pass_: str,
                   path: str | None = None) -> tuple[int, dict] | None:
    """Nearest-n cache entry (log-space) for the same (backend, impl, pass)."""
    best = None
    for key, rec in load_cache(path).items():
        try:
            b, i, kn, kp = _split_key(key)
        except ValueError:
            continue
        if (b, i, kp) != (backend, impl, pass_) or kn <= 0:
            continue
        dist = abs(np.log(kn) - np.log(max(n, 1)))
        if best is None or dist < best[0]:
            best = (dist, kn, rec)
    if best is None:
        return None
    return best[1], best[2]


def _default_backend() -> str:
    import jax
    return jax.default_backend()


def _default_impl(backend: str) -> str:
    return "pallas" if backend == "tpu" else "jnp"


def _valid_tile(v) -> bool:
    """A usable cached tile: an integral number > 0 (bool excluded).  A
    hand-edited or bit-flipped cache must degrade to defaults at lookup,
    never raise mid-``plan()``."""
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return False
    return float(v) == int(v) and int(v) > 0


def _default_blocks(n: int, pass_: str,
                    impl: str | None = None) -> tuple[int, int]:
    """Size-aware fallback when nothing is cached (the old constants,
    clamped).  cohesion_tri keeps its whole (n, block_z) column slab in
    VMEM, so its z tile shrinks as n grows (~6 MiB budget).  The
    selection pass (pald_topk) defaults to the PR 5 contract — 1024-row
    slabs, tile = n i.e. direct full-width top_k (the tile-min prefilter
    must be opted in or measured in; on clustered data direct wins).

    The ``pallas`` impl gets tiles that Mosaic compiles into the 16 MiB
    of scoped VMEM a TPU kernel may use: a (128, 256) selection tile
    (the bitonic network unrolls over the tile's lanes), 64-row knn
    blocks (each holds a (64, 128, 128) gathered cube, lane-padded), z
    tiles in whole 128-lane columns, and a tri z tile whose
    double-buffered (n, block_z) slab stays within 8 MiB."""
    pallas = impl == "pallas"
    if pass_ == "pald_topk":
        if pallas:
            return max(min(128, n), 1), max(min(256, n), 1)
        return max(min(1024, n), 1), max(n, 1)
    block = min(64 if pallas and pass_ == "pald_knn" else 128, n)
    # a Mosaic block spans whole 128-lane columns (or the whole padded axis)
    block_z = min(512, -(-n // 128) * 128 if pallas else n)
    if pass_ in ("cohesion_tri", "pald_tri") and pallas and n > 0:
        block_z = min(block_z, max((8 << 20) // (8 * n) // 128 * 128, 128))
    elif pass_ == "cohesion_tri" and n > 0:
        block_z = min(block_z, max((6 << 20) // (4 * n), 8))
    return max(block, 1), max(block_z, 1)


def resolve_blocks_ex(
    n: int,
    pass_: str,
    *,
    impl: str | None = None,
    backend: str | None = None,
    path: str | None = None,
    d: int | None = None,
    ties=None,
    k: int | None = None,
    p: int | None = None,
) -> tuple[int, int, str]:
    """(block, block_z, source) for one pass at size n.

    ``source`` records the provenance for ``PaldPlan.explain()``:
    ``"cache:<key>"`` exact hit, ``"nearest:<key>@n=<kn>"`` nearest-n hit
    (log-space), ``"default"`` size-aware heuristic (cold cache).

    ``d`` (feature dimension) extends the key for the fused pass — tiles
    tuned at one d are not reused for another; ``k`` does the same for the
    sparse knn pass (``pald_knn:k<k>``).  ``ties`` (a mode string, a
    registered functional name, or a ``WeightFunctional`` instance) extends
    the key for every non-default functional (their tile bodies differ); a
    miss on such a cell falls back to the strict cell's entry before the
    size heuristic, since the optima rarely move much.  ``p`` (mesh device
    count) extends the selection-pass key (``pald_topk:...:p<p>``); a miss
    on the mesh cell falls back to the single-device cell the same way."""
    backend = backend or _default_backend()
    impl = impl or _default_impl(backend)
    base = _pass_key(pass_, d, k=k)
    keyed = _pass_key(pass_, d, ties, k=k)
    meshed = _pass_key(pass_, d, ties, k=k, p=p)
    quarantined = None
    # mesh cell first, then the tie-mode cell, then strict single-device
    for pk in dict.fromkeys((meshed, keyed, base)):
        rec = lookup(backend, impl, n, pk, path)
        key = _key(backend, impl, n, pk)
        source = f"cache:{key}"
        if rec is None:
            near = lookup_nearest(backend, impl, n, pk, path)
            if near:
                rec = near[1]
                key = _key(backend, impl, near[0], pk)
                source = f"nearest:{key}"
        if isinstance(rec, dict) and "block" in rec:
            bz_rec = rec.get("block_z", rec["block"])
            if _valid_tile(rec["block"]) and _valid_tile(bz_rec):
                return (max(min(int(rec["block"]), n), 1),
                        max(min(int(bz_rec), n), 1),
                        source)
            # wrong-typed / non-positive tiles: fall through to defaults
            # with the quarantine provenance instead of raising mid-plan()
            quarantined = quarantined or f"quarantined:{key}"
        elif rec is not None:
            quarantined = quarantined or f"quarantined:{key}"
    b, bz = _default_blocks(n, pass_, impl)
    return b, bz, quarantined or "default"


def resolve_blocks(
    n: int,
    pass_: str,
    *,
    impl: str | None = None,
    backend: str | None = None,
    path: str | None = None,
    d: int | None = None,
    ties=None,
    k: int | None = None,
    p: int | None = None,
) -> tuple[int, int]:
    """(block, block_z) for one pass at size n: cached, nearest, or default.

    Thin wrapper over ``resolve_blocks_ex`` (which also reports the
    provenance of the answer)."""
    b, bz, _ = resolve_blocks_ex(n, pass_, impl=impl, backend=backend,
                                 path=path, d=d, ties=ties, k=k, p=p)
    return b, bz


def resolve_fused_tiles(
    n: int,
    d: int,
    block,
    block_z,
    *,
    impl: str | None = None,
    backend: str | None = None,
    ties=None,
    path: str | None = None,
) -> tuple[int, int, str | None]:
    """The fused pipeline's tile defaults, in exactly one place.

    ``block_z=None`` rides along with ``block`` ("auto" together, else the
    512 legacy default); "auto" resolves under the ``pald_fused`` pass keyed
    by (n, d, ties); both tiles clamp to n.  Shared by ``engine.plan`` and
    ``kernels.ops.pald_fused`` so the resolved plan can never drift from
    what the kernel entry point would have computed itself.

    Returns (block, block_z, source) — ``source`` is the cache provenance
    string when any "auto" was resolved, else None (fully explicit tiles).
    """
    if block_z is None:
        block_z = "auto" if block == "auto" else 512
    source = None
    if block == "auto" or block_z == "auto":
        rb, rbz, source = resolve_blocks_ex(
            n, "pald_fused", impl=impl, backend=backend, d=d, ties=ties,
            path=path)
        block = rb if block == "auto" else block
        block_z = rbz if block_z == "auto" else block_z
    return min(int(block), n), min(int(block_z), n), source


# ---------------------------------------------------------------------------
# measurement (producer side)
# ---------------------------------------------------------------------------
def time_fn(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds of fn(*args) with block_until_ready.

    The single timing discipline shared by the tuner and the benchmark
    suite (``benchmarks.common`` re-exports this)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def random_distance_matrix(n: int, seed: int = 0, dim: int = 8) -> np.ndarray:
    """Euclidean distances of gaussian points (tie-free w.h.p.)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, dim)).astype(np.float32)
    D = np.sqrt(((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)).astype(np.float32)
    np.fill_diagonal(D, 0.0)
    return D


def random_features(n: int, d: int = 8, seed: int = 0) -> np.ndarray:
    """Gaussian feature matrix (the fused pass's measurement input)."""
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32)


def _synthetic_inputs(n: int, seed: int = 0, with_weights: bool = False,
                      d: int = 8, with_distances: bool = True):
    """(D, W, X) measurement inputs; W only when the pass consumes it (built
    with the chunked kernel pipeline, never the O(n^3)-memory reference).
    ``with_distances=False`` (the fused pass) skips the O(n^2) D entirely —
    materializing it is exactly what that pass exists to avoid."""
    import jax.numpy as jnp
    X = jnp.asarray(random_features(n, d, seed))
    if not with_distances:
        return None, None, X
    D = jnp.asarray(random_distance_matrix(n, seed, dim=d), jnp.float32)
    W = None
    if with_weights:
        from repro.kernels import ops, ref
        W = ref.weights_ref(ops.focus(D, impl=None if ops.on_tpu() else "jnp"))
    return D, W, X


def _runner(pass_: str, D, W, X, block: int, block_z: int, impl: str,
            ties="drop", k: int | None = None, p: int | None = None):
    from repro.kernels import ops
    if pass_ == "pald_knn":
        return ops.pald_knn(D, k=k or 16, block=block, impl=impl,
                            ties=ties)[1]
    if pass_ == "pald_topk":
        if p is not None and p > 1:
            # mesh cell: time the sharded select->cohere body itself on a
            # p-device row shard — block/tile mean exactly what the
            # pald_knn_sharded consumer passes them as, so the argmin is
            # measured where it will be spent
            from repro.core import distributed_knn as dknn
            from repro.launch import mesh as meshlib
            m = meshlib.make_test_mesh((p,), ("data",))
            return dknn.pald_knn_sharded(X, m, k=k or 16, block=block,
                                         tile=block_z)[1]
        # block = rows per slab, block_z = tile-min prefilter width
        # (>= n means direct); candidates time the full selection entry
        return ops.topk_select(X, k or 16, impl=impl, block=block,
                               tile=block_z).distances
    if pass_ == "focus":
        return ops.focus_general(D, D, D, block=block, block_z=block_z,
                                 impl=impl, ties=ties)
    if pass_ == "focus_tri":
        return ops.focus(D, block=block, block_z=block_z, impl=impl,
                         schedule="tri", ties=ties)
    if pass_ == "cohesion":
        return ops.cohesion_from_weights(D, W, block=block, block_z=block_z,
                                         impl=impl, ties=ties)
    if pass_ == "cohesion_tri":
        return ops.cohesion_from_weights(D, W, block=block, block_z=block_z,
                                         impl=impl, schedule="tri", ties=ties)
    if pass_ == "pald":
        return ops.pald(D, block=block, block_z=block_z, impl=impl, ties=ties)
    if pass_ == "pald_tri":
        return ops.pald_tri(D, block=block, block_z=block_z, impl=impl,
                            ties=ties)
    if pass_ == "pald_fused":
        return ops.pald_fused(X, block=block, block_z=block_z, impl=impl,
                              ties=ties)
    raise ValueError(f"unknown pass {pass_!r} (expected one of {PASSES})")


def tune(
    n: int,
    pass_: str,
    *,
    impl: str | None = None,
    backend: str | None = None,
    blocks: Iterable[int] = (32, 64, 128, 256, 512),
    blocks_z: Iterable[int] = (128, 256, 512, 1024),
    path: str | None = None,
    save: bool = True,
    seed: int = 0,
    iters: int = 3,
    d: int | None = None,
    ties="drop",
    k: int | None = None,
    p: int | None = None,
    time_budget: float | None = None,
) -> dict:
    """Measure the candidate grid for one (n, pass, impl) cell and record the
    argmin.  Returns the record that was (or would be) cached.

    For ``pass_="pald_fused"`` the feature dimension ``d`` (default 8) joins
    the cache key — the fused tiles trade in-register distance compute
    against revisit traffic, and that tradeoff moves with d.  For
    ``pass_="pald_knn"`` the neighborhood size ``k`` (default 16) joins it
    the same way (``pald_knn:k<k>``); that pass has no z tile, so only the
    row-block axis of the grid is swept.  Non-default ``ties`` modes are
    keyed separately too (their tile bodies differ).

    ``pass_="pald_topk"`` (streaming neighbor selection) is keyed
    ``pald_topk:k<k>:d<d>`` with no ties suffix (selection is
    weight-independent); its grid sweeps the selection row slab
    (``blocks``) against the tile-min prefilter width (``blocks_z``,
    where a candidate >= n means the direct full-width top_k) — the
    prefilter-vs-direct crossover is data- and k-dependent, which is
    exactly why it is measured, not hardcoded.  With ``p`` > 1 the cell
    is the MESH cell (key gains ``:p<p>``): candidates time the sharded
    select->cohere body on a p-device row shard, so the cached
    (block, tile) is measured exactly where ``pald_knn_sharded``'s
    ``block="auto"`` will spend it; requires p forced/real devices.

    The sweep is guarded per candidate: a crashing candidate records a
    ``{"failed": True, "error": ...}`` row and the grid continues; once
    ``time_budget`` (wall seconds for the whole sweep, checked between
    candidates — a single in-flight measurement cannot be preempted)
    is exceeded, remaining candidates record ``{"skipped": "over-budget"}``
    rows.  The argmin is taken over the successful rows only; if every
    candidate failed, RuntimeError (nothing worth caching)."""
    backend = backend or _default_backend()
    impl = impl or _default_impl(backend)
    if p is not None and p > 1:
        if pass_ != "pald_topk":
            raise ValueError(
                f"p= (mesh device count) only keys the selection pass "
                f"(pald_topk), not {pass_!r}")
        import jax
        if p > len(jax.devices()):
            raise RuntimeError(
                f"tuning the p={p} mesh cell needs {p} devices, have "
                f"{len(jax.devices())} (force host devices via "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={p})")
    if pass_ in ("pald_fused", "pald_topk") and d is None:
        d = 8
    if pass_ == "pald_knn":
        k = k or 16
        blocks_z = (0,)  # no z tile: don't re-time identical cells
    if pass_ == "pald_topk":
        # k-dependent tiles: the row-slab grid scales with the slab cost,
        # blocks_z doubles as the tile-min prefilter width (n = direct)
        k = k or 16
        blocks = tuple(blocks) if tuple(blocks) != (32, 64, 128, 256, 512) \
            else (256, 512, 1024, 2048)
        blocks_z = tuple(blocks_z) if tuple(blocks_z) != (128, 256, 512, 1024) \
            else (32, 64, 128, n)
    D, W, X = _synthetic_inputs(
        n, seed, with_weights=pass_ in ("cohesion", "cohesion_tri"),
        d=d if d is not None else 8,
        with_distances=pass_ not in ("pald_fused", "pald_topk"),
    )
    rows = []
    t0 = time.monotonic()
    over_budget = False
    for b in sorted({min(b, n) for b in blocks}):
        for bz in sorted({min(z, n) for z in blocks_z}):
            if over_budget:
                rows.append({"block": b, "block_z": bz,
                             "skipped": "over-budget"})
                continue
            try:
                t = time_fn(
                    lambda: _runner(pass_, D, W, X, b, bz, impl, ties, k, p),
                    iters=iters)
            except Exception as exc:  # noqa: BLE001 - one bad candidate
                rows.append({"block": b, "block_z": bz, "failed": True,
                             "error": f"{type(exc).__name__}: {exc}"})
            else:
                rows.append({"block": b, "block_z": bz,
                             "seconds": round(t, 6)})
            if time_budget is not None and time.monotonic() - t0 > time_budget:
                over_budget = True
    ok = [r for r in rows if "seconds" in r]
    if not ok:
        raise RuntimeError(
            f"every candidate failed for (n={n}, pass={pass_!r}, "
            f"impl={impl!r}); first error: "
            f"{next(r['error'] for r in rows if r.get('failed'))}")
    best = min(ok, key=lambda r: r["seconds"])
    record = {
        "block": best["block"],
        "block_z": best["block_z"],
        "seconds": best["seconds"],
        "grid": rows,
        "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if save:
        save_entry(backend, impl, n,
                   _pass_key(pass_,
                             d if pass_ in ("pald_fused", "pald_topk")
                             else None,
                             None if pass_ == "pald_topk" else ties,
                             k=k if pass_ in ("pald_knn", "pald_topk")
                             else None,
                             p=p if pass_ == "pald_topk" else None),
                   record, path)
    return record


# ---------------------------------------------------------------------------
# method crossovers (dense / pairwise / triplet / kernel schedules)
# ---------------------------------------------------------------------------
_METHOD_IMPL = "-"  # methods span impls; keyed under a fixed placeholder


def tune_methods(
    ns: Sequence[int] = (64, 128, 256, 512, 1024),
    methods: Sequence[str] = ("dense", "pairwise", "triplet"),
    *,
    backend: str | None = None,
    path: str | None = None,
    save: bool = True,
    iters: int = 3,
) -> list[dict]:
    """Measure pald.cohesion per method across n; record the per-n winner so
    method="auto" uses observed crossovers instead of a magic constant."""
    from repro.core import pald
    backend = backend or _default_backend()
    out = []
    for n in ns:
        D, _, _X = _synthetic_inputs(n)
        timings, failed = {}, {}
        for m in methods:
            try:
                timings[m] = round(
                    time_fn(lambda: pald.cohesion(D, method=m), iters=iters),
                    6)
            except Exception as exc:  # noqa: BLE001 - one bad method
                failed[m] = f"{type(exc).__name__}: {exc}"
        if not timings:
            raise RuntimeError(
                f"every method failed at n={n}: {failed}")
        best = min(timings, key=timings.get)
        record = {"method": best, "timings": timings,
                  "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%S")}
        if failed:
            record["failed"] = failed
        if save:
            save_entry(backend, _METHOD_IMPL, n, "method", record, path)
        out.append({"n": n, **record})
    return out


def method_for_ex(n: int, *, backend: str | None = None,
                  path: str | None = None) -> tuple[str, str]:
    """(method, source) at size n — the provenance-reporting sibling of
    ``method_for`` (source: "cache:<key>" / "nearest:<key>@..." /
    "heuristic")."""
    backend = backend or _default_backend()
    rec = lookup(backend, _METHOD_IMPL, n, "method", path)
    key = _key(backend, _METHOD_IMPL, n, "method")
    source = f"cache:{key}"
    if rec is None:
        near = lookup_nearest(backend, _METHOD_IMPL, n, "method", path)
        if near:
            rec = near[1]
            key = _key(backend, _METHOD_IMPL, near[0], "method")
            source = f"nearest:{key}"
    fallback = "dense" if n <= 256 else "triplet"
    if rec is None:
        return fallback, "heuristic"
    # auto-selectable methods only: an edited/corrupted record must not
    # make plan() pick knn (needs k=) or an unknown string — fall to the
    # heuristic with quarantine provenance instead of raising mid-plan()
    m = rec.get("method") if isinstance(rec, dict) else None
    if m in ("dense", "pairwise", "triplet", "kernel"):
        return str(m), source
    return fallback, f"quarantined:{key}"


def method_for(n: int, *, backend: str | None = None,
               path: str | None = None) -> str:
    """Best cohesion method at size n: measured crossover if available,
    else the seed heuristic (dense small, triplet large)."""
    return method_for_ex(n, backend=backend, path=path)[0]
