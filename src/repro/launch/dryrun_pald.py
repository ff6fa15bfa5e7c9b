import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Production-mesh dry-run of the paper's own workload: distributed PaLD.

Lowers + compiles ``pald_distributed`` for n up to 10^5 points on the
single-pod (16,16) and multi-pod (2,16,16) meshes, per strategy, and
derives the roofline terms.  PaLD ops are comparisons+FMAs on the VPU, not
MXU matmuls, so the compute term uses the VPU-op peak; the collective term
is where the strategies differ (this is the paper's scalability story at
pod scale).

    python -m repro.launch.dryrun_pald --n 100000 --mesh both
"""
import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed, engine
from repro.launch import hlo_analysis, mesh as meshlib

# v5e VPU: 8 lanes x 128 sublanes x 4 ALUs x ~0.94 GHz ~= 3.85e12 op/s fp32.
VPU_PEAK = 3.85e12


def pald_ops(n: int) -> float:
    """Branch-free dense-pairwise op count (cmp+select+fma), DESIGN.md §7:
    pass1 2 cmp + 1 or + 1 add = 4, pass2 2 cmp + 1 and + 2 fma = 5 per
    (pair, z) -> ~9 n^3 ops over the full cube (we do n^3, not n^3/2,
    in the regular dense form)."""
    return 9.0 * n ** 3


def run_cell(n: int, multi_pod: bool, strategy: str, *, dtype=jnp.float32,
             verbose=True) -> dict:
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cell = {"workload": f"pald-n{n}", "strategy": strategy,
            "dtype": jnp.dtype(dtype).name,
            "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips}

    axis_names = list(mesh.axis_names)
    row_axes = tuple(a for a in axis_names if a != axis_names[-1])
    col_axis = axis_names[-1]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    if strategy in ("allgather", "ring"):
        spec_in = P(tuple(axis_names), None)
        lp = engine.plan_local(max(n // chips, 1), impl="jnp")
        body = functools.partial(
            distributed._allgather_body if strategy == "allgather"
            else distributed._ring_body,
            axis=tuple(axis_names), n_valid=None, plan=lp,
            **({"p": chips} if strategy == "ring" else {}),
        )
        out_spec = spec_in
    else:
        spec_in = P(row_axes, col_axis)
        pr = 1
        for a in row_axes:
            pr *= sizes[a]
        lp = engine.plan_local(max(n // pr, 1), impl="jnp")
        body = functools.partial(
            distributed._2d_body, row_axes=row_axes, col_axis=col_axis,
            stream_axis="pod" if (strategy == "2d+stream" and multi_pod) else None,
            n_valid=None, mesh_shape=sizes, plan=lp,
        )
        out_spec = spec_in

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=spec_in, out_specs=out_spec,
        check_vma=False,
    ))
    D = jax.ShapeDtypeStruct((n, n), dtype,
                             sharding=NamedSharding(mesh, spec_in))
    t0 = time.time()
    lowered = fn.lower(D)
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    coll = hlo_analysis.collective_stats(compiled.as_text())
    mem = compiled.memory_analysis()

    # the ring / 2d z-stream loops are fori_loops: bodies counted once.
    # scale the under-counted flops/bytes by the trip count
    trips = 1
    if strategy == "ring":
        trips = chips
    elif strategy == "2d+stream" and multi_pod:
        trips = sizes["pod"]
    flops = float(cost.get("flops", 0.0)) * trips
    byts = float(cost.get("bytes accessed", 0.0)) * trips
    collb = float(coll.total_traffic) * trips

    t_comp = pald_ops(n) / chips / VPU_PEAK
    terms = {
        "compute_s": t_comp,
        "memory_s": byts / hlo_analysis.HBM_BW,
        "collective_s": collb / hlo_analysis.ICI_BW,
    }
    terms["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k]
    ).removesuffix("_s")
    cell.update(
        status="ok",
        compile_s=round(t_compile, 2),
        hlo_flops_per_chip=flops,
        hlo_bytes_per_chip=byts,
        coll_bytes_per_chip=collb,
        pald_ops_per_chip=pald_ops(n) / chips,
        memory_analysis={
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "temp_size_in_bytes")
            if mem is not None and getattr(mem, k, None) is not None
        },
        roofline=terms,
        collectives=coll.as_dict(),
    )
    if verbose:
        ma = cell["memory_analysis"]
        tot = (ma.get("temp_size_in_bytes", 0) + ma.get("argument_size_in_bytes", 0)) / 2**30
        print(f"  ok compile {t_compile:5.1f}s  bytes/dev {tot:6.2f} GiB  "
              f"coll {collb/2**20:,.0f} MiB  compute {t_comp*1e3:.1f} ms  "
              f"coll_t {terms['collective_s']*1e3:.1f} ms  "
              f"bottleneck {terms['bottleneck']}")
    return cell


def knn_pald_ops(n: int, k: int) -> float:
    """Sharded-knn op count: selection scores every (row, candidate, dim)
    triple (~3 ops: diff, fma, compare-amortized) and the sparse cohesion
    runs the same 9-op inner loop as the dense form but over (k+1)-cliques
    only — O(n·k²) instead of O(n³)."""
    return 3.0 * n * n + 9.0 * n * (k + 1) ** 2


def knn_shard_estimate(n: int, d: int, k: int, *, strategy: str,
                       pr: int, pc: int, dtype_bytes: int = 4) -> dict:
    """Cost model for one mesh-sharded knn plan cell (no compile needed).

    Communication comes straight from ``distributed_knn.comm_estimate`` —
    every strategy moves O(n·d) feature words per device-round, never the
    O(n²) distance matrix.  Compute splits into the selection term
    (n²·d/p distance ops) and the sparse cohesion term (n·k²/p), both on
    the VPU.  Importable by tests: ``test_distributed.py`` asserts the
    comm term here matches the distributed_knn docstring's n·d claim.
    """
    from repro.core import distributed_knn as dknn

    p = pr * pc
    comm = dknn.comm_estimate(strategy, n=n, d=d, k=k, p=p, pr=pr, pc=pc)
    sel_ops = 3.0 * n * n * d / p
    coh_ops = 9.0 * n * (k + 1) ** 2 / p
    coll_bytes = comm["per_device_words"] * dtype_bytes
    terms = {
        "compute_s": (sel_ops + coh_ops) / VPU_PEAK,
        "collective_s": coll_bytes / hlo_analysis.ICI_BW,
    }
    terms["bottleneck"] = max(
        ("compute_s", "collective_s"), key=lambda kk: terms[kk]
    ).removesuffix("_s")
    return {
        "workload": f"pald-knn-n{n}-k{k}", "strategy": comm["strategy"],
        "mesh": f"{pr}x{pc}", "chips": p, "status": "ok",
        "selection_ops_per_chip": sel_ops,
        "cohesion_ops_per_chip": coh_ops,
        "comm": comm,
        "coll_bytes_per_chip": coll_bytes,
        "roofline": terms,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=102400)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--strategies", default="allgather,ring,2d,2d+stream")
    ap.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    ap.add_argument("--out", default="benchmarks/dryrun_out_pald")
    ap.add_argument("--knn-k", type=int, default=None,
                    help="emit mesh-sharded knn plan estimates for this k "
                         "instead of compiling the dense bodies")
    ap.add_argument("--knn-d", type=int, default=64,
                    help="feature dim for the knn estimates")
    args = ap.parse_args()
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    if args.knn_k is not None:
        for multi in meshes:
            pr, pc = (32, 16) if multi else (16, 16)
            for strat in args.strategies.split(","):
                if strat == "2d+stream":
                    continue
                tag = (f"paldknn{args.n}k{args.knn_k}__{strat}"
                       f"__{'multi' if multi else 'single'}")
                print(f"[dryrun-pald] {tag}")
                cell = knn_shard_estimate(
                    args.n, args.knn_d, args.knn_k, strategy=strat,
                    pr=pr, pc=pc)
                t = cell["roofline"]
                print(f"  est compute {t['compute_s']*1e3:.2f} ms  "
                      f"coll {cell['coll_bytes_per_chip']/2**20:,.1f} MiB  "
                      f"coll_t {t['collective_s']*1e3:.2f} ms  "
                      f"bottleneck {t['bottleneck']}")
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(cell, f, indent=1)
        print("[dryrun-pald] done, 0 failures")
        raise SystemExit(0)
    for multi in meshes:
        for strat in args.strategies.split(","):
            if strat == "2d+stream" and not multi:
                continue
            tag = (f"pald{args.n}__{strat}__{'multi' if multi else 'single'}"
                   + ("__bf16" if args.dtype == "bfloat16" else ""))
            print(f"[dryrun-pald] {tag}")
            try:
                cell = run_cell(args.n, multi, strat, dtype=dtype)
            except Exception:
                failures += 1
                cell = {"workload": tag, "status": "error",
                        "traceback": traceback.format_exc(limit=12)}
                print(cell["traceback"])
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(cell, f, indent=1)
    print(f"[dryrun-pald] done, {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
