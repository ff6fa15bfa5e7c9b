"""Paper Appendix C analogue: PaLD on graph shortest-path distances.

The paper runs the OpenMP pairwise algorithm on SNAP collaboration networks
(ca-GrQc 5242, ca-HepPh 12008, ca-CondMat 23133) with all-pairs shortest
path distances.  No network access here, so we synthesize collaboration-
network-like graphs (Watts-Strogatz small worlds with planted cliques),
compute APSP on the host (scipy's csgraph BFS), and run the same pipeline: distances -> PaLD ->
strong-tie communities, sequential vs distributed.
"""
from __future__ import annotations

import time

import networkx as nx
import numpy as np
from scipy.sparse.csgraph import shortest_path

import jax

from repro.core import analysis, distributed, pald
from repro.launch import mesh as meshlib

from .common import emit


def collaboration_graph(n: int = 1024, seed: int = 0) -> np.ndarray:
    """Small-world graph + planted cliques; returns APSP distance matrix."""
    rng = np.random.default_rng(seed)
    G = nx.connected_watts_strogatz_graph(n, k=8, p=0.08, seed=seed)
    # planted "research groups": extra cliques of size 5-12
    for _ in range(n // 64):
        mem = rng.choice(n, size=rng.integers(5, 13), replace=False)
        G.add_edges_from((int(a), int(b)) for i, a in enumerate(mem)
                         for b in mem[i + 1:])
    # unweighted APSP: one BFS per source in C (networkx's pure-Python
    # BFS takes minutes at the paper's n = 5,242)
    D = shortest_path(nx.to_scipy_sparse_array(G, nodelist=range(n)),
                      directed=False, unweighted=True).astype(np.float32)
    assert np.isfinite(D).all(), "graph must be connected"
    return D


def run(ns=(512, 1024)) -> list[dict]:
    rows = []
    ndev = len(jax.devices())
    mesh = meshlib.make_test_mesh((ndev,), ("data",))
    for n in ns:
        t0 = time.perf_counter()
        D = collaboration_graph(n)
        t_apsp = time.perf_counter() - t0

        t0 = time.perf_counter()
        C = np.asarray(pald.cohesion(D, method="triplet", block=min(256, n)))
        t_seq = time.perf_counter() - t0

        t0 = time.perf_counter()
        Cd = np.asarray(distributed.pald_distributed(D, mesh, strategy="ring",
                                                     impl="jnp"))
        t_par = time.perf_counter() - t0
        assert np.allclose(C, Cd, atol=1e-5)

        # graph distances are small integers -> massive exact ties; the
        # optimized paths drop ties (paper semantics), so communities are
        # conservative
        comms = [c for c in analysis.communities(C) if len(c) > 1]
        rows.append({
            "n": n,
            "apsp_s": round(t_apsp, 2),
            "pald_seq_s": round(t_seq, 3),
            f"pald_p{ndev}_s": round(t_par, 3),
            "speedup": round(t_seq / t_par, 2),
            "communities": len(comms),
            "largest": max((len(c) for c in comms), default=0),
        })
    return rows


def main() -> None:
    emit(run(), header="appendixC: PaLD on graph APSP distances (synthetic collaboration nets)")


if __name__ == "__main__":
    main()
