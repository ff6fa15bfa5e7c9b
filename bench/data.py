"""Seeded problem generators of the benchmark's configurations.

Copied from the repository's own generators (``collaboration_graph`` from
``benchmarks/bench_graphs.py``, ``clusters`` from ``chip_smoke.py``) so that
no later change to the program can move the yardstick.  A configuration
names its generator under ``"generator"``; ``generator(name)`` finds it
here, or in ``bench/generators/<name>.py`` (a module with a function of the
same name) for generators added later.
"""
from __future__ import annotations

import networkx as nx
import numpy as np
from scipy.sparse.csgraph import shortest_path


def collaboration_graph(n: int = 1024, seed: int = 0, *, ws_k: int = 8,
                        ws_p: float = 0.08, clique_min: int = 5,
                        clique_max: int = 12,
                        clique_every: int = 64) -> np.ndarray:
    """Small-world graph + planted cliques; returns APSP distance matrix.

    A connected Watts-Strogatz graph (each node joined to ``ws_k``
    neighbors, rewired with probability ``ws_p``) with ``n // clique_every``
    planted "research groups": cliques of ``clique_min`` to ``clique_max``
    members.  Unweighted all-pairs shortest paths by one BFS per source in
    C (scipy's csgraph), as float32 hop counts.
    """
    rng = np.random.default_rng(seed)
    G = nx.connected_watts_strogatz_graph(n, k=ws_k, p=ws_p, seed=seed)
    for _ in range(n // clique_every):
        mem = rng.choice(n, size=rng.integers(clique_min, clique_max + 1),
                         replace=False)
        G.add_edges_from((int(a), int(b)) for i, a in enumerate(mem)
                         for b in mem[i + 1:])
    D = shortest_path(nx.to_scipy_sparse_array(G, nodelist=range(n)),
                      directed=False, unweighted=True).astype(np.float32)
    if not np.isfinite(D).all():
        raise ValueError("graph must be connected")
    return D


def clusters(n: int, seed: int, *, d: int = 128, n_clusters: int = 32,
             spreads=(2.0, 6.0, 18.0), center_max: int = 160,
             lo: int = 0, hi: int = 255):
    """Mixed-density Gaussian clusters, rounded and clipped to [lo, hi].

    Spreads cycle over ``spreads`` by cluster over well-separated centers
    drawn from [0, center_max); returns float32 features and the cluster
    label of each row.  Integer features keep every dot product exact in
    float32, as uint8 SIFT descriptors do.
    """
    rng = np.random.default_rng(seed)
    centers = rng.integers(0, center_max, size=(n_clusters, d))
    spread = np.asarray(spreads)[np.arange(n_clusters) % len(spreads)]
    labels = rng.integers(0, n_clusters, size=n)
    X = centers[labels] + rng.normal(size=(n, d)) * spread[labels, None]
    return np.clip(np.rint(X), lo, hi).astype(np.float32), labels


GENERATORS = {"collaboration_graph": collaboration_graph,
              "clusters": clusters}


def generator(name: str):
    """The generator function a configuration names."""
    if name in GENERATORS:
        return GENERATORS[name]
    from bench import discover

    return getattr(discover.module("generators", name), name)
