"""Sparse k-NN cohesion values (``kernels/pald_knn.py``).

Work, counted with the real k, not the lane-padded width: for each point x,
neighbor pair y and candidate z of x's neighborhood (n k^2), 2 focus
comparisons and a count, 1 support comparison and an accumulate.  Bytes:
the (n, k) neighbor distances and indices and the (n, k, k)
neighbor-to-neighbor distances read once, the (n, k + 1) values written
once, float32.
"""

MATCH = ("knn_values_pallas",)


def work(n, d, k) -> dict:
    return dict(vpu_ops=5 * n * k * k,
                bytes=4 * (2 * n * k + n * k * k + n * (k + 1)))
