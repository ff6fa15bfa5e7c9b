"""Pass 2 of dense PaLD (``kernels/pald_cohesion_tri.py``): cohesion.

Work, counted from n alone: for each unordered triple of distinct points,
the 3 focus comparisons of pass 1, the 3 support comparisons (which of the
pair the third point is closer to) and the 3 accumulates of a weight into
C.  Bytes: D and the (n, n) weights read once, C written once, float32.
"""

MATCH = ("cohesion_tri_pallas",)


def work(n, d=None, k=None) -> dict:
    triples = n * (n - 1) * (n - 2) / 6
    return dict(vpu_ops=9 * triples, bytes=3 * 4 * n * n)
