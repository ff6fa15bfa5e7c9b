"""Pass 1 of dense PaLD (``kernels/pald_focus_tri.py``): focus sizes.

Work, counted from n alone as the brute-force algorithm needs it: for each
unordered triple {x, y, z} of distinct points, Algorithm 2 compares the
three distances pairwise (3 comparisons) and adds the triple to the focus
counts of the pairs whose focus holds the third point (3 adds).  Bytes: D
read once and the (n, n) focus sizes written once, float32.
"""

MATCH = ("focus_tri_pallas",)


def work(n, d=None, k=None) -> dict:
    triples = n * (n - 1) * (n - 2) / 6
    return dict(vpu_ops=6 * triples, bytes=2 * 4 * n * n)
