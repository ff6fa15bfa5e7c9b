"""Streaming k-nearest selection (``kernels/pald_topk.py``).

Work, counted from n, d and k alone: the 2 n^2 d flops of all pairwise
distances at the MXU's peak, and one comparison per candidate (n^2) on the
VPU.  Bytes: the (n, d) features read once, the (n, k) distances and
indices written once.
"""

MATCH = ("topk_pallas",)


def work(n, d, k) -> dict:
    return dict(mxu_flops=2 * n * n * d, vpu_ops=n * n,
                bytes=4 * n * d + 8 * n * k)
