"""Each kernel's work count against a count by hand at a tiny size."""
import itertools

import pytest

from bench import discover


def _triples(n):
    return sum(1 for _ in itertools.combinations(range(n), 3))


def _hand(kernel, n, d, k):
    if kernel == "focus_tri":
        # 3 comparisons and 3 focus-count adds per unordered triple
        return dict(vpu_ops=6 * _triples(n), bytes=4 * n * n * 2)
    if kernel == "cohesion_tri":
        # + 3 support comparisons, accumulates instead of counts
        return dict(vpu_ops=9 * _triples(n), bytes=4 * n * n * 3)
    if kernel == "topk":
        flops = sum(2 * d for _ in itertools.product(range(n), repeat=2))
        return dict(mxu_flops=flops, vpu_ops=n * n,
                    bytes=4 * n * d + 4 * n * k * 2)
    if kernel == "knn_values":
        ops = sum(5 for _ in itertools.product(range(n), range(k), range(k)))
        return dict(vpu_ops=ops, bytes=4 * (n * k * 2 + n * k * k
                                            + n * (k + 1)))
    raise KeyError(kernel)


@pytest.mark.parametrize("kernel,n,d,k", [
    ("focus_tri", 7, None, None), ("focus_tri", 12, None, None),
    ("cohesion_tri", 7, None, None), ("cohesion_tri", 12, None, None),
    ("topk", 5, 3, 2), ("topk", 9, 4, 3),
    ("knn_values", 4, 3, 3), ("knn_values", 6, 8, 5),
])
def test_work_matches_hand_count(kernel, n, d, k):
    got = discover.kernels()[kernel].work(n=n, d=d, k=k)
    want = _hand(kernel, n, d, k)
    assert {key: got.get(key, 0) for key in want} == pytest.approx(want)


def test_every_kernel_model_names_its_trace_ops():
    for name, mod in discover.kernels().items():
        assert mod.MATCH and all(isinstance(m, str) for m in mod.MATCH), name
