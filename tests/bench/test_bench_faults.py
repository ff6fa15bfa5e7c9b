"""A run with the timed path broken underneath comes out not correct: an
answer altered where it is produced, a neighbor swapped, and half of the
rows left out."""
import jax
import jax.numpy as jnp
import pytest

from bench import discover, run

MAN = discover.manifest()
TINY = {"grqc-apsp": 160, "sift-128": 512}


def _alter(x):
    """One answer changed by a hundredth of the largest."""
    return x.at[1, 1].add(0.01 * jnp.max(jnp.abs(x)))


def _halve(x):
    return x.at[x.shape[0] // 2:].set(0)


def _swap_neighbor(g):
    return g._replace(indices=g.indices.at[3, 0].set(g.indices[3, 1]))


def _patch(monkeypatch, cell, fault):
    from repro.core import pald
    from repro.kernels import ops

    entry = discover.traffic(cell["traffic"])["entry"]
    if entry == "dense_cohesion":
        target, name = pald, "cohesion"
        wrap = lambda out: fault(out)  # noqa: E731
    elif entry == "from_features":
        target, name = pald, "from_features"
        wrap = lambda out: fault(out)  # noqa: E731
    else:
        target, name = ops, "select_cohere"
        if fault is _swap_neighbor:
            wrap = lambda out: (fault(out[0]), out[1])  # noqa: E731
        else:
            wrap = lambda out: (out[0], fault(out[1]))  # noqa: E731
    orig = getattr(target, name)
    monkeypatch.setattr(target, name, lambda *a, **k: wrap(orig(*a, **k)))


CASES = [(c, f) for c in MAN["workloads"] for f in (_alter, _halve)] + [
    (c, _swap_neighbor) for c in MAN["workloads"]
    if discover.traffic(c["traffic"])["entry"] == "select_cohere"]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=lambda v: v["name"] if isinstance(v, dict)
                         else v.__name__.strip("_"))
def test_fault_is_not_correct(monkeypatch, cell, fault):
    _patch(monkeypatch, cell, fault)
    r = run.run_cell(MAN, cell, 2**31 + 3, 0.1, False,
                     jax.devices()[:cell["chips"]],
                     n=TINY[cell["config"]], log=lambda s: None)
    assert r["failed"] == 0
    assert not r["correct"], r["checks"]
