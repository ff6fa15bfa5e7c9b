"""A run with the timed path broken underneath comes out not correct: an
answer altered where it is produced, a neighbor swapped, half of the rows
left out, and, on more than one chip, the exchange between chips left out.
Each fault goes into the program function that the cell's entry declares
(``PROGRAM``), where the form of its result (``RESULT``) says."""
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from bench import discover, run
from bench.run import ROOT
from tiny import program, tiny_n

MAN = discover.manifest()


def _alter(x):
    """One answer changed by a hundredth of the largest."""
    return x.at[1, 1].add(0.01 * jnp.max(jnp.abs(x)))


def _halve(x):
    return x.at[x.shape[0] // 2:].set(0)


def _swap_neighbor(g):
    return g._replace(indices=g.indices.at[3, 0].set(g.indices[3, 1]))


def _gather_own(x, axis_name, *, axis=0, tiled=False, **_):
    """An all_gather with nothing exchanged: every slot holds this device's
    own block."""
    parts = [x] * jax.lax.psum(1, axis_name)
    return jnp.concatenate(parts, axis) if tiled else jnp.stack(parts, axis)


def _no_exchange(call):
    """The program traced with every all_gather kept on its device and
    every ppermute sending nothing."""
    def alone(*a, **k):
        with mock.patch.object(jax.lax, "all_gather", _gather_own), \
                mock.patch.object(jax.lax, "ppermute", lambda x, *_, **__: x):
            return call(*a, **k)
    return alone


def _patch(monkeypatch, cell, fault, root=ROOT):
    target, name, result = program(cell, root)
    orig = getattr(target, name)
    if fault is _no_exchange:
        monkeypatch.setattr(target, name, _no_exchange(orig))
        return
    if result == "dense":
        wrap = lambda out: fault(out)  # noqa: E731
    elif fault is _swap_neighbor:
        wrap = lambda out: (fault(out[0]), out[1])  # noqa: E731
    else:
        wrap = lambda out: (out[0], fault(out[1]))  # noqa: E731
    monkeypatch.setattr(target, name, lambda *a, **k: wrap(orig(*a, **k)))


CASES = [(c, f) for c in MAN["workloads"] for f in (_alter, _halve)] + [
    (c, _swap_neighbor) for c in MAN["workloads"] if program(c)[2] == "knn"] + [
    (c, _no_exchange) for c in MAN["workloads"] if c["chips"] > 1]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=lambda v: v["name"] if isinstance(v, dict)
                         else v.__name__.strip("_"))
def test_fault_is_not_correct(monkeypatch, cell, fault):
    _patch(monkeypatch, cell, fault)
    r = run.run_cell(MAN, cell, 2**31 + 3, 0.1, False,
                     jax.devices()[:cell["chips"]],
                     n=tiny_n(MAN, cell), log=lambda s: None)
    assert r["failed"] == 0
    assert not r["correct"], r["checks"]
