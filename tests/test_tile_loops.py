"""The grouped y-loop of ``kernels/tile_loops.py`` (interpret mode).

Every dense, tri and fused kernel runs its y-loop eight rows at a time
wherever the tile's row count divides by 8, and one row at a time
otherwise.  The grouped loop must give bitwise what the per-row loop gives:
each case runs a kernel twice, once as built and once with the group forced
to 1, on tied integer distances, and compares the two with
``np.array_equal``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.weights import index_xwins
from repro.kernels import ref, tile_loops
from repro.kernels.pald_cohesion import cohesion_pallas
from repro.kernels.pald_cohesion_tri import cohesion_tri_pallas
from repro.kernels.pald_focus import focus_pallas
from repro.kernels.pald_focus_tri import focus_tri_pallas
from repro.kernels.pald_fused import cohesion_fused_pallas, focus_fused_pallas


def _tied_D(rng, n):
    """Symmetric small-integer distances: many exact ties, zero diagonal."""
    A = rng.integers(1, 6, size=(n, n)).astype(np.float32)
    D = np.triu(A, 1)
    return jnp.asarray(D + D.T)


def _features(rng, n, d=3):
    """Small-integer features: manhattan distances tie often."""
    return jnp.asarray(rng.integers(0, 4, size=(n, d)).astype(np.float32))


def _run(kind, ties, block, rng):
    n = 2 * block
    D = _tied_D(rng, n)
    W = ref.weights_ref(ref.focus_ref(D, ties=ties))
    dense = dict(block_x=block, block_y=block, block_z=block,
                 interpret=True, ties=ties)
    fused = dict(metric="manhattan", n_valid=n - 3, block=block,
                 block_z=block, interpret=True, ties=ties)
    if kind == "focus_tri":
        return focus_tri_pallas(D, block=block, block_z=block,
                                interpret=True, ties=ties)
    if kind == "cohesion_tri":
        return cohesion_tri_pallas(D, W, block=block, block_z=block,
                                   interpret=True, ties=ties)
    if kind == "focus":
        return focus_pallas(D, block_xy=block, block_z=block, interpret=True,
                            ties=ties)
    if kind == "cohesion_plain":
        return cohesion_pallas(D, W, **dense)
    if kind == "cohesion_iota":     # index tiebreak from the grid position
        return cohesion_pallas(D, W, **{**dense, "ties": "ignore"})
    if kind == "cohesion_xw":       # index tiebreak from an explicit tile
        XW = index_xwins(0, n, 0, n).astype(jnp.float32)
        return cohesion_pallas(D, W, XW=XW, **{**dense, "ties": "ignore"})
    X = _features(rng, n)
    if kind == "focus_fused":
        return focus_fused_pallas(X, **fused)
    if kind == "cohesion_fused":
        return cohesion_fused_pallas(X, W, **fused)
    raise ValueError(kind)


KINDS = ("focus_tri", "cohesion_tri", "focus", "cohesion_plain",
         "cohesion_iota", "cohesion_xw", "focus_fused", "cohesion_fused")


@pytest.mark.parametrize("block", (8, 16, 32))
@pytest.mark.parametrize("ties", ("drop", "split", "ignore"))
@pytest.mark.parametrize("kind", KINDS)
def test_grouped_loop_is_bitwise_the_per_row_loop(kind, ties, block,
                                                  monkeypatch):
    assert tile_loops.y_group(block) == 8
    grouped = np.asarray(_run(kind, ties, block, np.random.default_rng(block)))
    jax.clear_caches()
    monkeypatch.setattr(tile_loops, "y_group", lambda rows: 1)
    per_row = np.asarray(_run(kind, ties, block, np.random.default_rng(block)))
    monkeypatch.undo()
    jax.clear_caches()
    assert np.array_equal(grouped, per_row), np.abs(grouped - per_row).max()


def test_group_of_a_sublane_tile():
    """The benchmark's tiles take 8 y a step; a row count that 8 does not
    divide takes the per-row loop."""
    assert tile_loops.y_group(128) == 8
    assert tile_loops.y_group(256) == 8
    assert tile_loops.y_group(12) == 1
    assert tile_loops.y_group(33) == 1


@pytest.mark.parametrize("ties", ("drop", "split", "ignore"))
def test_twelve_row_tiles_match_the_reference(rng, ties):
    """Tiles of 12 rows run the per-row loop and still match the oracles."""
    D = _tied_D(rng, 24)
    U_ref = ref.focus_ref(D, ties=ties)
    W = ref.weights_ref(U_ref)
    C_ref = np.asarray(ref.cohesion_ref(D, W, ties=ties))
    U = focus_tri_pallas(D, block=12, block_z=12, interpret=True, ties=ties)
    C = cohesion_tri_pallas(D, W, block=12, block_z=12, interpret=True,
                            ties=ties)
    Cd = cohesion_pallas(D, W, block_x=12, block_y=12, block_z=12,
                         interpret=True, ties=ties)
    np.testing.assert_array_equal(np.asarray(U), np.asarray(U_ref))
    np.testing.assert_allclose(np.asarray(C), C_ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(Cd), C_ref, rtol=1e-6, atol=1e-6)
