"""Every Pallas kernel of the main path compiles for a TPU v5e.

Interpret mode cannot see what Mosaic refuses (slices of loaded values,
lane reversals, tiles over the 16 MiB of scoped VMEM).  These tests compile
each kernel at the sizes ``chip_smoke.py`` runs, with the tiles a cold
tuning cache resolves for the ``pallas`` impl, for a described v5e chip:
no chip is needed, the TPU compiler is.  The topology is described inside a
module-scoped fixture, never at import, so every test worker collects the
same tests and only the worker running this file loads the TPU library.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.pald_cohesion import cohesion_pallas
from repro.kernels.pald_cohesion_tri import cohesion_tri_pallas
from repro.kernels.pald_focus import focus_pallas
from repro.kernels.pald_focus_tri import focus_tri_pallas
from repro.kernels.pald_fused import cohesion_fused_pallas, focus_fused_pallas
from repro.kernels.pald_knn import knn_values_pallas
from repro.kernels.pald_topk import topk_pallas
from repro.tuning import autotune

HBM_BYTES = 16 << 30          # one v5e chip
N_TRI = 5242                  # chip_smoke dense-tri (ca-GrQc's node count)
N_DENSE = 4096                # dense and fused kernels, d = 128
N_KNN, D, K = 65536, 128, 32  # chip_smoke knn-sparse
D_WIDE = 960                  # the GIST-960 shape, at the same n and k


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tiles(tmp_path_factory):
    """Cold-cache tiles of the pallas impl, as a TPU run resolves them."""
    cache = str(tmp_path_factory.mktemp("tune") / "blocktune.json")

    def resolve(n, pass_, **kw):
        b, bz, src = autotune.resolve_blocks_ex(
            n, pass_, impl="pallas", backend="tpu", path=cache, **kw)
        assert src == "default"
        return b, bz

    return resolve


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= HBM_BYTES, used
    return compiled


def _square(n, count):
    """``count`` (n, n) f32 operands."""
    return [((n, n), jnp.float32)] * count


@pytest.mark.parametrize("ties", ("drop", "ignore"))
def test_tri_kernels(one_chip, tiles, ties):
    block, block_z = tiles(N_TRI, "pald_tri")
    assert block_z % 128 == 0
    m = -(-N_TRI // block) * block
    _compile(one_chip, lambda D: focus_tri_pallas(
        D, block=block, block_z=block_z, ties=ties), *_square(m, 1))
    _compile(one_chip, lambda D, W: cohesion_tri_pallas(
        D, W, block=block, block_z=block_z, ties=ties), *_square(m, 2))


def test_dense_focus(one_chip, tiles):
    block, block_z = tiles(N_DENSE, "pald")
    _compile(one_chip, lambda D: focus_pallas(
        D, block_xy=block, block_z=block_z), *_square(N_DENSE, 1))


@pytest.mark.parametrize("variant", ("plain", "iota", "xw"))
def test_dense_cohesion(one_chip, tiles, variant):
    block, block_z = tiles(N_DENSE, "pald")
    kw = dict(block_x=block, block_y=block, block_z=block_z)
    if variant == "plain":
        fn = lambda D, W: cohesion_pallas(D, W, **kw)  # noqa: E731
        shapes = _square(N_DENSE, 2)
    elif variant == "iota":   # index tiebreak from the grid position
        fn = lambda D, W: cohesion_pallas(D, W, ties="ignore", **kw)  # noqa: E731
        shapes = _square(N_DENSE, 2)
    else:                     # index tiebreak from an explicit tile
        fn = lambda D, W, XW: cohesion_pallas(  # noqa: E731
            D, W, XW=XW, ties="ignore", **kw)
        shapes = _square(N_DENSE, 3)
    _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("ties", ("drop", "ignore"))
def test_fused_kernels(one_chip, tiles, ties):
    block, block_z = tiles(N_DENSE, "pald_fused", d=D)
    kw = dict(metric="euclidean", n_valid=N_DENSE - 5, block=block,
              block_z=block_z, ties=ties)
    _compile(one_chip, lambda X: focus_fused_pallas(X, **kw),
             ((N_DENSE, D), jnp.float32))
    _compile(one_chip, lambda X, W: cohesion_fused_pallas(X, W, **kw),
             ((N_DENSE, D), jnp.float32), ((N_DENSE, N_DENSE), jnp.float32))


def test_topk_selection(one_chip, tiles):
    block, tile = tiles(N_KNN, "pald_topk", d=D, k=K)
    assert 128 <= block <= 256 and tile <= 512 and tile & (tile - 1) == 0
    _compile(one_chip, lambda X: topk_pallas(
        X, k=K, metric="euclidean", n_valid=N_KNN - 3, block=block,
        block_z=tile), ((N_KNN, D), jnp.float32))


def test_topk_selection_wide(one_chip, tiles):
    """The selection's (block, d) and (block_z, d) feature tiles at a
    feature axis of 7.5 lane-widths, unpadded."""
    block, tile = tiles(N_KNN, "pald_topk", d=D_WIDE, k=K)
    _compile(one_chip, lambda X: topk_pallas(
        X, k=K, metric="euclidean", n_valid=N_KNN, block=block,
        block_z=tile), ((N_KNN, D_WIDE), jnp.float32))


@pytest.mark.parametrize("d", (D, D_WIDE))
def test_neighbor_cube(one_chip, tiles, d):
    """The knn pipeline's neighbor cube is one program whose temporaries
    (the chunk's staged (rows, k, d) gather) stay under 2 GiB at any d; the
    lane-padded (m, 128, 128) cube is its output."""
    from repro.kernels import ops

    block, _ = tiles(N_KNN, "pald_knn", k=K)
    lay = ops.knn_cube_layout(N_KNN, K, d, block=block)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in (((N_KNN, d), jnp.float32), ((N_KNN, K), jnp.float32),
                          ((N_KNN, K), jnp.int32))]
    compiled = jax.jit(lambda x, dn, idx: ops._neighbor_cube(
        x, dn, idx, kind="features", metric="euclidean", rows=lay["rows"],
        kp=128)).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 << 30, mem.temp_size_in_bytes
    assert mem.output_size_in_bytes >= N_KNN * 128 * 128 * 4


def test_knn_values(one_chip, tiles):
    block, _ = tiles(N_KNN, "pald_knn", k=K)
    assert block <= 64
    kp = 128                  # k lane-padded, as ops.knn_values does
    _compile(one_chip, lambda dn, g, idx: knn_values_pallas(
        dn, g, idx, block=block, k_valid=K),
        ((N_KNN, kp), jnp.float32), ((N_KNN, kp, kp), jnp.float32),
        ((N_KNN, kp), jnp.int32))


def test_kernels_keep_their_trace_names(one_chip):
    """Each kernel's custom call is named by its ``pallas_call(name=...)``,
    whatever the jitted wrapper around it is called: the device trace and
    the benchmark's work models find kernels by these names."""
    n, d, blk = 512, 128, 128
    D, W = ((n, n), jnp.float32), ((n, n), jnp.float32)
    X = ((n, d), jnp.float32)
    fused = dict(metric="euclidean", n_valid=n - 5, block=blk, block_z=blk)
    cases = {
        "focus_tri_pallas": (lambda D: focus_tri_pallas(
            D, block=blk, block_z=blk), D),
        "cohesion_tri_pallas": (lambda D, W: cohesion_tri_pallas(
            D, W, block=blk, block_z=blk), D, W),
        "focus_pallas": (lambda D: focus_pallas(
            D, block_xy=blk, block_z=blk), D),
        "cohesion_pallas": (lambda D, W: cohesion_pallas(
            D, W, block_x=blk, block_y=blk, block_z=blk), D, W),
        "focus_fused_pallas": (lambda X: focus_fused_pallas(X, **fused), X),
        "cohesion_fused_pallas": (lambda X, W: cohesion_fused_pallas(
            X, W, **fused), X, W),
        "topk_pallas": (lambda X: topk_pallas(
            X, k=K, metric="euclidean", n_valid=n - 3, block=blk,
            block_z=blk), X),
        "knn_values_pallas": (lambda dn, g, idx: knn_values_pallas(
            dn, g, idx, block=8, k_valid=K), ((n, 128), jnp.float32),
            ((n, 128, 128), jnp.float32), ((n, 128), jnp.int32)),
    }
    for name, (fn, *shapes) in cases.items():
        text = _compile(one_chip, fn, *shapes).as_text()
        calls = re.findall(r"%([\w.-]+) = [^\n]*tpu_custom_call", text)
        assert calls and all(re.fullmatch(rf"{name}\.\d+", c)
                             for c in calls), (name, calls)
