"""Collective-bytes parser + roofline terms."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import hlo_analysis as H
from repro.launch import mesh as meshlib


def test_shape_bytes():
    assert H._shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert H._shape_bytes("bf16[8]") == 16
    assert H._shape_bytes("(f32[2,2]{1,0}, s32[4])") == 16 + 16
    assert H._shape_bytes("pred[]") == 1  # scalar = 1 element


def test_parser_on_synthetic_hlo():
    txt = """
  %x = f32[16,64]{1,0} parameter(0)
  %ag = f32[128,64]{1,0} all-gather(f32[16,64]{1,0} %x), replica_groups={}
  %ar = f32[128,64]{1,0} all-reduce(%ag), to_apply=%add
  %rs = f32[16,64]{1,0} reduce-scatter(%ar), dimensions={0}
  ROOT %out = f32[16,64]{1,0} copy(%rs)
"""
    stats = H.collective_stats(txt)
    assert stats.by_kind["all-gather"][0] == 1
    assert stats.by_kind["all-gather"][1] == 16 * 64 * 4      # operand size
    assert stats.by_kind["all-reduce"][1] == 128 * 64 * 4
    assert stats.by_kind["reduce-scatter"][1] == 128 * 64 * 4
    assert stats.total_count == 3


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 host devices")
def test_parser_on_real_compiled_module():
    """psum of a (8, 32) array over 8 devices => one all-reduce whose operand
    bytes we can predict exactly."""
    mesh = meshlib.make_test_mesh((8,), ("data",))

    def f(x):
        return jax.lax.psum(x, "data")

    sharded = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                            check_vma=False)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    compiled = jax.jit(sharded).lower(x).compile()
    stats = H.collective_stats(compiled.as_text())
    assert stats.by_kind.get("all-reduce", (0, 0))[0] >= 1
    # per-device operand is the local (1, 32) f32 shard
    assert stats.by_kind["all-reduce"][1] == 32 * 4


def test_fused_path_never_materializes_D():
    """ISSUE 2 acceptance: the fused features→cohesion pipeline must never
    hold the full (n, n) distance matrix.

    Verified on the compiled executables' memory analysis: pass 1 of the
    fused path peaks *below the size of one D buffer* (n^2 f32), so a full
    distance matrix cannot exist at any point in it, while the materialized
    counterpart of the same computation carries at least D itself.  The
    full fused pipeline legitimately holds U and W (both (n, n)) — the
    assertion there is relative: at least one n^2 buffer less than
    materialize-then-kernel, at identical block sizes.
    """
    from repro.core import features
    from repro.kernels import ops

    n, d, blk = 512, 8, 16
    X = jnp.zeros((n, d), jnp.float32)
    d_bytes = n * n * 4

    def temp(fn):
        return jax.jit(fn).lower(X).compile().memory_analysis().temp_size_in_bytes

    fused_focus = temp(lambda X: ops._focus_fused_jnp(
        X, metric="sqeuclidean", block=blk, block_z=blk, n_valid=n))
    mat_focus = temp(lambda X: ops._focus_general_jnp(
        *(features.cdist_reference(X, metric="sqeuclidean"),) * 3, chunk=blk))
    assert fused_focus < d_bytes, (
        f"fused focus peaks at {fused_focus} B >= one D ({d_bytes} B): "
        "a full distance matrix fits in its temps")
    assert mat_focus >= d_bytes  # sanity: the materialized path does hold D

    fused_pipe = temp(lambda X: ops.pald_fused(
        X, metric="sqeuclidean", block=blk, block_z=blk, impl="jnp"))
    mat_pipe = temp(lambda X: ops.pald(
        features.cdist_reference(X, metric="sqeuclidean"),
        block=blk, block_z=blk, impl="jnp"))
    assert fused_pipe + d_bytes <= mat_pipe, (
        f"fused pipeline ({fused_pipe} B) saves less than one D buffer vs "
        f"materialized ({mat_pipe} B)")


def test_fused_select_cohere_never_materializes_D():
    """ISSUE 9 acceptance: the fused select->cohere pipeline allocates
    neither the (n, n) distance matrix nor a full per-row scored vector
    beyond one (chunk, n) slab.

    The jnp fused program is one lax.map over row slabs: selection, the
    neighbor feature gather and the cohesion tile body share each step,
    so its compiled temps must stay under ONE (n, n) f32 buffer and under
    a small multiple of the (chunk, n) slab — the working set the module
    comment in kernels/ops.py promises."""
    from repro.kernels import ops

    n, d, k, chunk = 2048, 8, 16, 128
    X = jnp.zeros((n, d), jnp.float32)
    d_bytes = n * n * 4
    slab_bytes = chunk * n * 4

    def temp(fn):
        return (jax.jit(fn).lower(X).compile()
                .memory_analysis().temp_size_in_bytes)

    fused = temp(lambda X: ops.select_cohere(
        X, k=k, block=chunk, tile=n)[1])
    assert fused < d_bytes, (
        f"fused select->cohere peaks at {fused} B >= one D ({d_bytes} B): "
        "a full distance matrix fits in its temps")
    assert fused <= 8 * slab_bytes, (
        f"fused select->cohere peaks at {fused} B > 8 slabs "
        f"({8 * slab_bytes} B): per-row state is not O(chunk * n)")

    # the tile-min prefilter strategy obeys the same bound
    pre = temp(lambda X: ops.select_cohere(
        X, k=k, block=chunk, tile=64)[1])
    assert pre < d_bytes and pre <= 8 * slab_bytes

    # selection alone too (the standalone knn_from_features backend)
    sel = temp(lambda X: (g := ops.topk_select(
        X, k, impl="jnp", block=chunk, tile=n)).distances)
    assert sel < d_bytes and sel <= 8 * slab_bytes


def test_roofline_terms():
    t = H.roofline_terms(hlo_flops=197e12, hlo_bytes=819e9, coll_bytes=50e9,
                         chips=1, flops_is_global=False)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(1.0)
    t2 = H.roofline_terms(hlo_flops=1e15, hlo_bytes=1e9, coll_bytes=0,
                          chips=1, flops_is_global=False)
    assert t2["bottleneck"] == "compute"


def test_model_flops():
    from repro import configs
    from repro.configs.base import SHAPES
    cfg = configs.get("llama3.2-3b")
    mf_train = H.model_flops(cfg, SHAPES["train_4k"])
    _, active = cfg.param_count()
    assert mf_train == pytest.approx(6 * active * 4096 * 256)
    mf_dec = H.model_flops(cfg, SHAPES["decode_32k"])
    assert mf_dec == pytest.approx(2 * active * 128)
    # MoE uses active (not total) params
    moe = configs.get("phi3.5-moe-42b-a6.6b")
    t, a = moe.param_count()
    assert H.model_flops(moe, SHAPES["train_4k"]) < 6 * t * 4096 * 256 / 3
