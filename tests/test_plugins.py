"""Plugin semantics + engine/baseline agreement (hypothesis where useful)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import given, settings, st  # hypothesis or skip-shim

from repro import core as C


def rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), dtype)


def test_transpose_plugin():
    x = rand((32, 256))
    assert jnp.array_equal(C.Transpose()(x), x.T)


def test_rmsnorm_plugin_unit_rms():
    x = rand((64, 256), 1)
    y = C.RMSNormPlugin()(x).astype(jnp.float32)
    rms = jnp.sqrt((y ** 2).mean(-1))
    assert jnp.allclose(rms, 1.0, atol=1e-3)


@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_bound(seed):
    x = rand((16, 128), seed)
    q = C.Quantize()(x)
    deq = C.Dequantize()(q)
    # symmetric int8: error bounded by scale/2 = amax/254 per row
    amax = jnp.abs(x).max(axis=-1, keepdims=True)
    assert bool(jnp.all(jnp.abs(deq - x) <= amax / 127.0 + 1e-7))


def test_chain_composition():
    x = rand((32, 256), 2)
    chain = [C.Scale(2.0), C.BiasAdd(1.0), C.Cast(jnp.bfloat16)]
    y = C.apply_chain(chain, x)
    assert y.dtype == jnp.bfloat16
    ref = (x * 2 + 1).astype(jnp.bfloat16)
    assert jnp.allclose(y.astype(jnp.float32), ref.astype(jnp.float32))


def test_descriptor_validation():
    d = C.describe("MN", "MNM16N128")
    d.validate((32, 256))
    with pytest.raises(ValueError):
        d.validate((30, 256))
    assert "MN->" in d.summary()


def test_out_logical_shape_through_transpose():
    d = C.describe("MNM16N128", "MNM16N128", C.Transpose())
    assert d.out_logical_shape((128, 256)) == (256, 128)


@pytest.mark.parametrize("src,dst", [("MN", "MNM16N128"), ("MNM16N128", "MN"),
                                     ("MN", "MNM8N128"), ("MNM8N128", "MNM16N128")])
def test_baselines_match_engine(src, dst):
    x_logical = rand((64, 256), 3)
    d = C.describe(src, dst)
    xin = C.by_name(src).from_logical(x_logical)
    want = C.xdma_copy(xin, d)
    got1 = C.baselines.sw_loop_1d_dma(xin, d)
    got2 = C.baselines.sw_loop_2d_dma(xin, d)
    got3 = C.baselines.copy_then_transform(xin, d)
    for got in (got1, got2, got3):
        assert jnp.array_equal(got, want), (src, dst)


def test_baselines_match_engine_transpose():
    x_logical = rand((256, 256), 4)
    d = C.describe("MNM16N128", "MNM16N128", C.Transpose())
    xin = C.MNM16N128.from_logical(x_logical)
    want = C.xdma_copy(xin, d)
    assert jnp.array_equal(C.baselines.sw_loop_1d_dma(xin, d), want)
    assert jnp.array_equal(C.baselines.sw_loop_2d_dma(xin, d), want)
    assert jnp.array_equal(C.baselines.copy_then_transform(xin, d), want)


def test_quantized_payload_travels_tiled():
    x = rand((64, 256), 5)
    d = C.describe("MN", "MNM32N128", C.Quantize())
    out = C.xdma_copy(x, d)
    assert isinstance(out, C.QTensor)
    assert out.values.dtype == jnp.int8
    assert out.values.shape == (2, 2, 32, 128)


# -- the plugin registry ------------------------------------------------------
def test_registry_lookup_and_duplicate_rejection():
    reg = C.registered_plugins()
    assert reg["transpose"] is C.Transpose
    assert C.plugin_by_name("gather_scatter") is C.GatherScatter
    with pytest.raises(KeyError, match="unknown plugin"):
        C.plugin_by_name("nope")
    with pytest.raises(ValueError, match="already registered"):
        @C.register_plugin
        class Imposter(C.Plugin):
            name = "transpose"


# -- compiler-era plugins -----------------------------------------------------
def test_gather_scatter_matches_take_and_inverts():
    x = rand((64, 128), 6)
    perm = np.random.default_rng(0).permutation(64)
    g = C.GatherScatter(indices=perm)
    assert jnp.array_equal(g(x), x[perm])
    inv = np.argsort(perm)
    assert jnp.array_equal(C.GatherScatter(indices=inv)(g(x)), x)
    assert g.out_logical_shape((64, 128)) == (64, 128)
    # expanding gather declares the new row count
    dup = C.GatherScatter(indices=np.arange(64).repeat(2))
    assert dup.out_logical_shape((64, 128)) == (128, 128)
    with pytest.raises(ValueError):
        C.GatherScatter()


def test_compress_roundtrip_occupancy_and_wire_bytes():
    x = rand((64, 128), 7)
    x = x.at[:32].set(0.0)
    ct = C.Compress(block_rows=8)(x)
    assert isinstance(ct, C.CTensor)
    assert ct.mask.shape == (8,) and float(ct.occupancy()) == 0.5
    dense = 64 * 128 * 4
    assert ct.wire_nbytes() == dense // 2 + 8   # half the blocks + the mask
    assert jnp.array_equal(C.Decompress()(ct), x)
    with pytest.raises(ValueError, match="not divisible"):
        C.Compress(block_rows=7)(x)


def test_reduce_stage_sum_max():
    x = rand((32, 128), 8)
    assert jnp.allclose(C.ReduceStage("sum")(x), x.sum(0, keepdims=True))
    assert jnp.array_equal(C.ReduceStage("max")(x), x.max(0, keepdims=True))
    assert C.ReduceStage("sum").out_logical_shape((32, 128)) == (1, 128)
    with pytest.raises(ValueError):
        C.ReduceStage("mean")


# -- rank-change declaration (CFG-time failure, not a cryptic jit error) -----
class _RankChanger(C.Plugin):
    name = "rank_changer_test"

    def __call__(self, x):
        return x.reshape(-1)

    def out_logical_shape(self, shape):
        return (int(np.prod(shape)),)


def test_undeclared_rank_change_raises_clearly():
    with pytest.raises(ValueError, match="changed logical rank"):
        C.plugins.chain_out_shape([_RankChanger()], (16, 128))
    # the descriptor surfaces it at CFG time too, naming the plugin
    d = C.describe("MN", "MN", _RankChanger())
    with pytest.raises(ValueError, match="rank_changer_test"):
        d.out_logical_shape((16, 128))


def test_declared_rank_change_is_allowed():
    squeeze = C.ReduceStage("sum", keepdims=False)
    assert squeeze.changes_rank
    assert C.plugins.chain_out_shape([squeeze], (16, 128)) == (128,)

    class Declared(_RankChanger):
        name = "declared_rank_changer_test"
        changes_rank = True

    assert C.plugins.chain_out_shape([Declared()], (16, 128)) == (16 * 128,)


# -- cfg_stats: fused vs fallback accounting ---------------------------------
def test_plugin_compiler_cfg_stats():
    from repro.core import plugin_compiler as PC
    from repro.core import xdma
    xdma.clear_cache()      # a CFG-cache hit skips _lower and records nothing
    PC.clear_stats()
    x = rand((64, 256), 9)
    xdma.transfer(x, C.describe("MN", "MNM8N128", C.Scale(1.25)))   # fuses
    xdma.transfer(x, C.describe("MN", "MNM32N128", C.Quantize()))   # falls back
    xdma.transfer(x, C.describe("MN", "MNM8N128"))                  # empty chain
    stats = PC.cfg_stats()
    assert stats["fused"] >= 1 and stats["fallback"] >= 2
    assert any(r.startswith("no-emit:quantize") for r in stats["reasons"])
    assert "empty-chain" in stats["reasons"]
    # a chain still compressed at its end would write a rank-1 bool mask,
    # which Mosaic cannot: refused by rule, never compiled
    ct = xdma.transfer(x, C.describe("MN", "MN", C.Compress(block_rows=8)))
    assert isinstance(ct, C.CTensor)
    assert "payload-output:compress_blocksparse" in PC.cfg_stats()["reasons"]


def test_plugin_compiler_tallies_each_lowering_once():
    """fused + fallback counts each lowering once: a refused chain at its
    CFG phase, a fusible one where its kernel is chosen, so a block too
    large for VMEM takes the XLA composition (same bits) as a fallback and
    never also counts as fused.  A forced kernel (backend='compiled')
    raises there instead of running XLA."""
    from repro.core import plugin_compiler as PC
    from repro.core import xdma
    xdma.clear_cache()
    PC.clear_stats()
    x = rand((64, 256), 9)
    big = rand((1024, 1024), 3)
    gather = C.describe("MN", "MN",
                        C.GatherScatter(indices=np.arange(1023, -1, -1)))
    lowerings = [(x, C.describe("MN", "MNM8N128", C.Scale(1.25))),   # streamed
                 (x, C.describe("MN", "MN", C.Transpose())),          # AGU
                 (x, C.describe("MN", "MNM32N128", C.Quantize())),    # refused
                 (big, gather)]                                       # vmem-block
    outs = [xdma.transfer(arr, desc) for arr, desc in lowerings]
    stats = PC.cfg_stats()
    assert stats["fused"] + stats["fallback"] == len(lowerings)
    assert (stats["fused"], stats["fallback"]) == (2, 2)
    assert set(stats["reasons"]) == {"no-emit:quantize_int8", "vmem-block"}
    want = xdma.transfer(big, dataclasses.replace(gather, backend="fused"))
    np.testing.assert_array_equal(np.asarray(outs[-1]), np.asarray(want))
    with pytest.raises(ValueError, match="vmem-block"):
        xdma.transfer(big, dataclasses.replace(gather, backend="compiled"))


def test_plugin_compiler_squeezes_unit_leading_dims():
    """A (1, M, N) movement runs the (M, N) kernel: same bytes, same bits."""
    from repro.core import xdma
    x = rand((64, 256), 5)[None]
    for desc in (C.describe("MN", "MNM8N128", C.Scale(1.25)),
                 C.describe("MN", "MN", C.Transpose())):
        got = xdma.transfer(x, desc)
        want = xdma.transfer(x, dataclasses.replace(desc, backend="fused"))
        assert got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
