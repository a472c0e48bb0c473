"""Real-width compiles of the main-path kernels for a described v5e chip.

Nothing runs: each kernel is lowered under ``forced_interpret(False)``
against one chip of a described ``v5e:2x2`` topology and compiled by the
TPU compiler installed with jax, which refuses what the chip would refuse
(VMEM overflow, unaligned slices, layouts Mosaic cannot lower).  Each compiled program must
hold a Mosaic kernel (``tpu_custom_call``), so no kernel silently fell back
to XLA.  Sizes are the serving path's: one full-context K matrix (S=4096,
d_kv=8x128) and one (32, 1024) KV page.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import (MN, RMSNormPlugin, Transpose, describe,
                        layout_for_dtype, plugin_compiler)
from repro.core import plugins as P
from repro.core.descriptor import page_descriptor
from repro.kernels import agu, forced_interpret

KV_MATRIX = (4096, 1024)
PAGE = (32, 1024)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Described-chip compiles land in the persistent cache but cannot be
    read back without a chip; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _mosaic_compile(fn, shape, dtype, sharding):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    with forced_interpret(False):
        lowered = jax.jit(fn).lower(x)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_agu_tile_kernel(dtype, one_chip, no_compile_cache):
    tiled = layout_for_dtype(dtype)
    plan, reason = agu.plan_relayout(MN, tiled, KV_MATRIX)
    assert plan is not None and plan.kind == "kernel", reason
    _mosaic_compile(plan.run, KV_MATRIX, dtype, one_chip)


def test_kv_store_rmsnorm_tile(one_chip, no_compile_cache):
    """The serving path's prefill store: RMSNorm on the stream + tile."""
    desc = describe(MN, layout_for_dtype(jnp.bfloat16), RMSNormPlugin())
    _mosaic_compile(plugin_compiler.compile_local(desc),
                    KV_MATRIX, jnp.bfloat16, one_chip)


def test_kv_load_tiled_transposed(one_chip, no_compile_cache):
    """The serving path's load: tiled K back as K^T in MN."""
    tiled = layout_for_dtype(jnp.bfloat16)
    desc = describe(tiled, MN, Transpose())
    _mosaic_compile(plugin_compiler.compile_local(desc),
                    tiled.physical_shape(KV_MATRIX), jnp.bfloat16, one_chip)


@pytest.mark.parametrize("direction", ["load", "store"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_page_compress_evict_restore(direction, dtype, one_chip,
                                     no_compile_cache):
    """Evict (page layout -> MN) and restore (MN -> page layout) through the
    Compress/Decompress wire codec, fused into one kernel."""
    name = jnp.dtype(dtype).name
    desc = page_descriptor(*PAGE, name, direction=direction,
                           wire_compress_rows=8)
    assert plugin_compiler.can_fuse(desc) == (True, "fusible")
    shape = desc.src.layout.physical_shape(PAGE)
    _mosaic_compile(plugin_compiler.compile_local(desc),
                    shape, dtype, one_chip)


def test_open_payload_chain_is_refused_not_compiled():
    """A chain whose output is still compressed would hand Mosaic a rank-1
    bool output (the compiler aborts the process on it): it is routed to
    the XLA composition by rule."""
    desc = describe(MN, layout_for_dtype(jnp.float32),
                    P.Compress(block_rows=8))
    ok, reason = plugin_compiler.can_fuse(desc)
    assert not ok and reason == "payload-output:compress_blocksparse"


@pytest.mark.parametrize("tokens", [32, 2048])
def test_held_expert_layer_at_published_widths(tokens, one_chip,
                                               no_compile_cache):
    """qwen3-moe-30b-a3b's sparse block at one chip's share (8 of 128
    experts, d 2048, width 768) for a decode batch and a prefill group: the
    grouped products over the held experts lower to the TPU's own grouped
    matmul kernel."""
    from repro.configs import qwen3_moe_30b_a3b
    from repro.layers import moe
    cfg = qwen3_moe_30b_a3b.chip_share()
    params = jax.eval_shape(lambda: moe.init_moe(jax.random.key(0), cfg))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.bfloat16, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((1, tokens, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(lambda p, x: moe.moe_apply(cfg, p, x)).lower(
        params, x).compile().as_text()
    assert "tpu_custom_call" in text, "no grouped matmul kernel"
