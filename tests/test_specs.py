"""Sharding-spec inference rules + divisibility fitting."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import mesh as MM
from repro.models import lm
from repro.sharding import Axes, kv_cache_spec

AX = Axes(batch=("data",), model="model", model_size=16, batch_size=16)


def test_param_rules_dense():
    cfg = configs.smoke_config("phi4_mini_3p8b")
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = MM.infer_param_specs(shapes, AX)
    blk = specs["blocks"][0]
    assert blk["attn"]["wq"] == P(None, None, "model")     # stacked lead dim
    assert blk["attn"]["wo"] == P(None, "model")
    assert blk["ffn"]["w_gate"] == P(None, None, "model")
    assert blk["ffn"]["w_down"] == P(None, "model")
    assert specs["embed"]["embed"] == P("model")
    assert specs["embed"]["head"] == P(None, "model")
    assert specs["norm_final"]["scale"] == P()


def test_expert_rules_ep_vs_tp():
    cfg = configs.get_config("qwen3-moe-30b-a3b")          # 128 experts: EP
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = MM.infer_param_specs(shapes, AX)
    assert specs["blocks"][0]["ffn"]["w_gate"] == P(None, "model")
    cfg2 = configs.get_config("mixtral-8x7b")              # 8 experts on 16: TP
    shapes2 = jax.eval_shape(lambda k: lm.init_params(k, cfg2),
                             jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs2 = MM.infer_param_specs(shapes2, AX)
    assert specs2["blocks"][0]["ffn"]["w_gate"] == P(None, None, None, "model")
    assert specs2["blocks"][0]["ffn"]["w_down"] == P(None, None, "model")


def test_fsdp_adds_dp_dim():
    cfg = configs.get_config("qwen3-1.7b")
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = MM.infer_param_specs(shapes, AX, fsdp=True)
    assert specs["blocks"][0]["attn"]["wq"] == P(None, "data", "model")
    # small leaves stay unsharded by fsdp
    assert specs["norm_final"]["scale"] == P()


def test_kv_cache_spec_rules():
    assert kv_cache_spec(AX, 16) == P("data", None, "model", None)
    assert kv_cache_spec(AX, 2) == P("data", "model", None, None)
    long_ax = Axes(batch=(), model="model", seq="data", model_size=16)
    assert kv_cache_spec(long_ax, 16) == P(None, "data", "model", None)
    assert kv_cache_spec(long_ax, 2) == P(None, ("data", "model"), None, None)


def test_fit_specs_drops_nondivisible():
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    # fake mesh with model=1 divides everything; use shape check instead
    specs = {"a": P("model"), "b": P("model")}
    shapes = {"a": jax.ShapeDtypeStruct((7,), jnp.float32),
              "b": jax.ShapeDtypeStruct((8,), jnp.float32)}
    fitted = MM.fit_specs(mesh, specs, shapes)
    assert fitted["a"] == P("model")   # 7 % 1 == 0
    assert fitted["b"] == P("model")


def test_axes_for_shapes():
    pytest.importorskip("jax")
    from repro.configs.base import SHAPES
    # long_500k on a fake 4x4 mesh: batch=1 -> context parallel on data
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ax = MM.axes_for(mesh, SHAPES["long_500k"])
    assert ax.seq == "data" and ax.batch == ()
    ax2 = MM.axes_for(mesh, SHAPES["train_4k"])
    assert ax2.batch == ("data",) and ax2.seq is None


def test_qwen3_moe_chip_share_parameter_count():
    """One chip of 16 sharing each layer by expert parallelism: experts 0-7
    of all 48 layers, the router's 128 outputs, attention and the untied
    vocabulary whole: 3.35B parameters, 6.71 GB in bf16."""
    from repro.configs import qwen3_moe_30b_a3b as q
    from repro.configs.specs import count_params
    cfg = q.chip_share()
    assert cfg.held_experts == (0, 8) and cfg.n_experts == 128
    total, _ = count_params(cfg)
    d, L, V = 2048, 48, 151936
    attn = d * 32 * 128 + 2 * d * 4 * 128 + 32 * 128 * d
    experts = 8 * 3 * d * 768
    per_layer = attn + experts + d * 128 + 2 * d + 2 * 128
    assert total == L * per_layer + 2 * V * d + d
    assert round(total / 1e9, 2) == 3.35
    assert round(2 * total / 1e9, 2) == 6.71
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    ffn = shapes["blocks"][0]["ffn"]
    assert ffn["router"].shape == (L, d, 128)
    assert ffn["w_gate"].shape == (L, 8, d, 768)
    assert ffn["w_down"].shape == (L, 8, 768, d)
