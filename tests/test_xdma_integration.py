"""XDMA-feature integration: layout-optimal cache exactness, MoE dispatch
conservation properties, int8 wire numerics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import given, settings, st  # hypothesis or skip-shim

from repro import configs
from repro.layers import moe as MOE
from repro.models import lm


@pytest.mark.parametrize("arch", ["phi4_mini_3p8b", "gemma3_27b",
                                  "mixtral_8x7b", "whisper_small"])
def test_xdma_cache_decode_exact(arch):
    """decode with the layout-optimal cache == full forward, all families."""
    cfg = dataclasses.replace(configs.smoke_config(arch), dtype=jnp.float32,
                              capacity_factor=8.0, xdma_cache=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 12
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S + 3),
                                          0, cfg.vocab)}
    if cfg.family == "audio":
        batch["audio_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    full_logits, _ = lm.forward(cfg, params, batch)
    cache = lm.init_cache(cfg, B, max_len=S + 8, dtype=jnp.float32)
    pb = dict(batch)
    pb["tokens"] = batch["tokens"][:, :S]
    logits, cache = lm.prefill(cfg, params, pb, cache)
    scale = float(jnp.abs(full_logits).max())
    assert float(jnp.abs(logits[:, 0] - full_logits[:, S - 1]).max()) < 2e-3 * scale
    for t in range(3):
        logits, cache = lm.decode_step(
            cfg, params, batch["tokens"][:, S + t:S + t + 1], cache)
        err = float(jnp.abs(logits[:, 0] - full_logits[:, S + t]).max())
        assert err < 2e-3 * scale, (arch, t, err)


def test_xdma_cache_shapes():
    cfg = dataclasses.replace(configs.smoke_config("phi4_mini_3p8b"),
                              xdma_cache=True)
    cache = lm.init_cache(cfg, B=2, max_len=32)
    k = cache["blocks"][0]["k"]
    v = cache["blocks"][0]["v"]
    assert k.shape == (cfg.n_periods, 2, cfg.n_kv_heads, cfg.head_dim, 32)
    assert v.shape == (cfg.n_periods, 2, cfg.n_kv_heads, 32, cfg.head_dim)


@given(st.integers(0, 50), st.sampled_from([2, 4, 8]))
@settings(max_examples=10, deadline=None)
def test_moe_combine_conserves_weighted_expert_outputs(seed, top_k_e):
    """With capacity >> tokens (no drops), MoE output == sum_k gate_k *
    expert_k(token) computed densely."""
    cfg = dataclasses.replace(
        configs.smoke_config("qwen3_moe_30b_a3b"), dtype=jnp.float32,
        n_experts=top_k_e * 2, top_k=2, capacity_factor=16.0)
    p = MOE.init_moe(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), 1),
                          (1, 6, cfg.d_model), jnp.float32)
    y, _ = MOE.moe_apply(cfg, p, x)
    # dense reference
    tokens = x.reshape(-1, cfg.d_model)
    logits = tokens @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    gates, eidx = jax.lax.top_k(probs, 2)
    gates = gates / gates.sum(-1, keepdims=True)
    dense = jnp.einsum("td,edf->tef", tokens, p["w_gate"])
    up = jnp.einsum("td,edf->tef", tokens, p["w_up"])
    h = jax.nn.silu(dense) * up
    outs = jnp.einsum("tef,efd->ted", h, p["w_down"])
    ref = jnp.zeros_like(tokens)
    for kk in range(2):
        ref = ref + gates[:, kk:kk + 1] * jnp.take_along_axis(
            outs, eidx[:, kk][:, None, None], axis=1)[:, 0]
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_moe_dropping_bounded_by_capacity():
    """The capacity buffer of the shard_map paths at capacity factor ~0: one
    slot per expert, so most tokens drop -> output ~ 0 (never NaN)."""
    cfg = dataclasses.replace(configs.smoke_config("mixtral_8x7b"),
                              dtype=jnp.float32, capacity_factor=0.01)
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
    tokens = x.reshape(-1, cfg.d_model)
    T, E = tokens.shape[0], cfg.n_experts
    gates, eidx, aux = MOE._route(cfg, p["router"], tokens)
    capacity = int(cfg.capacity_factor * cfg.top_k * T // E) + 1
    assert capacity == 1
    buf, slot, keep, order, _ = MOE._dispatch(cfg, tokens, eidx, gates, capacity)
    y = MOE._combine(cfg, MOE._expert_ffn(cfg, p, buf), slot, keep, order,
                     gates, T, cfg.d_model)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(float(aux))
    # at most E of the T*k assignments keep a slot: the other tokens drop
    assert int(keep.sum()) <= E
    assert int((np.abs(np.asarray(y)).max(-1) == 0).sum()) >= T - E


def test_int8_wire_roundtrip_precision():
    from repro.core import Quantize, Dequantize
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.float32)
    deq = Dequantize()(Quantize()(x))
    rel = float(jnp.abs(deq - x).max() / jnp.abs(x).max())
    assert rel < 0.01
