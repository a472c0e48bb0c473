"""qwen3-moe-30b-a3b [moe]: 48L d=2048 32H (GQA kv=4, head_dim 128, qk_norm)
vocab=151936 untied, every layer 128 routed SwiGLU experts of width 768,
top-8 renormalised (``norm_topk_prob``), no shared expert; rms eps 1e-6,
rope theta 1e6 [hf:Qwen/Qwen3-30B-A3B config.json].

``chip_share()`` is one chip's part of a deployment in which 16 chips share
each layer by expert parallelism: experts 0-7 of all 48 layers, with the
router's 128 outputs, attention and the vocabulary whole."""
import dataclasses

from .base import ATTN, LayerSpec, ModelConfig

SKIPS = {"long_500k": "pure full-attention arch (no sub-quadratic path)"}

EP_CHIPS = 16                   # chips that share each layer in chip_share()


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-moe-30b-a3b", family="moe",
        d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        d_ff=6144,              # published intermediate_size; no layer is dense
        vocab=151936,
        period=(LayerSpec(ATTN, moe=True),), n_periods=48,
        n_experts=128, top_k=8, d_ff_expert=768,
        rope_theta=1_000_000.0, qk_norm=True,
        tie_embeddings=False, norm_eps=1e-6,
    )


def chip_share() -> ModelConfig:
    """The experts one of ``EP_CHIPS`` chips holds: 0-7 of 128."""
    cfg = config()
    return dataclasses.replace(cfg, experts_held=(0, cfg.n_experts // EP_CHIPS))


def smoke() -> ModelConfig:
    return dataclasses.replace(
        config(), name="qwen3-moe-smoke",
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32, vocab=256,
        period=(LayerSpec(ATTN, moe=True),), n_periods=2,
        n_experts=8, top_k=2, d_ff_expert=32)
