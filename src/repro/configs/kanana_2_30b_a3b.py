"""kanana-2-30b-a3b [moe]: 48L d=2048 32H multi-head latent attention (no
query compression, kv_lora_rank 512, qk 128 nope + 64 rope, v 128, rope
theta 1e6 interleaved, no scaling); layer 0 dense SwiGLU 6144, layers 1-47
route over 128 experts of width 768 by sigmoid scores plus a correction
bias (noaux_tc, one group), top-6 renormalised and scaled by 2.448, beside
2 shared experts (one SwiGLU of 1536); vocab=128256 untied, rms eps 1e-6
[hf:kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json, model_type
deepseek_v3].

``chip_share()`` is one chip's part of a deployment in which 16 chips share
each layer by expert parallelism: experts 0-7 of the 47 sparse layers, with
the router's 128 outputs, all attention, both shared experts, the dense
layer and the vocabulary whole."""
import dataclasses

from .base import MLA, LayerSpec, ModelConfig, SHAPES

# served on the local held-expert path only: the shard_map MoE paths that
# the dry-run lowers route by softmax with no shared experts
SKIPS = {name: "sigmoid routing and shared experts run on the local "
               "held-expert path only" for name in SHAPES}

EP_CHIPS = 16                   # chips that share each layer in chip_share()


def config() -> ModelConfig:
    return ModelConfig(
        name="kanana-2-30b-a3b", family="moe",
        d_model=2048, n_heads=32, n_kv_heads=32,
        head_dim=192,           # qk_nope_head_dim + qk_rope_head_dim
        d_ff=6144, vocab=128256,
        lead=(LayerSpec(MLA),),
        period=(LayerSpec(MLA, moe=True),), n_periods=47,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=1_000_000.0,
        n_experts=128, top_k=6, d_ff_expert=768, d_ff_shared=2 * 768,
        router_score="sigmoid", routed_scale=2.448,
        tie_embeddings=False, norm_eps=1e-6,
    )


def chip_share() -> ModelConfig:
    """The experts one of ``EP_CHIPS`` chips holds: 0-7 of 128."""
    cfg = config()
    return dataclasses.replace(cfg, experts_held=(0, cfg.n_experts // EP_CHIPS))


def smoke() -> ModelConfig:
    return dataclasses.replace(
        config(), name="kanana-smoke",
        d_model=64, n_heads=4, n_kv_heads=4, head_dim=24, d_ff=64, vocab=256,
        n_periods=2, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_experts=8, top_k=2, d_ff_expert=32, d_ff_shared=64)
