"""Model configuration schema + the layer-period block description.

Every assigned architecture is expressed as a :class:`ModelConfig` whose
``period`` (a tuple of :class:`LayerSpec`) describes one repeating block of
layers; the model scans ``n_periods`` stacked copies between an optional
unstacked ``lead`` (layers that run first, e.g. DeepSeek-V3-style models'
leading dense layers) and an optional unstacked ``tail`` (e.g. gemma3's
62 = 6*10 + 2).  This keeps HLO size and compile time flat in depth
(DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.sharding import Axes

# layer kinds
ATTN = "attn"       # (optionally windowed) self-attention
MAMBA = "mamba"
MLSTM = "mlstm"
SLSTM = "slstm"
MLA = "mla"         # multi-head latent attention (DeepSeek-V2/V3)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str = ATTN
    window: Optional[int] = None    # sliding-window size (attn only)
    moe: bool = False               # MoE FFN instead of dense
    ffn: bool = True                # has an FFN sublayer at all

    def cache_kind(self) -> str:
        return {ATTN: "kv", MAMBA: "ssm", MLSTM: "mlstm", SLSTM: "slstm",
                MLA: "latent"}[self.kind]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | hybrid | ssm | vlm | audio
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    period: Tuple[LayerSpec, ...]
    n_periods: int
    tail: Tuple[LayerSpec, ...] = ()
    lead: Tuple[LayerSpec, ...] = ()   # unstacked layers before the periods

    # attention details
    rope_theta: float = 10000.0
    qk_norm: bool = False
    qkv_bias: bool = False
    mrope: bool = False

    # latent attention (MLA): queries of qk_nope_head_dim + qk_rope_head_dim
    # per head (no query compression), one shared kv_lora_rank latent plus a
    # qk_rope_head_dim rotary key per token, values of v_head_dim per head
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    moe_wire_int8: bool = False
    # (first, count): the contiguous range of the router's ``n_experts``
    # outputs whose weights live here (one chip's share under expert
    # parallelism); None holds them all
    experts_held: Optional[Tuple[int, int]] = None
    # "softmax": top-k of the softmax; "sigmoid": top-k of sigmoid scores
    # plus a per-expert correction bias, weighted by the unbiased scores
    # (DeepSeek-V3's noaux_tc with one group)
    router_score: str = "softmax"
    routed_scale: float = 1.0       # applied to the renormalised gates
    d_ff_shared: int = 0            # width of the shared experts' SwiGLU

    # SSM (mamba)
    ssm_d_inner: int = 0
    ssm_state: int = 16
    ssm_heads: int = 0

    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 0            # frames from the (stubbed) conv frontend

    # vlm
    vision_seq: int = 0             # patch embeddings from the stubbed frontend

    # embedding / misc
    ffn_kind: str = "swiglu"        # swiglu | gelu (whisper)
    tie_embeddings: bool = False
    embed_scale: bool = False
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    axes: Axes = Axes(batch=(), model=None)
    remat: str = "block"            # none | block — activation checkpoint policy
    fsdp: bool = False              # params sharded over DP (train); grads follow
    xdma_cache: bool = False        # XDMA layout-optimal KV cache: K stored as
                                    # K^T (B,KV,hd,S), V as (B,KV,S,hd) — the
                                    # paper's relayout-on-store applied to serving

    # -- derived ------------------------------------------------------------
    @property
    def n_layers(self) -> int:
        return (len(self.lead) + len(self.period) * self.n_periods
                + len(self.tail))

    @property
    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        """Every distinct slot kind: lead, period and tail."""
        return self.lead + self.period + self.tail

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the experts held here."""
        return self.experts_held or (0, self.n_experts)

    def with_axes(self, axes: Axes) -> "ModelConfig":
        return dataclasses.replace(self, axes=axes)

    def validate(self) -> None:
        assert self.n_heads % self.n_kv_heads == 0, (self.n_heads, self.n_kv_heads)
        if any(l.moe for l in self.layer_specs):
            assert self.n_experts > 0 and self.top_k > 0 and self.d_ff_expert > 0
            first, count = self.held_experts
            assert 0 <= first and 0 < count and first + count <= self.n_experts, \
                (self.experts_held, self.n_experts)
            assert self.router_score in ("softmax", "sigmoid"), self.router_score
        if any(l.kind == MAMBA for l in self.layer_specs):
            assert self.ssm_d_inner > 0 and self.ssm_heads > 0
        if any(l.kind == MLA for l in self.layer_specs):
            assert (self.kv_lora_rank > 0 and self.qk_nope_head_dim > 0
                    and self.qk_rope_head_dim > 0 and self.v_head_dim > 0)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One (input-shape) cell: what gets lowered in the dry-run."""

    name: str                       # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode
    microbatches: int = 1           # gradient-accumulation steps (train only)


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train", microbatches=8),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
