"""input_specs: ShapeDtypeStruct stand-ins for every (arch x shape) cell.

No device allocation: specs feed ``jax.jit(...).lower()`` in the dry-run and
``jax.eval_shape`` everywhere else.  Modality frontends are stubs per the
assignment: VLM cells get precomputed patch embeddings (+3-axis M-RoPE ids),
audio cells get precomputed frame embeddings.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .base import ModelConfig, ShapeConfig


def batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Model-input ShapeDtypeStructs for a train/prefill step."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    specs: Dict[str, Any] = {}
    if cfg.family == "vlm":
        specs["embeds"] = sds((B, S, cfg.d_model), jnp.bfloat16)
        specs["positions"] = sds((3, B, S), jnp.int32)
    elif cfg.family == "audio":
        specs["audio_embeds"] = sds((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        specs["tokens"] = sds((B, S), jnp.int32)
    else:
        specs["tokens"] = sds((B, S), jnp.int32)
    if shape.kind == "train":
        specs["labels"] = sds((B, S), jnp.int32)
    return specs


def decode_token_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    B = shape.global_batch
    sds = jax.ShapeDtypeStruct
    if cfg.family == "vlm":
        return {"embeds": sds((B, 1, cfg.d_model), jnp.bfloat16)}
    return {"tokens": sds((B, 1), jnp.int32)}


def count_params(cfg: ModelConfig) -> Tuple[int, int]:
    """(total, active) parameter counts, from abstract init (no allocation);
    with ``experts_held`` set, of the held experts' share."""
    from repro.models import lm
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
    # active = total minus the (1 - k/E) fraction of expert weights
    expert = 0
    def walk(tree, path=()):
        nonlocal expert
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, path + (k,))
        elif isinstance(tree, (tuple, list)):
            for i, v in enumerate(tree):
                walk(v, path + (str(i),))
        else:
            if any(k in ("w_gate", "w_up", "w_down") for k in path) and \
               "ffn" in path and cfg.n_experts:
                if tree.shape and tree.shape[-3:-2] != () and len(tree.shape) >= 3 \
                   and cfg.held_experts[1] in tree.shape:
                    expert += math.prod(tree.shape)
    walk(shapes)
    active = total - expert + (expert * cfg.top_k // max(cfg.n_experts, 1))
    return total, active
