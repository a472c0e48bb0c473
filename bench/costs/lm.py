"""Least FLOPs and bytes of a decoder LM's work, from its sizes.

``m`` is ``bench.weights.dims(cfg)``.  A count here is what the algorithm
needs and no more: each weight read once per step, only the valid cache
positions, logits only where the program needs them, no recomputation.  A
share of a peak built on these counts therefore stays under 100% of the
true time.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    """Matmul weights of one block: q, k, v, o projections and the SwiGLU."""
    d, H, KV, hd, F = m["d"], m["H"], m["KV"], m["hd"], m["F"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F


def param_count(m: dict) -> int:
    """Every weight: blocks (with norms and biases), embedding, final norm."""
    L, d, H, KV, hd = m["L"], m["d"], m["H"], m["KV"], m["hd"]
    per_layer = layer_matmul_params(m) + 2 * d
    if m["qk_norm"]:
        per_layer += 2 * hd
    if m["qkv_bias"]:
        per_layer += (H + 2 * KV) * hd
    return L * per_layer + m["V"] * d + d


def attention_flops(m: dict, keys: int) -> int:
    """q.k and p.v for one query over ``keys`` positions, all layers."""
    return 4 * m["L"] * m["H"] * m["hd"] * keys


def prefill_flops(m: dict, prompt_len: int) -> int:
    """One prompt: every block on every token, causal attention, and the
    output head on the last position only (the program's prefill)."""
    S = prompt_len
    return (2 * m["L"] * layer_matmul_params(m) * S
            + attention_flops(m, 1) * S * (S + 1) // 2
            + 2 * m["d"] * m["V"])


def decode_flops(m: dict, pos: int) -> int:
    """One generated token whose cache holds ``pos`` earlier positions."""
    return (2 * m["L"] * layer_matmul_params(m)
            + attention_flops(m, pos + 1) + 2 * m["d"] * m["V"])


def kv_bytes_per_position(m: dict, itemsize: int) -> int:
    """K and V of one token over all layers."""
    return 2 * m["L"] * m["KV"] * m["hd"] * itemsize
