"""Least FLOPs and bytes of a Qwen3-MoE decoder's work at one chip's expert
share, from its sizes.

``m`` is ``bench.weights_moe.dims(cfg)``.  As in ``bench/costs/lm.py``, a
count is what the algorithm needs and no more.  Attention, the router and
the head count on every token (the head on prefill's last position only);
the experts count on the assignments they received (the ``moe`` bank's
``assignments_held``), and a decode step reads only the held experts that
some token reached (``decode_experts_touched``), so a share of a peak
built on these counts stays under 100% of the true time.
"""
from __future__ import annotations

from bench.costs.lm import attention_flops


def layer_matmul_params(m: dict) -> int:
    """Matmul weights of one block that every token uses: the q, k, v, o
    projections and the router over all ``E`` experts."""
    d, H, KV, hd = m["d"], m["H"], m["KV"], m["hd"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + d * m["E"]


def expert_params(m: dict) -> int:
    """One expert's SwiGLU: gate, up and down."""
    return 3 * m["d"] * m["F"]


def shared_param_count(m: dict) -> int:
    """Every weight but the experts and the embedding table: blocks (with
    norms), output head, final norm: what a decode step reads whole."""
    per_layer = layer_matmul_params(m) + 2 * m["d"] + 2 * m["hd"]
    return m["L"] * per_layer + m["d"] * m["V"] + m["d"]


def param_count(m: dict) -> int:
    """Every weight held here: shared, held experts, embedding table."""
    return (shared_param_count(m) + m["L"] * m["n"] * expert_params(m)
            + m["V"] * m["d"])


def prefill_flops(m: dict, prompt_len: int) -> int:
    """One prompt but its expert work: attention and router on every token,
    causal attention, the head on the last position."""
    S = prompt_len
    return (2 * m["L"] * layer_matmul_params(m) * S
            + attention_flops(m, 1) * S * (S + 1) // 2
            + 2 * m["d"] * m["V"])


def decode_flops(m: dict, pos: int) -> int:
    """One generated token but its expert work, its cache holding ``pos``
    earlier positions."""
    return (2 * m["L"] * layer_matmul_params(m)
            + attention_flops(m, pos + 1) + 2 * m["d"] * m["V"])


def expert_flops(m: dict, assignments_held: int) -> int:
    """The held experts' work on the assignments they received."""
    return 2 * expert_params(m) * assignments_held


def decode_weight_bytes(m: dict, itemsize: int, steps: int,
                        experts_touched: int) -> int:
    """Weights of ``steps`` decode steps: the shared ones once a step, and
    one read of an expert per (step, layer) that some token reached."""
    return itemsize * (steps * shared_param_count(m)
                       + experts_touched * expert_params(m))
