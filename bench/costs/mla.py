"""Least FLOPs and bytes of a DeepSeek-V3-architecture decoder's work
(multi-head latent attention, sigmoid-routed experts beside shared ones,
leading dense layers) at one chip's expert share, from its sizes.

``m`` is ``bench.weights_mla.dims(cfg)``.  As in ``bench/costs/lm.py``, a
count is what the algorithm needs and no more.  Every token runs each
layer's attention projections (``kv_b`` once on its own latent, whether it
decompresses it in prefill or absorbs it in decode), the leading layers'
dense SwiGLU, the sparse layers' router and shared experts, and the head on
prefill's last position only.  Prefill attends decompressed: per causal
pair, q.k over ``dn + dr`` dims and p.v over ``dv`` a head.  Decode attends
absorbed: per cached position, q.k over the ``r + dr`` latent and p.v over
its first ``r`` columns, a head.  The experts count on the assignments they
received (the ``moe`` bank's ``assignments_held``), and a decode step reads
only the held experts that some token reached (``decode_experts_touched``),
so a share of a peak built on these counts stays under 100% of the true
time.
"""
from __future__ import annotations


def attn_params(m: dict) -> int:
    """One layer's attention matmul weights: W_q, W_kv_a, W_kv_b, W_o."""
    d, H, r, dn, dr, dv = (m[k] for k in ("d", "H", "r", "dn", "dr", "dv"))
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def token_matmul_params(m: dict) -> int:
    """Matmul weights every token uses across the layers, but the held
    experts and the head."""
    d, Ld, Lm = m["d"], m["Ld"], m["L"] - m["Ld"]
    return (m["L"] * attn_params(m) + Ld * 3 * d * m["Fd"]
            + Lm * (d * m["E"] + 3 * d * m["Fs"]))


def expert_params(m: dict) -> int:
    """One expert's SwiGLU: gate, up and down."""
    return 3 * m["d"] * m["F"]


def shared_param_count(m: dict) -> int:
    """Every weight but the held experts and the embedding table: what a
    decode step reads whole (norms, the routers' correction biases and the
    output head among them)."""
    d = m["d"]
    norms = m["L"] * (2 * d + m["r"]) + d
    return (token_matmul_params(m) + norms + (m["L"] - m["Ld"]) * m["E"]
            + d * m["V"])


def param_count(m: dict) -> int:
    """Every weight held here: shared, held experts, embedding table."""
    return (shared_param_count(m)
            + (m["L"] - m["Ld"]) * m["n"] * expert_params(m) + m["V"] * m["d"])


def prefill_flops(m: dict, prompt_len: int) -> int:
    """One prompt but its expert work: every token's matmuls, causal
    decompressed attention, the head on the last position."""
    S, H = prompt_len, m["H"]
    pair = 2 * H * (m["dn"] + m["dr"] + m["dv"])
    return (2 * token_matmul_params(m) * S
            + m["L"] * pair * S * (S + 1) // 2 + 2 * m["d"] * m["V"])


def decode_flops(m: dict, pos: int) -> int:
    """One generated token but its expert work, absorbed attention over a
    cache holding ``pos`` earlier positions."""
    per_position = 2 * m["H"] * (2 * m["r"] + m["dr"])
    return (2 * token_matmul_params(m) + m["L"] * per_position * (pos + 1)
            + 2 * m["d"] * m["V"])


def expert_flops(m: dict, assignments_held: int) -> int:
    """The held experts' work on the assignments they received."""
    return 2 * expert_params(m) * assignments_held


def latent_bytes_per_position(m: dict, itemsize: int) -> int:
    """One token's latent (``r + dr`` columns) over all layers."""
    return m["L"] * (m["r"] + m["dr"]) * itemsize


def decode_weight_bytes(m: dict, itemsize: int, steps: int,
                        experts_touched: int) -> int:
    """Weights of ``steps`` decode steps: the shared ones once a step, and
    one read of an expert per (step, layer) that some token reached."""
    return itemsize * (steps * shared_param_count(m)
                       + experts_touched * expert_params(m))
