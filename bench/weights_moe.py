"""Qwen3-MoE weights at one chip's expert share, drawn from a seed.

The MoE counterpart of ``bench/weights.py``: the benchmark's own names and
shapes (``shapes``), read directly by the plain reference
(``bench/reference/moe.py``) and mapped by ``serve_rounds_moe.py`` onto
the program's parameter tree.  The router keeps the published width (all experts); the
expert weights are the held ones only.  Every leaf comes from one jitted
call, ``jax.random.normal`` in the served dtype times a fixed scale, so
there is no float32 copy of the model at any point.
"""
from __future__ import annotations

from bench import weights


def dims(cfg: dict) -> dict:
    """The sizes a Qwen3-MoE decoder needs, read from its config file:
    those of ``bench.weights.dims`` with ``F`` the expert width, plus ``E``
    the router's width, ``k`` experts per token and ``first``/``n`` the
    held experts."""
    if not (cfg["norm_topk_prob"] and cfg["decoder_sparse_step"] == 1
            and not cfg["mlp_only_layers"]):
        raise ValueError("serves Qwen3-MoE with renormalised top-k gates "
                         "and every layer sparse")
    first, n = (int(v) for v in cfg["experts_held"])
    if n != cfg["num_experts"]:
        raise ValueError(f"num_experts {cfg['num_experts']} != held {n}")
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, H=H, KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // H,
                F=cfg["moe_intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"],
                E=int(cfg["published"]["num_experts"]),
                k=int(cfg["num_experts_per_tok"]), first=first, n=n,
                qk_norm=True,             # Qwen3's attention always has it
                qkv_bias=bool(cfg["attention_bias"]),
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind), kinds as in ``bench.weights.shapes``; the
    untied head is drawn like the embedding, so logits spread as in the
    tied dense cells."""
    m = dims(cfg)
    d, H, KV, hd, F, V, L, E, n = (
        m[k] for k in ("d", "H", "KV", "hd", "F", "V", "L", "E", "n"))
    if m["qkv_bias"]:
        raise ValueError("Qwen3-MoE has no attention bias")
    return {
        "embed": ((V, d), "embed"),
        "head": ((d, V), "embed"),
        "final_norm": ((d,), "norm"),
        "ln1": ((L, d), "norm"),
        "ln2": ((L, d), "norm"),
        "wq": ((L, d, H * hd), "matrix"),
        "wk": ((L, d, KV * hd), "matrix"),
        "wv": ((L, d, KV * hd), "matrix"),
        "wo": ((L, H * hd, d), "matrix"),
        "q_norm": ((L, hd), "norm"),
        "k_norm": ((L, hd), "norm"),
        "router": ((L, d, E), "matrix"),
        "w_gate": ((L, n, d, F), "matrix"),
        "w_up": ((L, n, d, F), "matrix"),
        "w_down": ((L, n, F, d), "matrix"),
    }


def make(cfg: dict, seed: int, dtype_name: str):
    """Every weight of ``cfg`` from ``seed``, as ``dtype_name`` device arrays,
    in one jitted call (fan-in scaling of a matrix is its second-last axis)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    spec = shapes(cfg)

    def draw(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(spec.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            if kind == "matrix":
                out[name] = z * jnp.asarray(shape[-2] ** -0.5, dtype)
            elif kind == "embed":
                out[name] = z * jnp.asarray(weights.EMBED_STD, dtype)
            else:
                out[name] = (jnp.asarray(1, dtype)
                             + z * jnp.asarray(weights.NORM_NOISE, dtype))
        return out

    return jax.jit(draw)(weights.seed_key(seed))
