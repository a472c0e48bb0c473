"""Model weights drawn from a seed, on the device, in the served dtype.

The names and shapes are the benchmark's own (``shapes``), so the plain
reference reads them directly; a driver maps them onto the program's
parameter tree.  All leaves come from one jitted call: each named leaf is
``jax.random.normal`` in the served dtype times a fixed scale, so there is
no float32 copy of the model at any point.
"""
from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A JAX key from any non-negative whole number (wider than 32 bits)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def dims(cfg: dict) -> dict:
    """The sizes a decoder LM needs, read from a Hugging Face config."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, H=H, KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // H,
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"],
                qk_norm=cfg["model_type"] == "qwen3",
                qkv_bias=cfg["model_type"] == "qwen2",
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind) with kind 'matrix' (fan-in scaled), 'embed',
    'norm' (1 + small noise) or 'bias' (small noise)."""
    m = dims(cfg)
    d, H, KV, hd, F, V, L = (m[k] for k in ("d", "H", "KV", "hd", "F", "V", "L"))
    out = {
        "embed": ((V, d), "embed"),
        "final_norm": ((d,), "norm"),
        "ln1": ((L, d), "norm"),
        "ln2": ((L, d), "norm"),
        "wq": ((L, d, H * hd), "matrix"),
        "wk": ((L, d, KV * hd), "matrix"),
        "wv": ((L, d, KV * hd), "matrix"),
        "wo": ((L, H * hd, d), "matrix"),
        "w_gate": ((L, d, F), "matrix"),
        "w_up": ((L, d, F), "matrix"),
        "w_down": ((L, F, d), "matrix"),
    }
    if m["qk_norm"]:
        out["q_norm"] = ((L, hd), "norm")
        out["k_norm"] = ((L, hd), "norm")
    if m["qkv_bias"]:
        out["bq"] = ((L, H * hd), "bias")
        out["bk"] = ((L, KV * hd), "bias")
        out["bv"] = ((L, KV * hd), "bias")
    return out


# embedding std: logits x.E^T then have a spread of about 0.1 * sqrt(d),
# a few units, like a trained model's, so greedy tokens are not near-ties
EMBED_STD = 0.1
NORM_NOISE = 0.1
BIAS_STD = 0.02


def make(cfg: dict, seed: int, dtype_name: str):
    """Every weight of ``cfg`` from ``seed``, as ``dtype_name`` device arrays,
    in one jitted call."""
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
                out[name] = z * jnp.asarray(EMBED_STD, dtype)
            elif kind == "norm":
                out[name] = jnp.asarray(1, dtype) + z * jnp.asarray(NORM_NOISE, dtype)
            else:
                out[name] = z * jnp.asarray(BIAS_STD, dtype)
        return out

    return jax.jit(draw)(seed_key(seed))
