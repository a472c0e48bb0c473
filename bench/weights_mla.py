"""DeepSeek-V3-architecture weights (multi-head latent attention, sigmoid-
routed experts beside shared ones, leading dense layers) at one chip's
expert share, drawn from a seed.

The MLA counterpart of ``bench/weights_moe.py``: the benchmark's own names
and shapes (``shapes``), read directly by the plain reference
(``bench/reference/mla.py``) and mapped by ``serve_rounds_mla.py`` onto
the program's parameter tree.  The leading dense layers' weights are named
``lead<i>.<name>``, one unstacked set each; the sparse layers' are stacked
under the plain names.  The router keeps the published width (all experts)
and its per-expert correction bias, drawn nonzero so that choosing experts
(by score plus bias) and weighting them (by score alone) differ; the expert
weights are the held ones only.  Every leaf comes from one jitted call,
``jax.random.normal`` in the served dtype times a fixed scale, so there is
no float32 copy of the model at any point.
"""
from __future__ import annotations

from bench import weights

# the correction bias's spread: about the gap between neighbouring top
# sigmoid scores at the router's logit spread (about 1), so it changes
# which experts some tokens choose without deciding every choice
ROUTER_BIAS_STD = 0.05

DENSE_KEYS = ("w_gate", "w_up", "w_down")     # a leading layer's SwiGLU


def dims(cfg: dict) -> dict:
    """The sizes a DeepSeek-V3-architecture decoder needs, read from its
    config file: ``d``, ``H``, ``V``, ``L`` (all layers), ``Ld`` (leading
    dense layers), ``Fd`` (their width), ``r`` (``kv_lora_rank``), ``dn``,
    ``dr``, ``dv`` (query/key no-rope and rope, value head dims), ``F``
    (expert width), ``Fs`` (the shared experts' width), ``E`` the router's
    width, ``k`` experts per token, ``scale`` the routed scale, and
    ``first``/``n`` the held experts."""
    if not (cfg["model_type"] == "deepseek_v3" and cfg["q_lora_rank"] is None
            and cfg["scoring_func"] == "sigmoid"
            and cfg["topk_method"] == "noaux_tc"
            and cfg["n_group"] == cfg["topk_group"] == 1
            and cfg["norm_topk_prob"] and cfg["moe_layer_freq"] == 1
            and cfg["rope_scaling"] is None and cfg["rope_interleave"]
            and cfg["hidden_act"] == "silu" and not cfg["attention_bias"]
            and not cfg["tie_word_embeddings"]):
        raise ValueError("serves DeepSeek-V3-architecture models without "
                         "query compression, with one-group noaux_tc sigmoid "
                         "routing, interleaved unscaled rope, untied head")
    first, n = (int(v) for v in cfg["experts_held"])
    if n != cfg["n_routed_experts"]:
        raise ValueError(f"n_routed_experts {cfg['n_routed_experts']} != "
                         f"held {n}")
    return dict(d=cfg["hidden_size"], H=cfg["num_attention_heads"],
                V=cfg["vocab_size"], L=cfg["num_hidden_layers"],
                Ld=int(cfg["first_k_dense_replace"]),
                Fd=cfg["intermediate_size"], r=cfg["kv_lora_rank"],
                dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
                dv=cfg["v_head_dim"], F=cfg["moe_intermediate_size"],
                Fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                E=int(cfg["published"]["n_routed_experts"]),
                k=int(cfg["num_experts_per_tok"]), first=first, n=n,
                scale=float(cfg["routed_scaling_factor"]),
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def _attn_shapes(m: dict, lead: tuple = ()) -> dict:
    """One layer's attention and norms, each shape behind ``lead``."""
    d, H, r, dn, dr, dv = (m[k] for k in ("d", "H", "r", "dn", "dr", "dv"))
    return {
        "ln1": (lead + (d,), "norm"),
        "wq": (lead + (d, H * (dn + dr)), "matrix"),
        "wkv_a": (lead + (d, r + dr), "matrix"),
        "kv_norm": (lead + (r,), "norm"),
        "wkv_b": (lead + (r, H * (dn + dv)), "matrix"),
        "wo": (lead + (H * dv, d), "matrix"),
        "ln2": (lead + (d,), "norm"),
    }


def shapes(cfg: dict) -> dict:
    """name -> (shape, kind), kinds as in ``bench.weights.shapes`` plus
    'router_bias'; the untied head is drawn like the embedding."""
    m = dims(cfg)
    d, V, Lm, Fd, F, Fs, E, n = (m["d"], m["V"], m["L"] - m["Ld"], m["Fd"],
                                 m["F"], m["Fs"], m["E"], m["n"])
    out = {"embed": ((V, d), "embed"), "head": ((d, V), "embed"),
           "final_norm": ((d,), "norm")}
    for i in range(m["Ld"]):
        layer = dict(_attn_shapes(m), w_gate=((d, Fd), "matrix"),
                     w_up=((d, Fd), "matrix"), w_down=((Fd, d), "matrix"))
        out.update({f"lead{i}.{k}": v for k, v in layer.items()})
    out.update(_attn_shapes(m, (Lm,)))
    out.update({
        "router": ((Lm, d, E), "matrix"),
        "router_bias": ((Lm, E), "router_bias"),
        "w_gate": ((Lm, n, d, F), "matrix"),
        "w_up": ((Lm, n, d, F), "matrix"),
        "w_down": ((Lm, n, F, d), "matrix"),
        "shared_gate": ((Lm, d, Fs), "matrix"),
        "shared_up": ((Lm, d, Fs), "matrix"),
        "shared_down": ((Lm, Fs, d), "matrix"),
    })
    return out


def make(cfg: dict, seed: int, dtype_name: str):
    """Every weight of ``cfg`` from ``seed``, as ``dtype_name`` device arrays,
    in one jitted call (fan-in scaling of a matrix is its second-last axis)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    spec = shapes(cfg)
    scale = {"embed": weights.EMBED_STD, "router_bias": ROUTER_BIAS_STD}

    def draw(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(spec.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            if kind == "matrix":
                out[name] = z * jnp.asarray(shape[-2] ** -0.5, dtype)
            elif kind == "norm":
                out[name] = (jnp.asarray(1, dtype)
                             + z * jnp.asarray(weights.NORM_NOISE, dtype))
            else:
                out[name] = z * jnp.asarray(scale[kind], dtype)
        return out

    return jax.jit(draw)(weights.seed_key(seed))
