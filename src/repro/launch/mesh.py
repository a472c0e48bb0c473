"""Production mesh + sharding-spec inference for params / optimizer / caches.

``make_production_mesh`` builds the assignment's meshes: (16, 16) data x model
single pod, (2, 16, 16) pod x data x model for two pods.  All spec inference
is path-based over the param pytree so model code and launcher cannot drift.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.sharding import Axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; found {len(devs)}. "
            "The dry-run launcher sets XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax.")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def axes_for(mesh: Mesh, shape: ShapeConfig) -> Axes:
    """Axis roles for a given input shape on a given mesh (DESIGN.md §5)."""
    names = tuple(mesh.axis_names)
    batch = tuple(n for n in ("pod", "data") if n in names)
    model = "model" if "model" in names else None
    dp = 1
    for n in batch:
        dp *= mesh.shape[n]
    seq = None
    if shape.kind == "decode" and (shape.global_batch < dp
                                   or shape.seq_len >= (1 << 18)):
        # long-context decode: batch can't fill DP -> context-parallel cache
        batch = tuple(n for n in batch if n == "pod")
        if shape.global_batch < 2:
            batch = ()
        seq = "data"
    msize = mesh.shape[model] if model else 0
    bsize = 1
    for n in batch:
        bsize *= mesh.shape[n]
    return Axes(batch=batch, model=model, seq=seq, model_size=msize,
                batch_size=bsize if batch else 0)


# ---------------------------------------------------------------------------
# parameter / optimizer / cache specs
# ---------------------------------------------------------------------------
_COL = re.compile(r"^(wq|wk|wv|bq|bk|bv|w_gate|w_up|b_up|w_z|w_x|conv_w)$")
_ROW = re.compile(r"^(wo|w_down|w_out|b_down)$")


def _param_rule(path: Tuple[str, ...], ndim: int, axes: Axes,
                shape: Tuple[int, ...] = ()) -> P:
    m = axes.model
    name = path[-1]
    stacked = 1 if any(p in ("blocks", "encoder") for p in path) else 0
    lead = (None,) * stacked

    def pad(spec):  # right-pad to ndim, then strip trailing Nones (canonical)
        spec = lead + spec
        spec = spec + (None,) * (ndim - len(spec))
        while spec and spec[-1] is None:
            spec = spec[:-1]
        return P(*spec)

    if name in ("embed",):
        return pad((m, None))
    if name == "head":
        return pad((None, m))
    if name == "router":
        return pad((None, None))
    if "ffn" in path and name in ("w_gate", "w_up", "w_down") and ndim - stacked == 3:
        n_exp = shape[stacked] if shape else 0
        if axes.model_size and n_exp and n_exp % axes.model_size == 0:
            return pad((m, None, None))      # experts over model (EP)
        if name == "w_down":
            return pad((None, m, None))      # TP experts: d_ff sharded
        return pad((None, None, m))
    if name.startswith("r_") and ndim - stacked == 3:
        return pad((m, None, None))          # sLSTM recurrent per-head
    if _COL.match(name):
        if ndim - stacked == 1:
            return pad((m,))
        return pad((None, m))
    if _ROW.match(name):
        if ndim - stacked == 1:
            return pad((None,))
        return pad((m, None))
    if name in ("w_B", "w_C", "w_dt"):
        return pad((None, None))
    if name == "norm" and "mamba" in path:
        return pad((m,))
    return pad(())                            # scales, biases, scalars: replicated


def _paths_and_leaves(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat[0]:
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        yield keys, leaf
    return


def infer_param_specs(params, axes: Axes, *, fsdp: bool = False,
                      fsdp_min_elems: int = 1 << 20):
    """TP specs from path rules; with ``fsdp=True`` large leaves additionally
    shard a free dimension over the DP axes (ZeRO-3 / FSDP via GSPMD: XLA
    inserts the all-gather at use).  Serving keeps fsdp=False (replicated)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        spec = _param_rule(keys, leaf.ndim, axes, tuple(leaf.shape))
        if fsdp and axes.batch and leaf.ndim >= 2 and leaf.size >= fsdp_min_elems:
            dp = max(1, axes.batch_size)
            parts = list(tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec))))
            for i, ax in enumerate(parts):
                if ax is None and leaf.shape[i] % dp == 0 and leaf.shape[i] >= dp:
                    parts[i] = axes.batch_spec
                    break
            spec = P(*parts)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def infer_state_specs(state_shapes, axes: Axes, *, zero: bool = True,
                      fsdp: bool = True):
    """Specs for {"params","opt","step"}; FSDP shards params over DP axes,
    ZeRO shards Adam moments of any still-replicated dims over DP."""
    pspecs = infer_param_specs(state_shapes["params"], axes, fsdp=fsdp)

    def zero_spec(spec: P, leaf) -> P:
        if not zero or not axes.batch or leaf.ndim < 2:
            return spec
        parts = tuple(spec) + (None,) * (leaf.ndim - len(spec))
        if parts[0] is None:
            return P(*( (axes.batch_spec,) + parts[1:]))
        return P(*parts)

    mu = jax.tree.map(zero_spec, pspecs,
                      state_shapes["opt"]["mu"])
    nu = jax.tree.map(zero_spec, pspecs, state_shapes["opt"]["nu"])
    return {"params": pspecs,
            "opt": {"mu": mu, "nu": nu, "count": P()},
            "step": P()}


def cache_specs(cfg: ModelConfig, cache_shapes, axes: Axes):
    """Specs mirroring models.lm.init_cache structure."""
    from repro.sharding import kv_cache_spec
    b = axes.batch_spec
    m = axes.model
    s = axes.seq
    k_layout = "bkhs" if cfg.xdma_cache else "bshd"
    v_layout = "bksh" if cfg.xdma_cache else "bshd"
    k_spec = tuple(kv_cache_spec(axes, cfg.n_kv_heads, k_layout))
    v_spec = tuple(kv_cache_spec(axes, cfg.n_kv_heads, v_layout))
    cross_spec = tuple(kv_cache_spec(axes, cfg.n_kv_heads, "bshd"))

    def rule(path: Tuple[str, ...], ndim: int) -> P:
        stacked = 1 if path[0] in ("blocks", "cross") else 0
        lead = (None,) * stacked
        name = path[-1]
        if name in ("k", "v"):
            if path[0] == "cross":
                return P(*(lead + cross_spec))
            return P(*(lead + (k_spec if name == "k" else v_spec)))
        if name == "conv":
            return P(*(lead + (b, None, m)))
        if name == "h":                        # mamba state (B,Hm,P,N)
            return P(*(lead + (b, m, None, None)))
        if "mlstm" in path:                    # (B,H,hd,hd)/(B,H,hd)/(B,H)
            return P(*((lead + (b, m) + (None,) * (ndim - stacked - 2))))
        if "slstm" in path:                    # (B, H*hd)
            return P(*(lead + (b, m)))
        if name in ("pos", "len"):
            return P(*(lead if name == "len" else ()))
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    specs = []
    for path, leaf in flat:
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        specs.append(rule(keys, leaf.ndim))
    return jax.tree_util.tree_unflatten(treedef, specs)


def fit_specs(mesh: Mesh, spec_tree, shape_tree):
    """Drop spec axes whose size doesn't divide the dimension (jit boundary
    requires even sharding; internal constraints pad instead).  E.g. kv=2
    heads cannot shard over model=16 -> that dim is replicated at the input."""
    import math as _m

    def ax_size(ax):
        names = ax if isinstance(ax, tuple) else (ax,)
        return _m.prod(mesh.shape[n] for n in names)

    def fit(spec, leaf):
        if not isinstance(spec, P):
            return spec
        parts = (tuple(spec) + (None,) * leaf.ndim)[:leaf.ndim]
        new = [ax if (ax is not None and leaf.shape[i] % ax_size(ax) == 0)
               else None for i, ax in enumerate(parts)]
        return P(*new)

    return jax.tree.map(fit, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_input_specs(batch_shapes, axes: Axes):
    b = axes.batch_spec

    def rule(keys, leaf):
        if keys[-1] == "positions":           # (3, B, S)
            return P(None, b, None)
        if leaf.ndim >= 3:                    # embeds / audio_embeds
            return P(b, None, None)
        return P(b, None)

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_shapes)
    specs = []
    for path, leaf in flat:
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        specs.append(rule(keys, leaf))
    return jax.tree_util.tree_unflatten(treedef, specs)
