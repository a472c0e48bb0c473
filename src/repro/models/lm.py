"""Scan-stacked decoder LM covering dense / MoE / hybrid / SSM / VLM families.

The depth dimension is ``n_periods`` scanned copies of a heterogeneous
``period`` (tuple of LayerSpec) between an optional unstacked ``lead`` and
an optional unstacked ``tail``; params and caches for the period are stacked
pytrees threaded through ``lax.scan`` (xs -> ys), so HLO size is O(period),
not O(depth).  The params' and caches' ``lead`` keys exist only where the
config has leading layers.

Modes:
  forward(...)                       train / prefill logits (+ MoE aux)
  prefill(...)                       logits + filled decode cache
  decode_step(...)                   one token with cache
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import (ATTN, MAMBA, MLA, MLSTM, SLSTM, LayerSpec,
                                ModelConfig)
from repro.layers import attention as A
from repro.layers import embedding as E
from repro.layers import mamba as M
from repro.layers import mla as LA
from repro.layers import mlp as F
from repro.layers import moe as MOE
from repro.layers import xlstm as X
from repro.layers.norms import init_rms, rms_norm
from repro.sharding import constrain, P


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_slot(key, cfg: ModelConfig, spec: LayerSpec):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm_mix": init_rms(ks[0], cfg.d_model)}
    if spec.kind == ATTN:
        p["attn"] = A.init_attn(ks[1], cfg)
        if cfg.encoder_layers:          # decoder w/ cross-attention (whisper)
            p["norm_cross"] = init_rms(ks[3], cfg.d_model)
            p["cross"] = A.init_attn(jax.random.fold_in(ks[1], 7), cfg, cross=True)
    elif spec.kind == MLA:
        p["attn"] = LA.init_mla(ks[1], cfg)
    elif spec.kind == MAMBA:
        p["mamba"] = M.init_mamba(ks[1], cfg)
    elif spec.kind == MLSTM:
        p["mlstm"] = X.init_mlstm(ks[1], cfg)
    elif spec.kind == SLSTM:
        p["slstm"] = X.init_slstm(ks[1], cfg)
    if spec.ffn:
        p["norm_ffn"] = init_rms(ks[2], cfg.d_model)
        if spec.moe:
            p["ffn"] = MOE.init_moe(ks[2], cfg)
        elif cfg.ffn_kind == "gelu":
            p["ffn"] = F.init_gelu_mlp(ks[2], cfg.d_model, cfg.d_ff)
        else:
            p["ffn"] = F.init_swiglu(ks[2], cfg.d_model, cfg.d_ff)
    return p


def init_params(key, cfg: ModelConfig):
    cfg.validate()
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {"embed": E.init_embed(keys[0], cfg)}
    blocks = []
    for si, spec in enumerate(cfg.period):
        kslot = jax.random.fold_in(keys[1], si)
        stacked = jax.vmap(lambda k: _init_slot(k, cfg, spec))(
            jax.random.split(kslot, cfg.n_periods))
        blocks.append(stacked)
    params["blocks"] = tuple(blocks)
    params["tail"] = tuple(
        _init_slot(jax.random.fold_in(keys[2], ti), cfg, spec)
        for ti, spec in enumerate(cfg.tail))
    params["norm_final"] = init_rms(keys[3], cfg.d_model)
    if cfg.lead:
        params["lead"] = tuple(
            _init_slot(jax.random.fold_in(keys[6], li), cfg, spec)
            for li, spec in enumerate(cfg.lead))
    if cfg.encoder_layers:
        enc_spec = LayerSpec(ATTN)
        enc_cfg = dataclasses.replace(cfg, encoder_layers=0)  # no cross in encoder
        params["encoder"] = jax.vmap(lambda k: _init_slot(k, enc_cfg, enc_spec))(
            jax.random.split(keys[4], cfg.encoder_layers))
        params["enc_norm"] = init_rms(keys[5], cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# one sublayer slot
# ---------------------------------------------------------------------------
def _constrain_slot_params(cfg, tree):
    """Pin each weight to its TP/FSDP sharding *inside* the layer scan (so
    backward reduce-scatters instead of full all-reduces), then cast matrices
    to the compute dtype so FSDP all-gathers and weight-grad syncs move bf16,
    not f32 (the f32 master stays outside the loop)."""
    if cfg.axes.model is None and not cfg.axes.batch:
        return tree
    from repro.launch.mesh import infer_param_specs
    from repro.sharding import constrain as _c
    specs = infer_param_specs(tree, cfg.axes, fsdp=cfg.fsdp)
    tree = jax.tree.map(_c, tree, specs)
    cast = lambda w: (w.astype(cfg.dtype)
                      if w.ndim >= 2 and jnp.issubdtype(w.dtype, jnp.floating)
                      else w)
    return jax.tree.map(cast, tree)


def _apply_slot(cfg, spec: LayerSpec, p, x, positions, *, cache=None,
                cache_pos=None, enc_out=None, cross_cache=None, mesh=None,
                causal=True, counts=None):
    """One slot.  Returns (x, new cache, aux, counts): ``counts`` as passed
    in, plus a MoE slot's routing counts when it is an array."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    h = rms_norm(x, p["norm_mix"]["scale"], cfg.norm_eps)
    if spec.kind == ATTN:
        kv_cache = None if cache is None else {"k": cache["k"], "v": cache["v"]}
        out, kv_cache = A.attn_apply(cfg, p["attn"], h, positions,
                                     causal=causal, window=spec.window,
                                     cache=kv_cache, cache_pos=cache_pos)
        if kv_cache is not None:
            new_cache.update(kv_cache)
        x = x + out
        if enc_out is not None or cross_cache is not None:
            hc = rms_norm(x, p["norm_cross"]["scale"], cfg.norm_eps)
            out, _ = A.attn_apply(cfg, p["cross"], hc, positions,
                                  causal=False, kv_x=enc_out,
                                  cache=cross_cache, apply_rope=False,
                                  cross=True)
            x = x + out
    elif spec.kind == MLA:
        if cache is None:
            out, _ = LA.mla_prefill(cfg, p["attn"], h, positions)
        else:
            out, mc = LA.mla_decode(cfg, p["attn"], h, positions, cache,
                                    cache_pos)
            new_cache.update(mc)
        x = x + out
    elif spec.kind == MAMBA:
        out, mc = M.mamba_apply(cfg, p["mamba"], h, cache=cache)
        if mc is not None:
            new_cache.update(mc)
        x = x + out
    elif spec.kind == MLSTM:
        out, mc = X.mlstm_apply(cfg, p["mlstm"], h, cache=cache)
        if mc is not None:
            new_cache.update(mc)
        x = x + out
    elif spec.kind == SLSTM:
        out, mc = X.slstm_apply(cfg, p["slstm"], h, cache=cache)
        if mc is not None:
            new_cache.update(mc)
        x = x + out
    x, aux, counts = _ffn_sublayer(cfg, spec, p, x, aux, counts, mesh)
    return x, new_cache, aux, counts


def _ffn_sublayer(cfg, spec: LayerSpec, p, x, aux, counts, mesh):
    """The slot's FFN (dense or MoE) with its residual.  ``counts`` (None,
    or an int32 (3,) running total) gains a MoE slot's routing counts."""
    if not spec.ffn:
        return x, aux, counts
    h = rms_norm(x, p["norm_ffn"]["scale"], cfg.norm_eps)
    if spec.moe and counts is not None:
        out, a, n = MOE.moe_apply(cfg, p["ffn"], h, mesh=mesh, with_counts=True)
        aux, counts = aux + a, counts + n
    elif spec.moe:
        out, a = MOE.moe_apply(cfg, p["ffn"], h, mesh=mesh)
        aux = aux + a
    elif cfg.ffn_kind == "gelu":
        out = F.gelu_mlp(cfg, p["ffn"], h)
    else:
        out = F.swiglu(cfg, p["ffn"], h)
    return x + out, aux, counts


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------
def _slot_cache(cfg, spec: LayerSpec, B, max_len, dtype):
    if spec.kind == ATTN:
        smax = min(spec.window, max_len) if spec.window else max_len
        if cfg.xdma_cache:
            # XDMA layout-optimal: K stored transposed, V dot-contiguous
            return {"k": jnp.zeros((B, cfg.n_kv_heads, cfg.head_dim, smax), dtype),
                    "v": jnp.zeros((B, cfg.n_kv_heads, smax, cfg.head_dim), dtype)}
        kv = (B, smax, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    if spec.kind == MLA:
        return LA.init_latent_cache(cfg, B, max_len, dtype)
    if spec.kind == MAMBA:
        return M.init_mamba_cache(cfg, B, dtype)
    if spec.kind == MLSTM:
        hd, H = cfg.head_dim, cfg.n_heads
        return {"mlstm": (jnp.zeros((B, H, hd, hd), jnp.float32),
                          jnp.zeros((B, H, hd), jnp.float32),
                          jnp.full((B, H), -1e30, jnp.float32))}
    if spec.kind == SLSTM:
        z = jnp.zeros((B, cfg.n_heads * cfg.head_dim), jnp.float32)
        return {"slstm": (z, z, z, jnp.full_like(z, -1e30))}
    raise ValueError(spec.kind)


def init_cache(cfg: ModelConfig, B: int, max_len: int, dtype=jnp.bfloat16):
    stack = lambda tree: jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape), tree)
    cache = {
        "blocks": tuple(stack(_slot_cache(cfg, s, B, max_len, dtype))
                        for s in cfg.period),
        "tail": tuple(_slot_cache(cfg, s, B, max_len, dtype) for s in cfg.tail),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.lead:
        cache["lead"] = tuple(_slot_cache(cfg, s, B, max_len, dtype)
                              for s in cfg.lead)
    if cfg.encoder_layers:
        kv = (B, cfg.encoder_seq, cfg.n_kv_heads, cfg.head_dim)
        cache["cross"] = {
            "k": jnp.zeros((cfg.n_periods,) + kv, dtype),
            "v": jnp.zeros((cfg.n_periods,) + kv, dtype),
            "len": jnp.full((cfg.n_periods,), cfg.encoder_seq, jnp.int32),
        }
    return cache


# ---------------------------------------------------------------------------
# encoder (whisper)
# ---------------------------------------------------------------------------
def _encode(cfg, params, audio_embeds):
    enc_cfg = dataclasses.replace(cfg, encoder_layers=0)
    spec = LayerSpec(ATTN)
    x = audio_embeds.astype(cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])

    def body(x, p):
        p = _constrain_slot_params(enc_cfg, p)
        y, _, _, _ = _apply_slot(enc_cfg, spec, p, x, pos, causal=False)
        return y, None

    if cfg.remat == "block":
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, params["encoder"])
    return rms_norm(x, params["enc_norm"]["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# forward (train / prefill without cache)
# ---------------------------------------------------------------------------
def forward(cfg: ModelConfig, params, batch, *, mesh=None):
    """batch: {tokens (B,S)} or {embeds}, optional {positions}, optional
    {audio_embeds} for enc-dec.  Returns (logits, aux)."""
    if "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = E.embed(cfg, params["embed"], tokens)
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    enc_out = None
    if cfg.encoder_layers:
        enc_out = _encode(cfg, params, batch["audio_embeds"])

    aux_total = jnp.zeros((), jnp.float32)
    for spec, p in zip(cfg.lead, params.get("lead", ())):
        x, _, a, _ = _apply_slot(cfg, spec, p, x, positions, enc_out=enc_out,
                                 mesh=mesh)
        aux_total = aux_total + a

    def block_body(carry, slot_params):
        x, aux = carry
        slot_params = _constrain_slot_params(cfg, slot_params)
        for spec, p in zip(cfg.period, slot_params):
            x, _, a, _ = _apply_slot(cfg, spec, p, x, positions,
                                     enc_out=enc_out, mesh=mesh)
            aux = aux + a
        return (x, aux), None

    body = jax.checkpoint(block_body) if cfg.remat == "block" else block_body
    (x, aux_total), _ = lax.scan(body, (x, aux_total), params["blocks"])

    for spec, p in zip(cfg.tail, params["tail"]):
        x, _, a, _ = _apply_slot(cfg, spec, p, x, positions, enc_out=enc_out,
                                 mesh=mesh)
        aux_total = aux_total + a

    x = rms_norm(x, params["norm_final"]["scale"], cfg.norm_eps)
    logits = E.lm_head(cfg, params["embed"], x)
    return logits, aux_total


# ---------------------------------------------------------------------------
# prefill (fills cache) and decode
# ---------------------------------------------------------------------------
def _zero_counts(on: bool):
    """Routing counts' starting total, or None where none are counted."""
    return jnp.zeros((3,), jnp.int32) if on else None


def prefill(cfg: ModelConfig, params, batch, cache, *, mesh=None,
            moe_counts: bool = False):
    """Run the prompt through the model, writing KV/state caches.

    Returns (logits_last (B,1,V), cache); with ``moe_counts`` also the
    program's routing counts, int32 (3,): assignments and held assignments
    summed over layers (:func:`repro.layers.moe.routing_counts`), and 0 in
    the third place, which counts decode steps' experts only."""
    if "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = E.embed(cfg, params["embed"], tokens)
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    enc_out = None
    if cfg.encoder_layers:
        enc_out = _encode(cfg, params, batch["audio_embeds"])
        # precompute cross K/V per decoder period slot
        def cross_kv(p):
            dt = cfg.dtype
            k = (enc_out @ p["cross"]["wk"].astype(dt)).reshape(
                B, -1, cfg.n_kv_heads, cfg.head_dim)
            v = (enc_out @ p["cross"]["wv"].astype(dt)).reshape(
                B, -1, cfg.n_kv_heads, cfg.head_dim)
            return k, v
        ks, vs = jax.vmap(cross_kv)(params["blocks"][0])
        cache["cross"] = {"k": ks.astype(cfg.dtype), "v": vs.astype(cfg.dtype),
                          "len": cache["cross"]["len"]}

    aux = jnp.zeros((), jnp.float32)
    counts = _zero_counts(moe_counts)
    new_lead = []
    for spec, p, c in zip(cfg.lead, params.get("lead", ()),
                          cache.get("lead", ())):
        x, nc, a, counts = _prefill_slot_correct(
            cfg, spec, p, x, positions, c, enc_out=enc_out, mesh=mesh,
            counts=counts)
        new_lead.append(nc)

    def block_body(carry, xs):
        x, aux, counts = carry
        slot_params, slot_caches = xs
        slot_params = _constrain_slot_params(cfg, slot_params)
        new_caches = []
        for spec, p, c in zip(cfg.period, slot_params, slot_caches):
            x, nc, a, counts = _prefill_slot_correct(
                cfg, spec, p, x, positions, c, enc_out=enc_out, mesh=mesh,
                counts=counts)
            aux = aux + a
            new_caches.append(nc)
        return (x, aux, counts), tuple(new_caches)

    body = jax.checkpoint(block_body) if cfg.remat == "block" else block_body
    (x, aux, counts), new_block_caches = lax.scan(
        body, (x, aux, counts), (params["blocks"], cache["blocks"]))

    new_tail = []
    for spec, p, c in zip(cfg.tail, params["tail"], cache["tail"]):
        x, nc, a, counts = _prefill_slot_correct(
            cfg, spec, p, x, positions, c, enc_out=enc_out, mesh=mesh,
            counts=counts)
        new_tail.append(nc)

    x = rms_norm(x, params["norm_final"]["scale"], cfg.norm_eps)
    logits = E.lm_head(cfg, params["embed"], x[:, -1:])
    cache = dict(cache, blocks=new_block_caches, tail=tuple(new_tail),
                 pos=jnp.asarray(x.shape[1], jnp.int32))
    if cfg.lead:
        cache["lead"] = tuple(new_lead)
    if moe_counts:
        return logits, cache, counts.at[2].set(0)
    return logits, cache


def _write_kv_cache(cfg, spec, attn_p, x_normed, positions, slot_cache):
    """Project K/V from the normed input and write them into the cache
    (rolled for sliding-window layers)."""
    B, S, _ = x_normed.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    dt = slot_cache["k"].dtype
    k = (x_normed @ attn_p["wk"].astype(x_normed.dtype)
         + (attn_p["bk"].astype(x_normed.dtype) if "bk" in attn_p else 0)
         ).reshape(B, S, KV, hd)
    v = (x_normed @ attn_p["wv"].astype(x_normed.dtype)
         + (attn_p["bv"].astype(x_normed.dtype) if "bv" in attn_p else 0)
         ).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        k = rms_norm(k, attn_p["k_norm"])
    from repro.layers.rope import rope_for
    k = rope_for(cfg, k, positions)
    from repro.sharding import kv_cache_spec
    smax = slot_cache["k"].shape[3] if cfg.xdma_cache else slot_cache["k"].shape[1]
    if S >= smax:
        kk, vv = k[:, S - smax:], v[:, S - smax:]
        shift = S % smax
        kk = jnp.roll(kk, shift, axis=1)
        vv = jnp.roll(vv, shift, axis=1)
        if cfg.xdma_cache:
            # relayout fused into the store (paper: transform-on-transfer)
            kk = kk.transpose(0, 2, 3, 1)               # (B,KV,hd,smax)
            vv = vv.transpose(0, 2, 1, 3)               # (B,KV,smax,hd)
            return dict(slot_cache,
                        k=constrain(kk.astype(dt), kv_cache_spec(cfg.axes, KV, "bkhs")),
                        v=constrain(vv.astype(dt), kv_cache_spec(cfg.axes, KV, "bksh")))
        cspec = kv_cache_spec(cfg.axes, KV)
        return dict(slot_cache, k=constrain(kk.astype(dt), cspec),
                    v=constrain(vv.astype(dt), cspec))
    if cfg.xdma_cache:
        kt = k.transpose(0, 2, 3, 1).astype(dt)         # (B,KV,hd,S)
        vt = v.transpose(0, 2, 1, 3).astype(dt)         # (B,KV,S,hd)
        ck = lax.dynamic_update_slice(slot_cache["k"], kt, (0, 0, 0, 0))
        cv = lax.dynamic_update_slice(slot_cache["v"], vt, (0, 0, 0, 0))
        return dict(slot_cache,
                    k=constrain(ck, kv_cache_spec(cfg.axes, KV, "bkhs")),
                    v=constrain(cv, kv_cache_spec(cfg.axes, KV, "bksh")))
    cspec = kv_cache_spec(cfg.axes, KV)
    ck = lax.dynamic_update_slice(slot_cache["k"], k.astype(dt), (0, 0, 0, 0))
    cv = lax.dynamic_update_slice(slot_cache["v"], v.astype(dt), (0, 0, 0, 0))
    return dict(slot_cache, k=constrain(ck, cspec), v=constrain(cv, cspec))


def _prefill_slot_correct(cfg, spec, p, x, positions, slot_cache, *,
                          enc_out=None, mesh=None, counts=None):
    """Apply one slot in prefill mode, producing both output and cache
    (and ``counts`` as in :func:`_apply_slot`)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["norm_mix"]["scale"], cfg.norm_eps)
    new_cache = dict(slot_cache)
    if spec.kind == ATTN:
        out, _ = A.attn_apply(cfg, p["attn"], h, positions, causal=True,
                              window=spec.window)
        new_cache = _write_kv_cache(cfg, spec, p["attn"], h, positions, slot_cache)
        x = x + out
        if enc_out is not None:
            hc = rms_norm(x, p["norm_cross"]["scale"], cfg.norm_eps)
            out, _ = A.attn_apply(cfg, p["cross"], hc, positions, causal=False,
                                  kv_x=enc_out, apply_rope=False)
            x = x + out
    elif spec.kind == MLA:
        out, lat = LA.mla_prefill(cfg, p["attn"], h, positions)
        new_cache, x = LA.write_latent(slot_cache, lat), x + out
    elif spec.kind == MAMBA:
        out, nc = M.mamba_apply(cfg, p["mamba"], h, cache=slot_cache)
        new_cache, x = nc, x + out
    elif spec.kind == MLSTM:
        out, nc = X.mlstm_apply(cfg, p["mlstm"], h, cache=slot_cache)
        new_cache, x = nc, x + out
    elif spec.kind == SLSTM:
        out, nc = X.slstm_apply(cfg, p["slstm"], h, cache=slot_cache)
        new_cache, x = nc, x + out
    x, aux, counts = _ffn_sublayer(cfg, spec, p, x, aux, counts, mesh)
    return x, new_cache, aux, counts


def decode_step(cfg: ModelConfig, params, tokens, cache, *, mesh=None,
                moe_counts: bool = False):
    """One decode step.  tokens (B,1) (or embeds (B,1,d)); returns
    (logits (B,1,V), new cache), and with ``moe_counts`` the step's routing
    counts, int32 (3,), summed over layers
    (:func:`repro.layers.moe.routing_counts`)."""
    pos = cache["pos"]
    if tokens.ndim == 3:
        x = tokens.astype(cfg.dtype)
    else:
        x = E.embed(cfg, params["embed"], tokens)
    B = x.shape[0]
    if getattr(pos, "ndim", 0) >= 1:
        # ragged batch: per-request positions, shape (B,) -> (B, 1)
        positions = pos.astype(jnp.int32)[:, None]
    else:
        positions = jnp.full((B, 1), pos, jnp.int32)
    cross = cache.get("cross")
    counts = _zero_counts(moe_counts)
    new_lead = []
    for spec, p, c in zip(cfg.lead, params.get("lead", ()),
                          cache.get("lead", ())):
        x, nc, _, counts = _apply_slot(cfg, spec, p, x, positions, cache=c,
                                       cache_pos=pos, mesh=mesh, counts=counts)
        new_lead.append(dict(c, **nc))

    def block_body(carry, xs):
        x, counts = carry
        slot_params, slot_caches, cross_xs = xs
        slot_params = _constrain_slot_params(cfg, slot_params)
        new_caches = []
        for spec, p, c in zip(cfg.period, slot_params, slot_caches):
            x, nc, _, counts = _apply_slot(
                cfg, spec, p, x, positions, cache=c, cache_pos=pos,
                cross_cache=None if cross is None else cross_xs, mesh=mesh,
                counts=counts)
            new_caches.append(dict(c, **nc))
        return (x, counts), tuple(new_caches)

    # without cross-attention, dummy per-period xs keep the scan uniform
    cross_xs = (jnp.zeros((cfg.n_periods, 0), jnp.int32) if cross is None
                else cross)
    (x, counts), new_block_caches = lax.scan(
        block_body, (x, counts), (params["blocks"], cache["blocks"], cross_xs))

    new_tail = []
    for spec, p, c in zip(cfg.tail, params["tail"], cache["tail"]):
        x, nc, _, counts = _apply_slot(cfg, spec, p, x, positions, cache=c,
                                       cache_pos=pos, mesh=mesh, counts=counts)
        new_tail.append(dict(c, **nc))

    x = rms_norm(x, params["norm_final"]["scale"], cfg.norm_eps)
    logits = E.lm_head(cfg, params["embed"], x)
    new_cache = dict(cache, blocks=new_block_caches, tail=tuple(new_tail),
                     pos=pos + 1)
    if cfg.lead:
        new_cache["lead"] = tuple(new_lead)
    if moe_counts:
        return logits, new_cache, counts
    return logits, new_cache
