"""Multi-head latent attention (MLA, DeepSeek-V2/V3), without query
compression, over a latent cache.

Per token the layer keeps one latent of ``kv_lora_rank + qk_rope_head_dim``
columns: the RMS-normed compressed key/value ``c_kv`` followed by the
rotary key ``k_pe`` that every head shares.  Both forms of the layer read
and write that one leaf, ``{"latent": (B, S, r + dr)}``:

* prefill (and training) decompresses per head: ``k = [c_kv W_UK ; k_pe]``
  (``qk_nope_head_dim + qk_rope_head_dim`` dims) and ``v = c_kv W_UV``
  (``v_head_dim``), then runs the flash-chunked causal attention;
* decode absorbs ``W_UK`` into the query (``W_UK^T q_nope`` beside ``q_pe``
  is one ``r + dr``-wide query per head), attends over the latent cache as
  one key/value head shared by every query head, takes the value as the
  latent's first ``r`` columns and applies ``W_UV`` after.  The cache is
  never decompressed, which would cost ``r H (dn + dv)`` multiply-adds per
  cached token and step before attending.

Both use the softmax scale ``(qk_nope_head_dim + qk_rope_head_dim)^-1/2``.
The rotary dims rotate as HF's DeepSeek-V3 does with ``rope_interleave``:
the (even, odd) pairs are first gathered into rotate-half order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .attention import _mask_valid, chunked_attention
from .norms import rms_norm
from .rope import apply_rope


def init_mla(key, cfg):
    d, H = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    ks = jax.random.split(key, 4)
    normal = jax.nn.initializers.normal
    return {
        "wq": normal(d ** -0.5)(ks[0], (d, H * (dn + dr)), jnp.float32),
        "wkv_a": normal(d ** -0.5)(ks[1], (d, r + dr), jnp.float32),
        "kv_norm": jnp.ones((r,), jnp.float32),
        "wkv_b": normal(r ** -0.5)(ks[2], (r, H * (dn + dv)), jnp.float32),
        "wo": normal((H * dv) ** -0.5)(ks[3], (H * dv, d), jnp.float32),
    }


def init_latent_cache(cfg, B: int, max_len: int, dtype):
    return {"latent": jnp.zeros(
        (B, max_len, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dtype)}


def _rope(cfg, x, positions):
    """Interleaved rotary embedding of x (B, S, heads, dr) at positions
    (B, S)."""
    *lead, dr = x.shape
    x = x.reshape(*lead, dr // 2, 2).swapaxes(-1, -2).reshape(*lead, dr)
    return apply_rope(x, positions, cfg.rope_theta)


def _queries(cfg, p, x, positions):
    """(q_nope (B, S, H, dn), roped q_pe (B, S, H, dr))."""
    B, S, _ = x.shape
    dn = cfg.qk_nope_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, cfg.n_heads, -1)
    return q[..., :dn], _rope(cfg, q[..., dn:], positions)


def _latent(cfg, p, x, positions):
    """x (B, S, d) -> the cache's latent (B, S, r + dr): normed c_kv, then
    roped k_pe."""
    r = cfg.kv_lora_rank
    ckv = x @ p["wkv_a"].astype(x.dtype)
    c = rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = _rope(cfg, ckv[..., None, r:], positions)[:, :, 0]
    return jnp.concatenate([c, k_pe], axis=-1)


def _split_kv_b(cfg, p, dtype):
    """kv_b's up-projections per head: (W_UK (r, H, dn), W_UV (r, H, dv))."""
    w = p["wkv_b"].astype(dtype).reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_prefill(cfg, p, x, positions):
    """Decompressed causal attention over x (B, S, d).  Returns (y, the
    latent of every position (B, S, r + dr))."""
    B, S, _ = x.shape
    H, r, dr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    q_nope, q_pe = _queries(cfg, p, x, positions)
    lat = _latent(cfg, p, x, positions)
    w_uk, w_uv = _split_kv_b(cfg, p, x.dtype)
    c = lat[..., :r]
    k_nope = jnp.einsum("bsr,rhn->bshn", c, w_uk)
    v = jnp.einsum("bsr,rhv->bshv", c, w_uv)
    k_pe = jnp.broadcast_to(lat[:, :, None, r:], (B, S, H, dr))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    out = chunked_attention(q, k, v, causal=True, q_chunk=min(1024, S),
                            kv_chunk=min(1024, S))
    y = out.reshape(B, S, H * cfg.v_head_dim) @ p["wo"].astype(x.dtype)
    return y, lat


def write_latent(cache, lat):
    """A prefill's latents (B, S, r + dr) into the cache from position 0."""
    return {"latent": lax.dynamic_update_slice(
        cache["latent"], lat.astype(cache["latent"].dtype), (0, 0, 0))}


def mla_decode(cfg, p, x, positions, cache, cache_pos):
    """One token x (B, 1, d) at ``cache_pos`` (a scalar, or (B,) for a
    ragged batch), absorbed.  Writes its latent into ``cache`` and attends
    over the valid prefix.  Returns (y (B, 1, d), new cache)."""
    B = x.shape[0]
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dt = x.dtype
    q_nope, q_pe = _queries(cfg, p, x, positions)
    lat = _latent(cfg, p, x, positions).astype(cache["latent"].dtype)
    Smax = cache["latent"].shape[1]
    slot = jnp.minimum(cache_pos, Smax - 1)
    if getattr(cache_pos, "ndim", 0) >= 1:
        cl = cache["latent"].at[jnp.arange(B), slot].set(lat[:, 0])
    else:
        cl = lax.dynamic_update_slice(cache["latent"], lat, (0, slot, 0))
    w_uk, w_uv = _split_kv_b(cfg, p, dt)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32).astype(dt)
    q = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1)          # (B, H, r+dr)
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s = jnp.einsum("bhc,bsc->bhs", q, cl,
                   preferred_element_type=jnp.float32) * scale
    s = _mask_valid(s[:, None], cache_pos + 1, Smax)[:, 0]
    prob = jax.nn.softmax(s, axis=-1)
    # the whole latent row is the value; its rotary columns are dropped
    # after the product, so the cache is read as it lies
    o = jnp.einsum("bhs,bsc->bhc", prob.astype(cl.dtype), cl,
                   preferred_element_type=jnp.float32)[..., :r]
    out = jnp.einsum("bhr,rhv->bhv", o.astype(dt), w_uv)
    y = out.reshape(B, 1, H * cfg.v_head_dim) @ p["wo"].astype(dt)
    return y, {"latent": cl}
