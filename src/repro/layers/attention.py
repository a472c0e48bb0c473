"""GQA attention: flash-chunked train/prefill, cached decode, cross-attention.

Memory discipline: scores never materialize beyond (q_chunk x kv_chunk) tiles
(flash-style running max/denominator), so 32k prefill fits VMEM/HBM budgets.
Under GSPMD, a KV cache whose sequence dim is sharded (long_500k context
parallelism) needs no manual merge: the softmax reductions over the sharded
axis lower to all-reduces automatically.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.sharding import constrain, kv_cache_spec, P
from .norms import rms_norm
from .rope import rope_for

NEG_INF = -1e30


def init_attn(key, cfg, *, cross: bool = False):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    init = jax.nn.initializers.normal(stddev=d ** -0.5)
    p = {
        "wq": init(ks[0], (d, H * hd), jnp.float32),
        "wk": init(ks[1], (d, KV * hd), jnp.float32),
        "wv": init(ks[2], (d, KV * hd), jnp.float32),
        "wo": jax.nn.initializers.normal(stddev=(H * hd) ** -0.5)(
            ks[3], (H * hd, d), jnp.float32),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((KV * hd,), jnp.float32)
        p["bv"] = jnp.zeros((KV * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _chunk_of(n: int, want: int) -> int:
    c = max(1, min(want, n))
    while n % c:
        c -= 1
    return c


def _chunk_pairs(nq, nk, qc, kc, q_offset, Sk, causal, window):
    """Static block-sparse schedule: (qi, kj) chunk pairs intersecting the
    attention mask band.  Fully-masked pairs are never emitted — causal
    halves the work, sliding windows reduce it to a band (flash-style block
    skipping, scheduled at trace time)."""
    pairs = []
    for qi in range(nq):
        q_lo = q_offset + qi * qc
        q_hi = q_lo + qc - 1
        for kj in range(nk):
            k_lo, k_hi = kj * kc, kj * kc + kc - 1
            if causal and k_lo > q_hi:
                continue                      # entirely in the future
            if window is not None and k_hi <= q_lo - window:
                continue                      # entirely beyond the window
            pairs.append((qi, kj))
    return pairs


def chunked_attention_dense(q, k, v, *, causal=True, window=None,
                            q_offset=0, q_chunk=1024, kv_chunk=1024):
    """Flash attention, dense schedule (every q-chunk scans every kv-chunk).

    Used when q is *sequence-sharded* over the model axis (head counts that
    don't divide TP): the block-sparse variant's dynamic indexing over the
    sharded chunk dim would force per-step all-gathers (measured 27x
    collective blow-up on phi4 prefill — see EXPERIMENTS.md §Perf)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qc, kc = _chunk_of(Sq, q_chunk), _chunk_of(Sk, kv_chunk)
    nq, nk = Sq // qc, Sk // kc
    scale = hd ** -0.5

    qt = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qt = qt.reshape(B, nq, qc, KV, G, hd).transpose(1, 0, 3, 4, 2, 5)
    kt = k.reshape(B, nk, kc, KV, hd).transpose(1, 0, 3, 2, 4)
    vt = v.reshape(B, nk, kc, KV, hd).transpose(1, 0, 3, 2, 4)

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, qc)
    k_pos = jnp.arange(Sk).reshape(nk, kc)

    @jax.checkpoint
    def kv_step(carry, xs):
        m, l, acc, qi, qp = carry
        kb, vb, kp = xs
        s = jnp.einsum("bkgqh,bkch->bkgqc", qi, kb,
                       preferred_element_type=jnp.float32)
        bias = jnp.zeros((qc, kc), jnp.float32)
        if causal:
            bias = jnp.where(kp[None, :] <= qp[:, None], bias, NEG_INF)
        if window is not None:
            bias = jnp.where(kp[None, :] > qp[:, None] - window, bias, NEG_INF)
        if causal or window is not None:
            s = s + bias[None, None, None]
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bkch->bkgqh", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc, qi, qp), None

    def q_block(args):
        qi, qp = args
        m0 = jnp.full((B, KV, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qc, hd), jnp.float32)
        (m, l, acc, _, _), _ = lax.scan(
            kv_step, (m0, l0, a0, qi, qp), (kt, vt, k_pos))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = lax.map(q_block, (qt, q_pos))
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def chunked_attention(q, k, v, *, causal=True, window=None,
                      q_offset=0, q_chunk=1024, kv_chunk=1024):
    """Flash-style attention with block-sparse pair scheduling.

    q, k (B,Sq|Sk,H|KV,hd); v (B,Sk,KV,hv), hv may differ from hd (latent
    attention); f32 running max/denominator.  One scan over the *valid*
    (q-chunk, kv-chunk) pairs; per-chunk state lives in a chunk-indexed
    carry updated in place (dynamic-update-slice)."""
    B, Sq, H, hd = q.shape
    Sk, KV, hv = k.shape[1], k.shape[2], v.shape[3]
    G = H // KV
    qc, kc = _chunk_of(Sq, q_chunk), _chunk_of(Sk, kv_chunk)
    nq, nk = Sq // qc, Sk // kc
    scale = hd ** -0.5

    # scale folded into q once (saves one score-shaped multiply per pair)
    qt = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qt = qt.reshape(B, nq, qc, KV, G, hd).transpose(1, 0, 3, 4, 2, 5)
    kt = k.reshape(B, nk, kc, KV, hd).transpose(1, 0, 3, 2, 4)
    vt = v.reshape(B, nk, kc, KV, hv).transpose(1, 0, 3, 2, 4)

    pairs = _chunk_pairs(nq, nk, qc, kc, q_offset, Sk, causal, window)
    pair_arr = jnp.asarray(pairs, jnp.int32)          # (npairs, 2)

    m0 = jnp.full((nq, B, KV, G, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nq, B, KV, G, qc), jnp.float32)
    a0 = jnp.zeros((nq, B, KV, G, qc, hv), jnp.float32)

    @jax.checkpoint
    def pair_step(carry, pair):
        m, l, acc = carry
        qi, kj = pair[0], pair[1]
        qb = lax.dynamic_index_in_dim(qt, qi, 0, keepdims=False)
        kb = lax.dynamic_index_in_dim(kt, kj, 0, keepdims=False)
        vb = lax.dynamic_index_in_dim(vt, kj, 0, keepdims=False)
        mi = lax.dynamic_index_in_dim(m, qi, 0, keepdims=False)
        li = lax.dynamic_index_in_dim(l, qi, 0, keepdims=False)
        ai = lax.dynamic_index_in_dim(acc, qi, 0, keepdims=False)

        s = jnp.einsum("bkgqh,bkch->bkgqc", qb, kb,
                       preferred_element_type=jnp.float32)
        # additive mask bias, (qc, kc) only — fuses into the score add
        qp = q_offset + qi * qc + jnp.arange(qc)
        kp = kj * kc + jnp.arange(kc)
        bias = jnp.zeros((qc, kc), jnp.float32)
        if causal:
            bias = jnp.where(kp[None, :] <= qp[:, None], bias, NEG_INF)
        if window is not None:
            bias = jnp.where(kp[None, :] > qp[:, None] - window, bias, NEG_INF)
        if causal or window is not None:
            s = s + bias[None, None, None]
        m_new = jnp.maximum(mi, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mi - m_new)
        li = li * corr + p.sum(-1)
        ai = ai * corr[..., None] + jnp.einsum(
            "bkgqc,bkch->bkgqh", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        m = lax.dynamic_update_index_in_dim(m, m_new, qi, 0)
        l = lax.dynamic_update_index_in_dim(l, li, qi, 0)
        acc = lax.dynamic_update_index_in_dim(acc, ai, qi, 0)
        return (m, l, acc), None

    (m, l, acc), _ = lax.scan(pair_step, (m0, l0, a0), pair_arr)
    out = acc / jnp.maximum(l, 1e-30)[..., None]       # (nq,B,KV,G,qc,hd)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, hv)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, length, *, rolling=False):
    """q (B,1,H,hd); caches (B,Smax,KV,hd); length = #valid tokens.

    ``rolling=True`` marks a circular window cache: once full, every slot is
    valid (slot order is irrelevant because K carries RoPE already)."""
    B, _, H, hd = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = hd ** -0.5
    # NOTE dtype discipline: never .astype() the cache — that materializes a
    # second full-cache copy in the decode loop. bf16 inputs with f32
    # accumulation via preferred_element_type.
    qh = q[:, 0].reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qh, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = _mask_valid(s, length, Smax)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def _mask_valid(s, length, Smax):
    """Mask scores (B,KV,G,Smax) beyond the valid cache prefix.  ``length``
    is a scalar (uniform batch — the compiled program is unchanged) or a
    (B,) vector of per-request lengths (continuous batching, where ragged
    requests share one decode step)."""
    lv = jnp.minimum(jnp.asarray(length), Smax)
    if lv.ndim:
        valid = jnp.arange(Smax)[None] < lv[:, None]          # (B, Smax)
        return jnp.where(valid[:, None, None, :], s, NEG_INF)
    valid = jnp.arange(Smax) < lv
    return jnp.where(valid[None, None, None], s, NEG_INF)


def decode_attention_xdma(q, kt_cache, v_cache, length):
    """Decode against the XDMA layout-optimal cache: K stored transposed
    (B,KV,hd,Smax) so the q.K^T dot streams it with no in-loop relayout, and
    V stored (B,KV,Smax,hd) contiguous for the PV dot (paper: accelerator-
    optimal layout at rest; relayout fused into the store)."""
    B, _, H, hd = q.shape
    KV, Smax = kt_cache.shape[1], kt_cache.shape[3]
    G = H // KV
    scale = hd ** -0.5
    qh = q[:, 0].reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bkhs->bkgs", qh, kt_cache,
                   preferred_element_type=jnp.float32) * scale
    s = _mask_valid(s, length, Smax)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksh->bkgh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def attn_apply(cfg, p, x, positions, *, causal=True, window=None,
               cache=None, cache_pos=None, kv_x=None, apply_rope=True,
               cross=False):
    """Full attention sublayer.

    train/prefill: ``cache=None`` -> flash-chunked attention over x (or kv_x
    for cross-attention).  decode: ``cache`` = {"k","v"} (B,Smax,KV,hd) plus
    ``cache_pos`` — a scalar (uniform batch; unchanged compiled program) or a
    (B,) vector of per-request positions (ragged continuous batching);
    returns (out, new_cache).
    """
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    mspec = cfg.axes.model
    ms = cfg.axes.model_size
    bspec = cfg.axes.batch_spec
    # Megatron head-parallel attention when heads divide the model axis;
    # if only Q heads divide (GQA kv < TP), K/V are repeated to H heads so
    # everything shards on the head dim (memory x G on K/V activations,
    # enables block-sparse scheduling); otherwise sequence-parallel attention
    # (heads replicated, S sharded) — avoids GSPMD padding/remat storms for
    # e.g. 24 or 14 heads on 16 ranks.
    head_ok = bool(mspec) and ms and H % ms == 0 and KV % ms == 0
    head_repeat = (not head_ok) and bool(mspec) and ms and H % ms == 0
    q_head_ax = mspec if (head_ok or head_repeat) else None
    q_seq_ax = (None if head_ok or head_repeat or not mspec
                else (mspec if S > 1 else None))

    def proj(y, w, b=None):
        o = y @ w.astype(dt)
        if b is not None:
            o = o + b.astype(dt)
        return o

    q = proj(x, p["wq"], p.get("bq")).reshape(B, S, H, hd)
    q = constrain(q, P(bspec, q_seq_ax, q_head_ax, None))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])

    is_cross = cross or (kv_x is not None)
    if cache is not None and is_cross:
        # cross-attn decode: encoder K/V precomputed in cache, never updated
        out = decode_attention(q, cache["k"], cache["v"], cache["len"])
        out = constrain(out, P(bspec, None, mspec, None))
        return proj(out.reshape(B, S, H * hd), p["wo"]), cache

    src = kv_x if is_cross else x
    k = proj(src, p["wk"], p.get("bk")).reshape(B, src.shape[1], KV, hd)
    v = proj(src, p["wv"], p.get("bv")).reshape(B, src.shape[1], KV, hd)
    k = constrain(k, P(bspec, None, q_head_ax, None))
    v = constrain(v, P(bspec, None, q_head_ax, None))
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"])
    if apply_rope and not is_cross:
        q = rope_for(cfg, q, positions)
        if cache is None:
            k = rope_for(cfg, k, positions)
        else:
            k = rope_for(cfg, k, positions)  # decode: positions = current pos

    if cache is None:
        k_att, v_att = k, v
        if head_repeat and S > 1:
            G = H // KV
            k_att = jnp.repeat(k, G, axis=2)
            v_att = jnp.repeat(v, G, axis=2)
            k_att = constrain(k_att, P(bspec, None, mspec, None))
            v_att = constrain(v_att, P(bspec, None, mspec, None))
        # block-sparse pair scheduling needs the q-chunk dim unsharded; the
        # seq-sharded path (q_seq_ax set) uses the dense schedule instead
        impl = chunked_attention_dense if q_seq_ax is not None else chunked_attention
        out = impl(q, k_att, v_att, causal=causal and not is_cross,
                   window=window,
                   q_chunk=min(1024, S), kv_chunk=min(1024, src.shape[1]))
    elif cfg.xdma_cache:
        # XDMA layout-optimal cache: K stored transposed, V dot-contiguous —
        # no relayout in the decode loop (paper's relayout-on-store)
        Smax = cache["k"].shape[3]
        slot = cache_pos % Smax if window is not None else jnp.minimum(cache_pos, Smax - 1)
        dt_c = cache["k"].dtype
        if getattr(cache_pos, "ndim", 0) >= 1:
            # ragged batch (continuous batching): per-request write slots —
            # advanced-index scatter, one slot per batch row
            bidx = jnp.arange(B)
            ck = cache["k"].at[bidx, :, :, slot].set(k[:, 0].astype(dt_c))
            cv = cache["v"].at[bidx, :, slot, :].set(v[:, 0].astype(dt_c))
        else:
            knew = k[:, 0][..., None]                   # (B,KV,hd,1)
            vnew = v[:, 0][:, :, None, :]               # (B,KV,1,hd)
            ck = lax.dynamic_update_slice(cache["k"], knew.astype(dt_c),
                                          (0, 0, 0, slot))
            cv = lax.dynamic_update_slice(cache["v"], vnew.astype(dt_c),
                                          (0, 0, slot, 0))
        ck = constrain(ck, kv_cache_spec(cfg.axes, KV, "bkhs"))
        cv = constrain(cv, kv_cache_spec(cfg.axes, KV, "bksh"))
        cache = dict(cache, k=ck, v=cv)
        out = decode_attention_xdma(q, ck, cv, cache_pos + 1)
    else:
        Smax = cache["k"].shape[1]
        slot = cache_pos % Smax if window is not None else jnp.minimum(cache_pos, Smax - 1)
        if getattr(cache_pos, "ndim", 0) >= 1:
            bidx = jnp.arange(B)
            ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
        else:
            ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, slot, 0, 0))
            cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, slot, 0, 0))
        cspec = kv_cache_spec(cfg.axes, KV)
        ck = constrain(ck, cspec)
        cv = constrain(cv, cspec)
        cache = dict(cache, k=ck, v=cv)
        out = decode_attention(q, ck, cv, cache_pos + 1, rolling=window is not None)

    out = constrain(out, P(bspec, None if cache is not None else q_seq_ax,
                           q_head_ax, None))
    y = proj(out.reshape(B, S, H * hd), p["wo"])
    y = constrain(y, P(bspec, None, None))
    return y, cache
