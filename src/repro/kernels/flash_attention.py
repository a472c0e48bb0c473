"""Pallas TPU flash-attention kernel: scores never leave VMEM.

The XLA-level flash implementation (layers/attention.py) materializes each
(qc x kc) score tile to HBM through the softmax chain — on TPU this kernel
keeps the tile and the running (m, l, acc) statistics in VMEM scratch across
the kv grid dimension, so HBM traffic is just the q/k/v streams plus the
output (the XDMA Frontend discipline applied to attention).

Grid: (BH, nq, nk) with nk innermost (sequential); scratch persists per
(BH, qi) program family.  Causal/window masking via an additive bias
computed from program ids.  Checked against ref.py in interpret mode on the
CPU backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            qc: int, kc: int, nk: int, causal: bool, window, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (qc, hd)
    k = k_ref[0]                                   # (kc, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qp = qi * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
    kp = kj * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
    if causal:
        s = jnp.where(kp <= qp, s, NEG_INF)
    if window is not None:
        s = jnp.where(kp > qp - window, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    q_chunk: int = 512, kv_chunk: int = 512):
    """q (BH, Sq, hd); k, v (BH, Sk, hd).  Returns (BH, Sq, hd).

    GQA callers fold (B, KV, G) into BH and broadcast K/V beforehand."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    qc = min(q_chunk, Sq)
    while Sq % qc:
        qc -= 1
    kc = min(kv_chunk, Sk)
    while Sk % kc:
        kc -= 1
    nq, nk = Sq // qc, Sk // kc
    scale = hd ** -0.5
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(_kernel, qc=qc, kc=kc, nk=nk, causal=causal,
                          window=window, scale=scale),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qc, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kc, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kc, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, qc, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qc, 1), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
            pltpu.VMEM((qc, hd), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(q, k, v)


def flash_attention_gqa(q, k, v, *, causal=True, window=None,
                        q_chunk=512, kv_chunk=512):
    """q (B,Sq,H,hd), k/v (B,Sk,KV,hd) -> (B,Sq,H,hd) via the kernel."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Sk, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Sk, hd)
    o = flash_attention(qf, kf, vf, causal=causal, window=window,
                        q_chunk=q_chunk, kv_chunk=kv_chunk)
    return o.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
