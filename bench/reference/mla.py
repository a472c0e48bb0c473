"""Plain DeepSeek-V3-architecture forward pass (multi-head latent attention,
sigmoid-routed experts beside shared ones, leading dense layers) at one
chip's expert share, float32, for the check.

Straightforward ``jax.numpy`` over one sequence, computed as HF's
``DeepseekV3ForCausalLM`` does: no kernels, no cache, no batching, no
sorting or grouping of tokens.  Attention is the published, decompressed
per-head form: ``q = x W_q`` split into a no-rope and a rope part per
head; the compressed ``[c_kv ; k_pe] = x W_kv_a``, ``c_kv`` RMS-normed
(``kv_a_layernorm``) and decompressed per head by ``W_kv_b`` into the
no-rope key and the value; rotary embedding on the rope parts with HF's
interleaved pairing (the (even, odd) pairs gathered into rotate-half order,
then rotated), one rope key shared by every head; causal softmax with
scale ``(qk_nope_head_dim + qk_rope_head_dim)^-1/2``.  The sparse block
scores every token over all ``E`` experts by sigmoid, chooses the top
``k`` of score plus correction bias, weighs them by the unbiased scores
renormalised (``+ 1e-20``) and scaled by ``routed_scaling_factor``, applies
each held expert densely to every token times its weight where chosen and
0 where not, and adds the shared experts' SwiGLU once (HF
``DeepseekV3MoE``).  It imports nothing of the program and reads the names
of ``bench/weights_mla.py``.  Matmuls run at ``highest`` precision;
``operand_dtype`` is the control's switch, as in ``lm.py``: every matmul
operand, the router's too, is rounded to that dtype first.

Departures from the published model: only the held experts' part of each
sparse block is computed (the absent experts' part belongs to the other
chips of the deployment), and the weights are random.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.lm import HIGHEST, _rms, _rope, round_to

ATTN_KEYS = ("ln1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln2")
SPARSE_KEYS = ("router", "router_bias", "w_gate", "w_up", "w_down",
               "shared_gate", "shared_up", "shared_down")


def _rope_interleaved(x, theta):
    """x (S, heads, dr): HF's ``apply_rotary_pos_emb_interleave``."""
    S, heads, dr = x.shape
    x = x.reshape(S, heads, dr // 2, 2).swapaxes(-1, -2).reshape(S, heads, dr)
    return _rope(x, theta)


@functools.partial(jax.jit, static_argnames=("dims", "operand_dtype"))
def logits_at(weights, tokens, rows, *, dims, operand_dtype=None):
    """Float32 logits at sequence positions ``rows`` of one sequence.

    ``tokens`` (S,) int32 (padding after the real tokens does not reach
    earlier positions); ``rows`` (n,) int32; ``dims`` a hashable tuple of
    ``bench.weights_mla.dims`` items.  Returns (n, vocab) float32."""
    m = dict(dims)
    H, r, dn, dr, dv = m["H"], m["r"], m["dn"], m["dr"], m["dv"]
    eps, theta = m["eps"], m["theta"]
    E, k, first, n = m["E"], m["k"], m["first"], m["n"]
    f32 = jnp.float32

    def q(a):
        return round_to(a.astype(f32), operand_dtype)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=HIGHEST)

    def swiglu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    S = tokens.shape[0]
    x = weights["embed"].astype(f32)[tokens]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def attention(x, w):
        """The residual after one layer's attention; the FFN's normed input."""
        h = _rms(x, w["ln1"], eps)
        qh = mm(h, w["wq"]).reshape(S, H, dn + dr)
        ckv = mm(h, w["wkv_a"])
        c = _rms(ckv[:, :r], w["kv_norm"], eps)
        kv = mm(c, w["wkv_b"]).reshape(S, H, dn + dv)
        q_pe = _rope_interleaved(qh[..., dn:], theta)
        k_pe = _rope_interleaved(ckv[:, None, r:], theta)
        qh = jnp.concatenate([qh[..., :dn], q_pe], -1)
        kh = jnp.concatenate([kv[..., :dn],
                              jnp.broadcast_to(k_pe, (S, H, dr))], -1)
        s = jnp.einsum("qhd,khd->hqk", q(qh), q(kh), precision=HIGHEST)
        s = jnp.where(mask[None], s * (dn + dr) ** -0.5, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", q(p), q(kv[..., dn:]),
                       precision=HIGHEST)
        x = x + mm(o.reshape(S, H * dv), w["wo"])
        return x, _rms(x, w["ln2"], eps)

    for i in range(m["Ld"]):                       # leading dense layers
        w = {key.split(".", 1)[1]: v.astype(f32) for key, v in weights.items()
             if key.startswith(f"lead{i}.")}
        x, h = attention(x, w)
        x = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])

    def sparse_layer(x, w):
        w = {key: v.astype(f32) for key, v in w.items()}
        x, h = attention(x, w)
        scores = jax.nn.sigmoid(mm(h, w["router"]))               # (S, E)
        _, idx = lax.top_k(scores + w["router_bias"], k)
        top = jnp.take_along_axis(scores, idx, -1)
        top = top / (top.sum(-1, keepdims=True) + 1e-20) * m["scale"]
        # weight of every expert for every token: 0 where not chosen
        gate = jnp.zeros((S, E), f32).at[jnp.arange(S)[:, None], idx].set(top)
        for e in range(n):
            x = x + gate[:, first + e, None] * swiglu(
                h, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
        x = x + swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
        return x, None

    x, _ = lax.scan(sparse_layer, x,
                    {key: weights[key] for key in ATTN_KEYS + SPARSE_KEYS})
    x = _rms(x[rows], weights["final_norm"].astype(f32), eps)
    return mm(x, weights["head"])
