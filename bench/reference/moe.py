"""Plain Qwen3-MoE forward pass at one chip's expert share, float32, for the
check.

Straightforward ``jax.numpy`` over one sequence: no kernels, no cache, no
batching, no sorting or grouping of tokens.  Attention is that of
``bench/reference/lm.py`` (its helpers are imported); the sparse block
routes every token over all ``E`` experts (softmax, top ``k``, gates
renormalised to sum to 1) and applies each held expert densely to every
token, times its gate where the token was routed to it and 0 where not.
It imports nothing of the program and reads the names of
``bench/weights_moe.py``.  Matmuls run at ``highest`` precision;
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

LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "router", "w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=("dims", "operand_dtype"))
def logits_at(weights, tokens, rows, *, dims, operand_dtype=None):
    """Float32 logits at sequence positions ``rows`` of one sequence.

    ``tokens`` (S,) int32 (padding after the real tokens does not reach
    earlier positions); ``rows`` (n,) int32; ``dims`` a hashable tuple of
    ``bench.weights_moe.dims`` items.  Returns (n, vocab) float32."""
    m = dict(dims)
    H, KV, hd, eps, theta = m["H"], m["KV"], m["hd"], m["eps"], m["theta"]
    E, k, first, n = m["E"], m["k"], m["first"], m["n"]
    G = H // KV
    f32 = jnp.float32

    def q(a):
        return round_to(a.astype(f32), operand_dtype)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=HIGHEST)

    S = tokens.shape[0]
    x = weights["embed"].astype(f32)[tokens]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, w):
        w = {key: v.astype(f32) for key, v in w.items()}
        h = _rms(x, w["ln1"], eps)
        qh = mm(h, w["wq"]).reshape(S, H, hd)
        kh = mm(h, w["wk"]).reshape(S, KV, hd)
        vh = mm(h, w["wv"]).reshape(S, KV, hd)
        qh = _rope(_rms(qh, w["q_norm"], 1e-6), theta)
        kh = _rope(_rms(kh, w["k_norm"], 1e-6), theta)
        kh = jnp.repeat(kh, G, axis=1)                 # head h reads kv h // G
        vh = jnp.repeat(vh, G, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q(qh), q(kh), precision=HIGHEST)
        s = jnp.where(mask[None], s * hd ** -0.5, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", q(p), q(vh), precision=HIGHEST)
        x = x + mm(o.reshape(S, H * hd), w["wo"])

        h = _rms(x, w["ln2"], eps)
        probs = jax.nn.softmax(mm(h, w["router"]), axis=-1)       # (S, E)
        top, idx = lax.top_k(probs, k)
        top = top / top.sum(-1, keepdims=True)
        # gate of every expert for every token: 0 where not routed
        gate = jnp.zeros((S, E), f32).at[jnp.arange(S)[:, None], idx].set(top)
        for e in range(n):
            y = mm(jax.nn.silu(mm(h, w["w_gate"][e])) * mm(h, w["w_up"][e]),
                   w["w_down"][e])
            x = x + gate[:, first + e, None] * y
        return x, None

    x, _ = lax.scan(layer, x, {key: weights[key] for key in LAYER_KEYS})
    x = _rms(x[rows], weights["final_norm"].astype(f32), eps)
    return mm(x, weights["head"])
