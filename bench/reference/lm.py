"""Plain decoder-LM forward pass (Qwen2 / Qwen3), float32, for the check.

Straightforward ``jax.numpy``: no kernels, no cache, no batching, full
causal attention over one sequence.  It imports nothing of the program and
reads the benchmark's own weight names (``bench/weights.py``).  Matmuls run
at ``highest`` precision, so on a TPU they are float32 and not bfloat16
passes.

``operand_dtype`` is the control's switch: every matmul operand is rounded
to that dtype first (``round_to``, in float32 arithmetic; products and sums
stay float32), which is what a matmul in that precision with float32
accumulation computes.  A float32 -> float8 -> float32 convert pair would
not do: XLA may drop it as a no-op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, heads, hd): rotate-half rotary embedding at positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def round_to(x, dtype):
    """``x`` (float32) rounded to the nearest value of the float format
    ``dtype`` (ties to even, subnormals kept, saturating at its largest
    finite value), returned as float32."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    mant, exp = jnp.frexp(jnp.maximum(jnp.abs(x), float(info.smallest_normal)))
    ulp = jnp.ldexp(jnp.ones_like(x), exp - 1 - int(info.nmant))
    y = jnp.minimum(jnp.round(jnp.abs(x) / ulp) * ulp, float(info.max))
    return jnp.sign(x) * y


@functools.partial(jax.jit, static_argnames=("dims", "operand_dtype"))
def logits_at(weights, tokens, rows, *, dims, operand_dtype=None):
    """Float32 logits at sequence positions ``rows`` of one sequence.

    ``tokens`` (S,) int32 (padding after the real tokens does not reach
    earlier positions: attention is causal); ``rows`` (n,) int32;
    ``dims`` a hashable tuple of ``bench.weights.dims`` items.
    Returns (n, vocab) float32."""
    m = dict(dims)
    H, KV, hd, eps, theta = m["H"], m["KV"], m["hd"], m["eps"], m["theta"]
    G = H // KV
    f32 = jnp.float32

    def q(a):
        return round_to(a.astype(f32), operand_dtype)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=HIGHEST)

    S = tokens.shape[0]
    x = weights["embed"].astype(f32)[tokens]
    layer_keys = [k for k in ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate",
                              "w_up", "w_down", "q_norm", "k_norm", "bq",
                              "bk", "bv") if k in weights]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, w):
        w = {k: v.astype(f32) for k, v in w.items()}
        h = _rms(x, w["ln1"], eps)
        qh = mm(h, w["wq"]) + w.get("bq", 0.0)
        kh = mm(h, w["wk"]) + w.get("bk", 0.0)
        vh = mm(h, w["wv"]) + w.get("bv", 0.0)
        qh, kh, vh = (qh.reshape(S, H, hd), kh.reshape(S, KV, hd),
                      vh.reshape(S, KV, hd))
        if "q_norm" in w:
            qh = _rms(qh, w["q_norm"], 1e-6)
            kh = _rms(kh, w["k_norm"], 1e-6)
        qh, kh = _rope(qh, theta), _rope(kh, theta)
        kh = jnp.repeat(kh, G, axis=1)                 # head h reads kv h // G
        vh = jnp.repeat(vh, G, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q(qh), q(kh), precision=HIGHEST)
        s = jnp.where(mask[None], s * hd ** -0.5, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", q(p), q(vh), precision=HIGHEST)
        x = x + mm(o.reshape(S, H * hd), w["wo"])
        h = _rms(x, w["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                   w["w_down"])
        return x, None

    x, _ = lax.scan(layer, x, {k: weights[k] for k in layer_keys})
    x = _rms(x[rows], weights["final_norm"].astype(f32), eps)
    return mm(x, weights["embed"].T)
