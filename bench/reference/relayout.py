"""Plain reference of the KV store-then-load round trip.

The store normalises each token's row of the (S, KV * hd) KV matrix by its
root mean square (no weight) and the load returns the matrix transposed,
(KV * hd, S).  Float32, no layout code of the program.

``worst_error`` is the check's number: the largest |got - want| over
(|want| + 1/64).  A faithful bfloat16 rounding reads under 2^-8; one in a
format with a 3-bit mantissa (float8 e4m3) reads up to 2^-4.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.lm import round_to

FLOOR = 1.0 / 64


@functools.partial(jax.jit, static_argnames=("eps", "operand_dtype"))
def expected(kv, *, eps, operand_dtype=None):
    """kv (1, S, KV, hd) -> (1, KV * hd, S) float32 (``operand_dtype``: the
    result rounded to that dtype, the control)."""
    B, S, KV, hd = kv.shape
    x = kv.reshape(B, S, KV * hd).astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y = round_to(y, operand_dtype)
    return jnp.swapaxes(y, 1, 2)


@functools.partial(jax.jit, static_argnames=("eps",))
def _worst(kv, got, *, eps):
    want = expected(kv, eps=eps)
    got = got.astype(jnp.float32).reshape(want.shape)
    return jnp.max(jnp.abs(got - want) / (jnp.abs(want) + FLOOR))


def worst_error(kv, got, eps: float) -> float:
    if got.size != kv.size:
        return float("inf")
    return float(_worst(kv, got, eps=eps))


def control_error(kv, eps: float, operand_dtype) -> float:
    """The reference rounded to ``operand_dtype`` against itself in float32."""
    want = expected(kv, eps=eps)
    low = expected(kv, eps=eps, operand_dtype=operand_dtype)
    return float(jnp.max(jnp.abs(low - want) / (jnp.abs(want) + FLOOR)))
