"""Pallas kernel: RMSNorm-on-stream fused with MN -> tiled relayout.

This is the paper's Prefill workload (§III-C): KV-cache rows are RMSNormed by
a SIMD "accelerator" *while* being moved into the GeMM-optimal tiled layout —
the Plugin Host in hardware, a fused VMEM pass here.  One grid step streams
``d_buf * tm`` logical rows: norm needs the full row, so the row dimension is
the burst axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode
from .agu import eff_d_buf


def _kernel(x_ref, w_ref, o_ref, *, tm: int, tn: int, d: int, eps: float,
            n: int, has_weight: bool):
    rows = x_ref[...]                              # (d*tm, n)
    xf = rows.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = xf * rms
    if has_weight:
        y = y * w_ref[...].astype(jnp.float32)
    y = y.astype(rows.dtype)
    # (d*tm, n) -> (d, gn_local= n//tn ... ) physical tiles (d, n//tn, tm, tn)
    y = y.reshape(d, tm, n // tn, tn).swapaxes(1, 2)
    o_ref[...] = y


def rmsnorm_relayout(x: jnp.ndarray, weight, tile_shape, *, eps: float = 1e-6,
                     d_buf: int = 9) -> jnp.ndarray:
    m, n = x.shape
    tm, tn = tile_shape
    gm, gn = m // tm, n // tn
    d = eff_d_buf(gm, d_buf)
    grid = (gm // d,)
    has_weight = weight is not None
    w = weight if has_weight else jnp.zeros((n,), x.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, d=d, eps=eps, n=n,
                          has_weight=has_weight),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d * tm, n), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((d, gn, tm, tn), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gm, gn, tm, tn), x.dtype),
        interpret=interpret_mode(),
    )(x, w)
