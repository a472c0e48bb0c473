"""Pallas kernel: symmetric int8 quantize-on-stream into the int8 tile layout.

The wire-format producer for compressed collectives (core/remote.py): rows are
scaled to int8 while being tiled to MNM32N128 (the int8 VREG-native layout),
emitting per-row f32 scales alongside — the Quantize XDMA plugin in hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode
from .agu import eff_d_buf


def _kernel(x_ref, v_ref, s_ref, *, tm: int, tn: int, d: int, n: int):
    rows = x_ref[...].astype(jnp.float32)          # (d*tm, n)
    amax = jnp.max(jnp.abs(rows), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(rows / scale), -127, 127).astype(jnp.int8)
    v_ref[...] = q.reshape(d, tm, n // tn, tn).swapaxes(1, 2)
    s_ref[...] = scale


def quantize_tiled(x: jnp.ndarray, tile_shape=(32, 128), *, d_buf: int = 9):
    m, n = x.shape
    tm, tn = tile_shape
    gm, gn = m // tm, n // tn
    d = eff_d_buf(gm, d_buf)
    grid = (gm // d,)
    values, scales = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, d=d, n=n),
        grid=grid,
        in_specs=[pl.BlockSpec((d * tm, n), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((d, gn, tm, tn), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((d * tm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((gm, gn, tm, tn), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(x)
    return values, scales
