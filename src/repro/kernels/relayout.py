"""Legacy relayout entry points, now thin wrappers over the generic AGU kernel.

The seed hand-wrote four special-case Pallas kernels here (tile / untile /
tiled-transpose / mn-transpose — the paper's Fig. 4 / Table III traffic).
Since the N-D affine Frontend refactor (DESIGN.md §8) they are all instances
of the ONE pattern-driven stream kernel in :mod:`repro.kernels.agu`: the grid
and BlockSpecs are synthesized from the layout pair's composed affine
pattern, and ``d_buf`` — the paper's stream-buffer depth, swept 3/5/9 in
Fig. 4 — sets the burst depth exactly as before.  Outputs are bit-identical
to the seed kernels (everything here is a pure element permutation); the
parity tests in ``tests/test_agu.py`` pin that.

``tile_block`` / ``untile_block`` remain as the in-VMEM relayout stages the
plugin compiler documentation references; they are the 2D special case of
``Layout.from_logical`` / ``Layout.to_logical`` applied to a block.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core import layouts as L

from .agu import agu_relayout


# --------------------------------------------------------------------------
# Shared in-VMEM relayout stages: the 2D special case of the layout algebra
# on a block already resident in VMEM (see Layout.to_logical/from_logical).
# --------------------------------------------------------------------------
def tile_block(x: jnp.ndarray, tm: int, tn: int) -> jnp.ndarray:
    """Logical (M, N) block -> physical (M//tm, N//tn, tm, tn) tile block."""
    m, n = x.shape
    return x.reshape(m // tm, tm, n // tn, tn).transpose(0, 2, 1, 3)


def untile_block(blk: jnp.ndarray) -> jnp.ndarray:
    """Physical (gm, gn, tm, tn) tile block -> logical (gm*tm, gn*tn) block."""
    gm, gn, tm, tn = blk.shape
    return blk.transpose(0, 2, 1, 3).reshape(gm * tm, gn * tn)


def _tiled(tile_shape: Tuple[int, int]) -> L.Layout:
    return L.tiled_layout(*tile_shape)


def tile(x: jnp.ndarray, tile_shape: Tuple[int, int], *,
         d_buf: int = 9) -> jnp.ndarray:
    """MN -> MNMtmNtn (Prefill 2)."""
    return agu_relayout(x, src_layout=L.MN, dst_layout=_tiled(tile_shape),
                        d_buf=d_buf)


def untile(x: jnp.ndarray, *, d_buf: int = 9) -> jnp.ndarray:
    """MNMtmNtn -> MN (Prefill 1); the tile geometry comes from the buffer."""
    tm, tn = x.shape[-2], x.shape[-1]
    return agu_relayout(x, src_layout=_tiled((tm, tn)), dst_layout=L.MN,
                        d_buf=d_buf)


def tiled_transpose(x: jnp.ndarray, *, d_buf: int = 9) -> jnp.ndarray:
    """MNMtmNtn -> MNMtmNtn, logically transposed (the KV-cache Load op)."""
    gm, gn, tm, tn = x.shape
    lay = _tiled((tm, tn))
    return agu_relayout(x, src_layout=lay, dst_layout=lay, transpose=True,
                        d_buf=d_buf)


def mn_transpose(x: jnp.ndarray, *, block: int = 128,
                 d_buf: int = 9) -> jnp.ndarray:
    """MN -> MN, transposed.  ``block`` is retained for API compatibility;
    the AGU planner picks the superblock from the pattern."""
    del block
    return agu_relayout(x, src_layout=L.MN, dst_layout=L.MN, transpose=True,
                        d_buf=d_buf)
