"""jit'd public wrappers for the Pallas kernels (the `ops` layer).

``relayout`` lowers a layout pair through the generic AGU kernel
(:mod:`repro.kernels.agu`): the planner composes the two affine patterns and
synthesizes the grid/BlockSpecs; pairs outside kernel coverage (no common
loop-nest refinement, row-stride padding, rank > 2) fall back to the fused
XLA composition — identical fusion semantics, and
:func:`repro.kernels.agu.agu_stats` records the reason (the CI parity gate
watches it).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from repro.core import layouts as L
from repro.core import plugins as P
from . import agu
from .fused_rmsnorm_relayout import rmsnorm_relayout
from .quant import quantize_tiled

__all__ = ["chain_transpose", "relayout", "rmsnorm_relayout",
           "quantize_tiled"]


def chain_transpose(chain: Sequence[P.Plugin]) -> Optional[bool]:
    """A plugin chain read as an AGU relayout: its ``transpose`` flag when
    the chain is empty or one ``Transpose``, else None (the AGU has no
    other plugin stage)."""
    if not chain:
        return False
    if len(chain) == 1 and isinstance(chain[0], P.Transpose):
        return True
    return None


def relayout(x: jnp.ndarray, *, src_layout: L.Layout, dst_layout: L.Layout,
             transpose: bool = False, d_buf: int = 9) -> jnp.ndarray:
    logical = src_layout.logical_shape(x.shape)
    plan, reason = agu.plan_relayout(src_layout, dst_layout, logical,
                                     transpose=transpose, d_buf=d_buf)
    if plan is not None:
        agu.record_plan(plan)
        return plan.run(x)
    agu.record_fallback(reason)
    # fallback: logical-path relayout (XLA fuses it into one stream)
    v = src_layout.to_logical(x)
    if transpose:
        v = jnp.swapaxes(v, -1, -2)
    return dst_layout.from_logical(v)
