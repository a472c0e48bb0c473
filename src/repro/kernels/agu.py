"""The generic XDMA Frontend kernel: ONE pattern-driven Pallas stream engine.

Paper Fig. 2(b): the Frontend is a single N-D affine address generator, not a
family of special-case movers.  This module is its Pallas lowering — the
``pallas_call`` grid + BlockSpec ``index_map`` ARE the generator's outer loop
levels, synthesized from the :class:`~repro.core.layouts.Layout` pair (and
validated against their composed :class:`~repro.core.layouts.PatternPair`),
and the kernel body is the layout algebra applied per burst in VMEM.  The
four hand-written relayout kernels of the seed (tile / untile /
tiled-transpose / mn-transpose) are all instances of this one kernel; the
wrappers in :mod:`repro.kernels.relayout` now just call it.

Planning (:func:`plan_relayout`) picks the burst geometry:

* no transpose — slabs of ``gr`` logical rows x ``gc*d`` columns, where
  ``gr``/``gc`` are the lcm of the two layouts' tile factors (the smallest
  slab both Frontends can relayout) and ``d`` is the effective ``d_buf``
  stream-buffer depth (paper Table II, swept 3/5/9 in Fig. 4);
* transpose — square-ish superblocks sized to the lcm of the crossing tile
  factors, grown toward the 128-lane VREG width, ``d_buf`` bursts along the
  column axis;
* layouts whose composed pattern has no common loop-nest refinement (or
  geometries outside BlockSpec reach, e.g. row-stride padding) return a
  *fallback reason* instead of a plan — the caller lowers through the fused
  XLA composition, and :func:`agu_stats` tallies why (the CI parity gate
  asserts the canonical layout pairs never take that path).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import layouts as L
from repro.runtime import telemetry as _tm

from . import interpret_mode

__all__ = ["plan_relayout", "AGUPlan", "agu_relayout", "agu_stats",
           "clear_agu_stats", "record_fallback", "record_plan", "eff_d_buf",
           "slab_spec"]


def eff_d_buf(extent: int, d_buf: int) -> int:
    """Largest burst depth <= d_buf that divides the streaming extent."""
    d = max(1, min(d_buf, extent))
    while extent % d:
        d -= 1
    return d


# -- AGU coverage accounting (one event per plan, mirrors cfg_stats) ---------
# Counters live in telemetry.bank("agu"); this module keeps only the view.
_BANK = _tm.bank("agu")


def agu_stats() -> Dict[str, Any]:
    """How relayout requests lowered: through the generic AGU kernel, as the
    identity stream, or via the XLA fallback (with per-reason detail).

    .. deprecated:: PR 7
        Thin view over ``telemetry.bank("agu")`` — prefer
        :func:`repro.runtime.telemetry.snapshot`, which carries the same
        counters under ``surfaces["agu_stats"]``.
    """
    return {"kernel": _BANK.get("kernel"), "identity": _BANK.get("identity"),
            "fallback": _BANK.get("fallback"),
            "reasons": _BANK.with_prefix("reason:")}


def clear_agu_stats() -> None:
    _BANK.clear()


def _record(kind: str, reason: str = "") -> None:
    _BANK.inc(kind)
    if kind == "fallback":
        _BANK.inc(f"reason:{reason or 'unknown'}")


def record_fallback(reason: str) -> None:
    """Callers outside the planner (e.g. the engine routing a plugin chain
    off the kernel path) record their fallbacks here."""
    _record("fallback", reason)


def record_plan(plan: "AGUPlan") -> None:
    """Tally a planned lowering (kernel or identity) in :func:`agu_stats`."""
    _record(plan.kind)


# -- BlockSpec synthesis from the layout IR ----------------------------------
def slab_spec(layout: L.Layout, rows: int, cols: int, logical_shape,
              row_sel: Optional[int], col_sel: Optional[int]) -> pl.BlockSpec:
    """BlockSpec for the physical region of a (rows, cols) logical slab.

    ``row_sel`` / ``col_sel`` give the position of the grid id that strides
    the slab along that logical dim (0 for the first grid axis, 1 for the
    second, ...), or None when the slab spans the whole dim (the block then
    includes any stride padding of that dim).  Works for any 2D-logical
    layout: tiled dims contribute (grid, tile) block dims, the permutation is
    applied to the block exactly as to the buffer.
    """
    m, n = logical_shape
    sel = {0: row_sel, 1: col_sel}
    ext = {0: rows, 1: cols}
    shape, tags = [], []
    for d, kind in layout._phys_dims(2):
        t = layout.dim_tile(2, d)
        e = ext[d] + (layout.dim_pad(2, d) if ext[d] == (m, n)[d] else 0)
        if kind == "grid":
            shape.append(e // t)
            tags.append(sel[d])
        elif kind == "tile":
            shape.append(t)
            tags.append(None)
        else:
            shape.append(e)
            tags.append(sel[d])

    def index_map(*ids, _tags=tuple(tags)):
        return tuple(0 if t is None else ids[t] for t in _tags)

    return pl.BlockSpec(tuple(shape), index_map)


# -- planning ----------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AGUPlan:
    """One planned lowering of a relayout through the generic kernel."""

    kind: str                               # "identity" | "kernel"
    src_layout: L.Layout
    dst_layout: L.Layout
    logical_shape: Tuple[int, ...]
    transpose: bool
    grid: Tuple[int, ...] = ()
    block: Tuple[int, int] = (0, 0)         # logical (rows, cols) per step
    pair: Optional[L.PatternPair] = None    # the composed src⁻¹∘dst pattern

    @property
    def out_logical(self) -> Tuple[int, ...]:
        m, n = self.logical_shape
        return (n, m) if self.transpose else (m, n)

    def run(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.kind == "identity":
            return x
        m, n = self.logical_shape
        br, bc = self.block
        in_spec = slab_spec(self.src_layout, br, bc, (m, n), 0, 1)
        if self.transpose:
            out_spec = slab_spec(self.dst_layout, bc, br, self.out_logical,
                                 1, 0)
        else:
            out_spec = slab_spec(self.dst_layout, br, bc, (m, n), 0, 1)
        src_layout, dst_layout, transpose = (self.src_layout, self.dst_layout,
                                             self.transpose)

        def kernel(src_ref, dst_ref):
            v = src_layout.to_logical(src_ref[...])
            if transpose:
                v = jnp.swapaxes(v, -1, -2)
            dst_ref[...] = dst_layout.from_logical(v)

        return pl.pallas_call(
            kernel,
            grid=self.grid,
            in_specs=[in_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(
                self.dst_layout.physical_shape(self.out_logical), x.dtype),
            interpret=interpret_mode(),
        )(x)


def _grow(base: int, extent: int, cap: int = 128) -> int:
    """Largest multiple of ``base`` dividing ``extent``, <= max(base, cap)."""
    best = base
    f = 2
    while base * f <= max(base, cap):
        if extent % (base * f) == 0:
            best = base * f
        f += 1
    return best


def plan_relayout(src_layout: L.Layout, dst_layout: L.Layout,
                  logical_shape, *, transpose: bool = False,
                  d_buf: int = 9):
    """-> (AGUPlan, '') or (None, fallback_reason).

    Pure planning — no tracing, no stats.  Use :func:`agu_relayout` (or
    ``repro.kernels.ops.relayout``) for the recorded, executing entry point.
    """
    shape = tuple(int(s) for s in logical_shape)
    if len(shape) != 2:
        return None, f"rank:{len(shape)}"
    src_layout.check(shape)
    m, n = shape
    structure = lambda l: (l.tile, l.perm, l.pad)
    if not transpose and structure(src_layout) == structure(dst_layout):
        return AGUPlan(kind="identity", src_layout=src_layout,
                       dst_layout=dst_layout, logical_shape=shape,
                       transpose=False), ""
    pair = L.relayout_pair(src_layout, dst_layout, shape, transpose=transpose)
    if pair is None:
        return None, "nest-incompatible"
    if src_layout.dim_pad(2, 0) or dst_layout.dim_pad(2, 0):
        return None, "row-pad"
    st0, st1 = src_layout.dim_tile(2, 0), src_layout.dim_tile(2, 1)
    dt0, dt1 = dst_layout.dim_tile(2, 0), dst_layout.dim_tile(2, 1)
    if transpose:
        if src_layout.is_padded or dst_layout.is_padded:
            return None, "pad-transpose"
        br = math.lcm(st0, dt1)
        bc = math.lcm(st1, dt0)
        if m % br or n % bc:
            return None, f"granule:{br}x{bc}"
        br = _grow(br, m)
        bc = _grow(bc, n)
        bc *= eff_d_buf(n // bc, d_buf)
        grid = (m // br, n // bc)
    else:
        gr = math.lcm(st0, dt0)
        gc = math.lcm(st1, dt1)
        if m % gr or n % gc:
            return None, f"granule:{gr}x{gc}"
        # untiled/permuted pairs have degenerate (1, 1) granules; grow them
        # toward one VREG slab (8 x 128) so the grid stays coarse.  Tiled
        # granules (>= one tile) keep their legacy geometry.
        gr = _grow(gr, m, cap=8)
        gc = _grow(gc, n, cap=128)
        if src_layout.dim_pad(2, 1) or dst_layout.dim_pad(2, 1):
            # padded column strides: the block must span the whole (padded)
            # row so the kernel's layout algebra sees the full stride; the
            # d_buf burst depth stacks along rows instead
            br, bc = gr * eff_d_buf(m // gr, d_buf), n
        else:
            br, bc = gr, gc * eff_d_buf(n // gc, d_buf)
        grid = (m // br, n // bc)
    return AGUPlan(kind="kernel", src_layout=src_layout,
                   dst_layout=dst_layout, logical_shape=shape,
                   transpose=transpose, grid=grid, block=(br, bc),
                   pair=pair), ""


def agu_relayout(x: jnp.ndarray, *, src_layout: L.Layout,
                 dst_layout: L.Layout, transpose: bool = False,
                 d_buf: int = 9) -> jnp.ndarray:
    """Force the generic AGU kernel; raises when the pair has no plan."""
    logical = src_layout.logical_shape(x.shape)
    plan, reason = plan_relayout(src_layout, dst_layout, logical,
                                 transpose=transpose, d_buf=d_buf)
    if plan is None:
        raise ValueError(
            f"no AGU kernel plan for {src_layout.name}->{dst_layout.name}"
            f"{' transposed' if transpose else ''} on {logical} ({reason})")
    record_plan(plan)
    return plan.run(x)
