"""The XDMA plugin compiler: lower a descriptor's whole datapath into one
Pallas kernel per endpoint side.

Paper Fig. 2(c) puts the plugin hosts *inside* the reader -> writer datapath:
data is manipulated while it streams, in a single hardware pass.  The plugin
host composition in :mod:`repro.core.engine` trusts XLA to fuse the separate
reader / plugin / writer ops; this module closes the remaining gap by
compiling ``reader -> pre-chain -> post-chain -> writer`` (local movements)
or ``reader -> pre-chain`` / ``post-chain -> writer`` (the two sides of a
remote movement) into **one** ``pallas_call`` each, with the relayout stages
of :mod:`repro.kernels.relayout` emitted as the first/last kernel stage and
each plugin's :meth:`~repro.core.plugins.Plugin.emit` hook as a middle stage.

Two kernel templates:

* **streamed** — every plugin in the chain is row-local and shape-preserving
  (``streaming=True``): the kernel walks the logical rows in ``d_buf``-deep
  bursts exactly like the relayout kernels, so the stream-buffer depth of
  paper Table II stays meaningful for plugin-carrying descriptors.
* **block** — anything else that still has ``emit`` everywhere (transpose,
  gather/scatter, compress, reduce): one grid step stages the whole logical
  array through VMEM — still a single fused pass, no HBM round-trip between
  stages.

Any chain containing a plugin without ``emit`` (e.g. ``Quantize``, whose
QTensor payload splits the stream), or whose output is still a payload
pytree (``Compress`` not followed by ``Decompress``: Mosaic cannot write the
rank-1 bool mask), falls back to the fused-XLA composition —
behaviour is identical by construction and enforced bitwise by the
differential harness (``tests/test_differential.py``).  :func:`cfg_stats`
reports how many lowerings fused vs fell back, and why.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import telemetry as _tm

from . import layouts as L
from . import plugins as P
from .descriptor import XDMADescriptor

__all__ = ["can_fuse", "compile_local", "maybe_compile_local",
           "maybe_compile_side", "cfg_stats", "clear_stats"]


# -- fusion accounting (one event per lowering, not per Data phase) ----------
# A refused chain is one event at its CFG phase; a fusible chain is one event
# per input shape, where its kernel (or the vmem-block fallback) is chosen.
# Counters live in telemetry.bank("plugin_compiler"); this module keeps the
# historical view functions.
_BANK = _tm.bank("plugin_compiler")


def cfg_stats() -> Dict[str, Any]:
    """Fused vs fallback lowering counts, with per-reason fallback detail.

    .. deprecated:: PR 7
        Thin view over ``telemetry.bank("plugin_compiler")`` — prefer
        :func:`repro.runtime.telemetry.snapshot`, which carries the same
        counters under ``surfaces["cfg_stats"]``.
    """
    return {"fused": _BANK.get("fused"), "fallback": _BANK.get("fallback"),
            "reasons": _BANK.with_prefix("reason:")}


def clear_stats() -> None:
    _BANK.clear()


def _record(fused: bool, reason: str = "") -> None:
    if fused:
        _BANK.inc("fused")
    else:
        _BANK.inc("fallback")
        _BANK.inc(f"reason:{reason or 'unknown'}")


# -- fusibility --------------------------------------------------------------
def _chain_fusible(chain: Sequence[P.Plugin]) -> Optional[str]:
    """None when the chain lowers to one Mosaic kernel, else the fallback
    reason.  Two rules: every plugin needs an emit hook, and a payload
    pytree (a ``CTensor``'s mask) may live inside the kernel but not leave
    it — Mosaic has no rank-1 or bool kernel outputs, and the compiler
    aborts the process on them, so such chains are refused here, never
    tried."""
    open_payload = None
    for p in chain:
        if not p.supports_emit:
            return f"no-emit:{p.name}"
        if p.pytree_payload:
            open_payload = p
        elif p.unpacks_payload:
            open_payload = None
    if open_payload is not None:
        return f"payload-output:{open_payload.name}"
    return None


def can_fuse(desc: XDMADescriptor) -> Tuple[bool, str]:
    """Whether the *local* datapath of ``desc`` compiles to one kernel.

    This is the ``backend='auto'`` policy: plugin-carrying local movements
    with a fully emit-capable chain fuse; empty chains keep the plain XLA
    relayout (nothing to fuse into the datapath); anything else falls back.
    """
    if desc.movement != "local":
        return False, f"movement:{desc.movement}"
    chain = desc.pre + desc.post
    if not chain:
        return False, "empty-chain"
    reason = _chain_fusible(chain)
    if reason is not None:
        return False, reason
    return True, "fusible"


# -- kernel construction -----------------------------------------------------
def _read_stage(blk: jnp.ndarray, layout: L.Layout) -> jnp.ndarray:
    # The layout algebra applied to a VMEM-resident block: a BlockSpec slab
    # of a physical buffer is itself the physical image of its logical slab,
    # so the whole-buffer conversion is also the per-burst kernel stage.
    return layout.to_logical(blk)


def _write_stage(v: jnp.ndarray, layout: L.Layout) -> jnp.ndarray:
    return layout.from_logical(v)


def _chain_consts(chain: Sequence[P.Plugin]) -> Tuple[Tuple[int, ...], Tuple[Any, ...]]:
    """Per-plugin const counts + the flat const operand list (captured once
    at CFG time, streamed into the kernel as extra inputs)."""
    counts, flat = [], []
    for p in chain:
        cs = tuple(p.emit_consts())
        counts.append(len(cs))
        flat.extend(cs)
    return tuple(counts), tuple(flat)


def _emit_chain(v, chain, counts, const_vals):
    ci = 0
    for p, nc in zip(chain, counts):
        v = p.emit(v, *const_vals[ci:ci + nc])
        ci += nc
    return v


def _out_struct(in_aval, src_layout, chain):
    """eval_shape of the logical composition: the kernel's output pytree."""
    def f(x):
        v = src_layout.to_logical(x)
        return P.apply_chain(chain, v)
    return jax.eval_shape(f, in_aval)


def _compile_block(chain, src_layout, dst_layout, in_aval):
    """Whole-array template: one grid step, full blocks through VMEM.  The
    chain's output is a plain array (:func:`_chain_fusible` keeps payload
    pytrees inside the kernel)."""
    from repro.kernels import interpret_mode
    counts, consts = _chain_consts(chain)
    struct = _out_struct(in_aval, src_layout, chain)

    def kernel(x_ref, *refs):
        const_refs, out_ref = refs[:len(consts)], refs[len(consts)]
        v = _read_stage(x_ref[...], src_layout)
        v = _emit_chain(v, chain, counts, tuple(r[...] for r in const_refs))
        out_ref[...] = _write_stage(v, dst_layout)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            dst_layout.physical_shape(struct.shape), struct.dtype),
        interpret=interpret_mode())
    return lambda x: call(x, *consts)


def _burst_rows(chain, src_layout, dst_layout, m: int, d_buf: int) -> Optional[int]:
    """Rows per streamed burst, or None when the geometry forces the block
    template.  Base granularity is the lcm of the two layouts' row-tile
    factors (the smallest slab both Frontends can relayout); ``d_buf``
    bursts stack on top of it exactly as in the AGU relayout kernel.  Row-
    stride padding cannot be row-slabbed (the padding rows sit at the end of
    the buffer), so it falls to the block template."""
    from repro.kernels.agu import eff_d_buf
    if src_layout.dim_pad(2, 0) or dst_layout.dim_pad(2, 0):
        return None
    base = math.lcm(src_layout.dim_tile(2, 0), dst_layout.dim_tile(2, 0))
    if m % base:
        return None
    return base * eff_d_buf(m // base, d_buf)


def _compile_streamed(chain, src_layout, dst_layout, in_aval, d_buf):
    """Row-burst template for all-streaming chains (d_buf-deep bursts)."""
    from repro.kernels import interpret_mode
    from repro.kernels.agu import slab_spec
    logical = src_layout.logical_shape(in_aval.shape)
    if len(logical) != 2:
        return None
    m, n = logical
    rows = _burst_rows(chain, src_layout, dst_layout, m, d_buf)
    if rows is None:
        return None
    out_dtype = P.chain_out_dtype(chain, in_aval.dtype)
    counts, consts = _chain_consts(chain)

    def spec(layout, nn):
        # full-width row slab, synthesized from the layout IR (tiled dims
        # become (grid, tile) block dims; perm/pad ride along)
        return slab_spec(layout, rows, nn, (m, nn), 0, None)

    const_specs = [pl.BlockSpec(c.shape, lambda i, _nd=len(c.shape): (0,) * _nd)
                   for c in consts]

    def kernel(x_ref, *refs):
        const_refs, (out_ref,) = refs[:len(consts)], refs[len(consts):]
        v = _read_stage(x_ref[...], src_layout)
        v = _emit_chain(v, chain, counts, tuple(r[...] for r in const_refs))
        out_ref[...] = _write_stage(v, dst_layout)

    call = pl.pallas_call(
        kernel,
        grid=(m // rows,),
        in_specs=[spec(src_layout, n)] + const_specs,
        out_specs=spec(dst_layout, n),
        out_shape=jax.ShapeDtypeStruct(dst_layout.physical_shape((m, n)),
                                       out_dtype),
        interpret=interpret_mode(),
    )
    return lambda x: call(x, *consts)


# The whole-block template holds its input, its output and an f32 copy of
# the logical block in VMEM at once.  A v5e kernel gets 16 MiB of scoped
# VMEM by default; blocks up to half of it leave the rest to the kernel's
# own temporaries.  Larger arrays take the XLA composition.
_BLOCK_VMEM_BYTES = 8 << 20


def _unit_lead(chain, src_layout, dst_layout, logical, out_logical) -> int:
    """Leading logical dims of extent 1 that both layouts keep as plain
    leading physical dims: dropping them leaves every byte in place, so a
    (1, S, d) movement runs the (S, d) kernel.  Plugins that address an
    absolute axis keep the full rank."""
    if any(getattr(p, "axis", -1) >= 0 for p in chain):
        return 0
    k = 0
    while k < len(logical) - 2 and logical[k] == 1:
        k += 1
    plain = [(d, "plain") for d in range(k)]
    while k and (src_layout._phys_dims(len(logical))[:k] != plain[:k]
                 or dst_layout._phys_dims(len(out_logical))[:k] != plain[:k]):
        k -= 1
    return k


def _composition(chain, src_layout, dst_layout):
    """The XLA composition of the same datapath (bit-identical by
    construction: the same reader, plugin and writer ops)."""
    return lambda x: _write_stage(
        P.apply_chain(chain, _read_stage(x, src_layout)), dst_layout)


def _compile_for_aval(chain, src_layout, dst_layout, d_buf, in_aval):
    """Pick the kernel for one input shape -> (callable, fallback reason or
    None).  In order: squeeze unit leading dims; a lone ``Transpose`` is the
    AGU's transposing relayout (gridded superblocks, so any size fits VMEM);
    all-streaming chains take the row-burst template; the rest take the
    whole-block template when it fits VMEM, else the XLA composition
    (reason ``vmem-block``)."""
    from repro.kernels import agu
    from repro.kernels import ops as kops
    logical = src_layout.logical_shape(in_aval.shape)
    out_logical = P.chain_out_shape(chain, logical)
    k = _unit_lead(chain, src_layout, dst_layout, logical, out_logical)
    if k:
        inner_shape = src_layout.physical_shape(logical[k:])
        inner, reason = _compile_for_aval(
            chain, src_layout, dst_layout, d_buf,
            jax.ShapeDtypeStruct(inner_shape, in_aval.dtype))
        out_shape = dst_layout.physical_shape(out_logical)
        return (lambda x: inner(x.reshape(inner_shape)).reshape(out_shape),
                reason)
    if kops.chain_transpose(chain) and len(logical) == 2:
        plan, _ = agu.plan_relayout(src_layout, dst_layout, logical,
                                    transpose=True, d_buf=d_buf)
        if plan is not None:
            return functools.partial(kops.relayout, src_layout=src_layout,
                                     dst_layout=dst_layout, transpose=True,
                                     d_buf=d_buf), None
    if all(p.streaming for p in chain):
        fn = _compile_streamed(chain, src_layout, dst_layout, in_aval, d_buf)
        if fn is not None:
            return fn, None
    out_dtype = P.chain_out_dtype(chain, in_aval.dtype)
    block = (math.prod(in_aval.shape) * jnp.dtype(in_aval.dtype).itemsize
             + math.prod(out_logical) * jnp.dtype(out_dtype).itemsize
             + math.prod(logical) * 4)
    if block > _BLOCK_VMEM_BYTES:
        return _composition(chain, src_layout, dst_layout), "vmem-block"
    return _compile_block(chain, src_layout, dst_layout, in_aval), None


def _specializing(chain, src_layout, dst_layout, d_buf, validate, *,
                  strict: bool):
    """Descriptor-level callable: specializes one kernel per input aval
    (mirroring how jit caches executables by shape under the CFG cache) and
    tallies each specialization as fused or fallback.  ``strict`` (a forced
    kernel) raises where the XLA composition would stand in."""
    kernels: Dict[Tuple, Callable] = {}

    def run(x):
        x = jnp.asarray(x)
        aval = jax.ShapeDtypeStruct(x.shape, x.dtype)
        key = (x.shape, str(x.dtype))
        fn = kernels.get(key)
        if fn is None:
            validate(aval)
            fn, reason = _compile_for_aval(chain, src_layout, dst_layout,
                                           d_buf, aval)
            if strict and reason is not None:
                raise ValueError(f"descriptor is not fusible at {x.shape} "
                                 f"({reason}); use the fused-XLA backend")
            _record(reason is None, reason or "")
            kernels[key] = fn
        return fn(x)

    return run


# -- public entry points -----------------------------------------------------
def _local(desc: XDMADescriptor, *, strict: bool) -> Callable:
    def validate(aval):
        desc.validate(desc.src.layout.logical_shape(aval.shape))

    return _specializing(desc.pre + desc.post, desc.src.layout,
                         desc.dst.layout, desc.d_buf, validate, strict=strict)


def compile_local(desc: XDMADescriptor) -> Callable:
    """The full local datapath as one kernel; raises when not fusible, or
    (at its first call with a shape) when no kernel fits that shape.

    The returned callable specializes (and memoizes) one ``pallas_call`` per
    input shape/dtype — wrap it in ``jax.jit`` for the usual CFG caching.
    """
    if desc.movement != "local":
        raise ValueError(f"compile_local only lowers local movements, "
                         f"got {desc.movement!r}")
    reason = _chain_fusible(desc.pre + desc.post)
    if reason is not None:
        raise ValueError(f"descriptor is not fusible ({reason}); "
                         "use the fused-XLA backend instead")
    return _local(desc, strict=True)


def maybe_compile_local(desc: XDMADescriptor) -> Optional[Callable]:
    """``backend='auto'`` policy + stats: the compiled datapath, or None to
    signal the XLA-composition fallback.  A fusible chain is tallied per
    input shape, where its kernel is chosen; a refused one here, once."""
    ok, reason = can_fuse(desc)
    if not ok:
        _record(False, reason)
        return None
    return _local(desc, strict=False)


def maybe_compile_side(layout: L.Layout, chain: Sequence[P.Plugin], *,
                       side: str, d_buf: int = 9) -> Optional[Callable]:
    """One endpoint side of a remote movement as a single kernel, or None.

    ``side='src'``: reader + pre-chain (physical src buffer -> link payload);
    ``side='dst'``: post-chain + writer (link payload -> physical dst
    buffer).  The identity layout stands in for the link end.  A non-empty,
    fully emit-capable chain whose payload stays a plain array fuses
    (pytree payloads like QTensor/CTensor split the stream across the
    collective).  Sides with no plugins don't count as fallbacks — there is
    no chain to fuse, and the reader/writer runs as the plain relayout it
    always was."""
    if side == "src":
        src_layout, dst_layout = layout, L.MN
    elif side == "dst":
        src_layout, dst_layout = L.MN, layout
    else:
        raise ValueError(f"side must be 'src' or 'dst', got {side!r}")
    chain = tuple(chain)
    if not chain:
        return None
    reason = _chain_fusible(chain)
    if reason is None:
        for p in chain:
            if p.pytree_payload:
                reason = f"pytree-payload:{p.name}"
                break
    if reason is not None:
        _record(False, reason)
        return None
    return _specializing(chain, src_layout, dst_layout, d_buf,
                         lambda aval: None, strict=False)
