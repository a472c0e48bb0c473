"""XDMA local engine: fused layout-transforming copies within one memory.

This module is a *lowering backend*: the descriptor-driven entry point is
:func:`repro.core.api.transfer`, which dispatches here for local movements
(and caches one jitted executable per descriptor — the CFG phase).

Two lowerings of the same descriptor:

* ``xdma_copy`` — the *fused-stream* path: reader (physical->logical view),
  plugin cascade, writer (logical->physical).  Under ``jax.jit`` XLA fuses
  this into a single HBM pass (read once, write once) — the software analogue
  of the hardware datapath in paper Fig. 2(a).
* ``xdma_copy_pallas`` — the TPU-native lowering via the generic AGU kernel
  in ``repro.kernels.agu`` (grid + BlockSpecs synthesized from the layout
  pair's composed affine pattern; d_buf = burst/pipeline depth).  Kernel
  selection is by *pattern*, not by layout special cases: any 2D relayout /
  transpose the planner can express lowers through the one kernel, the rest
  (plugin chains, rank > 2, incompatible nests) falls back to the fused path
  — ``repro.kernels.agu.agu_stats()`` records why.  The kernel compiles
  for a TPU and interprets on the CPU backend
  (``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .descriptor import XDMADescriptor
from . import layouts as L
from . import plugins as P

__all__ = ["xdma_copy", "xdma_copy_pallas", "reader", "writer"]


def reader(x: jnp.ndarray, layout: L.Layout) -> jnp.ndarray:
    """XDMA Frontend read side: stream physical buffer out in logical order."""
    return layout.to_logical(x)


def writer(x: jnp.ndarray, layout: L.Layout) -> jnp.ndarray:
    """XDMA Frontend write side: stream logical data into the physical layout."""
    return layout.from_logical(x)


def xdma_copy(x: jnp.ndarray, desc: XDMADescriptor) -> jnp.ndarray:
    """One XDMA task on a local memory: src layout -> plugins -> dst layout.

    ``x`` is the *physical* source buffer.  Returns the *physical* destination
    buffer.  Pure function of (x, desc); jit-stable because desc is static.
    """
    if isinstance(x, P.CTensor):
        # compressed carrier in this memory: relayout the dense values, keep
        # the mask side-channel on the stream (Decompress consumes it)
        logical = P.CTensor(values=reader(x.values, desc.src_layout),
                            mask=x.mask)
    else:
        logical = reader(x, desc.src_layout)
    desc.validate(logical.shape)
    logical = P.apply_chain(desc.plugins, logical)
    if isinstance(logical, P.QTensor):
        # Quantized payload: write values tiled, scales ride along row-major.
        return P.QTensor(values=writer(logical.values, desc.dst_layout),
                         scales=logical.scales)
    if isinstance(logical, P.CTensor):
        # Block-compressed payload: the dense carrier takes the dst layout,
        # the occupancy mask rides along as the side-channel.
        return P.CTensor(values=writer(logical.values, desc.dst_layout),
                         mask=logical.mask)
    return writer(logical, desc.dst_layout)


@functools.partial(jax.jit, static_argnames=("desc",))
def xdma_copy_jit(x: jnp.ndarray, desc: XDMADescriptor) -> jnp.ndarray:
    return xdma_copy(x, desc)


def xdma_copy_pallas(x: jnp.ndarray, desc: XDMADescriptor) -> jnp.ndarray:
    """TPU-native lowering through the generic AGU kernel.

    Supports pure relayout and relayout+transpose on 2D logical data (the
    paper's Fig. 4 / Table III workloads) for ANY layout pair the pattern
    planner covers.  Other plugin chains fall back to the fused XLA path —
    they fuse identically there (and the fallback is tallied in
    ``repro.kernels.agu.agu_stats()``).
    """
    from repro.kernels import agu, ops as kops  # local import: keep core importable w/o kernels

    transpose = kops.chain_transpose(desc.plugins)
    if transpose is None:
        agu.record_fallback("plugin-chain")
        return xdma_copy(x, desc)
    return kops.relayout(
        x,
        src_layout=desc.src_layout,
        dst_layout=desc.dst_layout,
        transpose=transpose,
        d_buf=desc.d_buf,
    )
