"""Mesh-axis conventions and sharding-constraint helpers.

Axis roles (DESIGN.md §5):
  batch axes  — ``("pod", "data")`` on the multi-pod mesh, ``("data",)``
                on a single pod: data parallelism (+ ZeRO optimizer sharding).
  model axis  — ``"model"``: tensor parallelism (heads / d_ff / vocab / experts).
  seq axis    — context parallelism for long_500k reuses ``"data"``
                (batch=1 leaves it free).

``constrain`` is a no-op outside a mesh context so layer code runs unchanged
in single-device tests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["Axes", "constrain", "P"]


@dataclasses.dataclass(frozen=True)
class Axes:
    """Names of the mesh axes playing each role (None = replicated role)."""

    batch: Tuple[str, ...] = ("data",)
    model: Optional[str] = "model"
    seq: Optional[str] = None      # context-parallel axis for long-context decode
    model_size: int = 0            # size of the model axis (0 = unknown)
    batch_size: int = 0            # total DP degree (0 = unknown)

    @property
    def batch_spec(self):
        return self.batch if len(self.batch) > 1 else (self.batch[0] if self.batch else None)


# single-device default (tests); launchers pass explicit Axes via the config
CPU_AXES = Axes(batch=(), model=None, seq=None)


def _ambient_mesh():
    try:
        from jax.interpreters import pxla
        m = pxla.thread_resources.env.physical_mesh
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    try:
        m = jax.sharding.get_abstract_mesh()
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    return None


def constrain(x, spec: P):
    """with_sharding_constraint that (a) degrades to identity without a mesh
    and (b) clamps spec axes whose size doesn't divide the dimension —
    non-divisible shardings trigger GSPMD "involuntary full rematerialization"
    storms, so replicating that dim is strictly better."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    sizes = dict(mesh.shape)      # concrete and abstract meshes alike
    parts = (tuple(spec) + (None,) * x.ndim)[:x.ndim]
    clamped = []
    for i, ax in enumerate(parts):
        if ax is None:
            clamped.append(None)
            continue
        names = ax if isinstance(ax, tuple) else (ax,)
        if any(n not in sizes for n in names):
            clamped.append(None)
            continue
        total = 1
        for n in names:
            total *= sizes[n]
        clamped.append(ax if x.shape[i] % total == 0 else None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*clamped))
    except (ValueError, RuntimeError, TypeError):
        return x


def kv_cache_spec(axes: Axes, n_kv: int, layout: str = "bshd") -> P:
    """Sharding for a KV cache.  KV heads take the model axis when they
    divide it; otherwise the sequence dim takes the model axis (balanced
    memory, psum-merged attention) — plus the context-parallel seq axis.

    layouts: "bshd" (B,S,KV,hd) conventional; "bkhs" (B,KV,hd,S) = XDMA K^T;
    "bksh" (B,KV,S,hd) = XDMA V."""
    m, ms = axes.model, axes.model_size
    b = axes.batch_spec
    if m and ms and n_kv % ms == 0:
        kv_ax, seq_ax = m, axes.seq
    else:
        kv_ax = None
        seq_names = tuple(n for n in ((axes.seq,) if axes.seq else ())
                          + ((m,) if m else ()))
        seq_ax = (seq_names if len(seq_names) > 1
                  else (seq_names[0] if seq_names else None))
    if layout == "bshd":
        return P(b, seq_ax, kv_ax, None)
    if layout == "bkhs":
        return P(b, kv_ax, None, seq_ax)
    if layout == "bksh":
        return P(b, kv_ax, seq_ax, None)
    raise ValueError(layout)


def spec(*names) -> P:
    return P(*names)
