"""XDMA Plugins: standardized on-the-fly data manipulation during transfers.

Paper Fig. 2(c): two Plugin Hosts (post-reader, pre-writer) share a uniform
architecture; one or more plugins can be cascaded, each with its own control
bits.  Here a :class:`Plugin` is a pure function on the *logical* stream; the
engine composes the chain between the reader (physical->logical) and the
writer (logical->physical) so XLA fuses everything into a single pass — the
data never round-trips HBM between stages, which is the architectural point.

``Quantize``/``Dequantize`` carry scales alongside the payload (a
:class:`QTensor`), mirroring the paper's "compute-while-transfer" plugin port
(iDMA Table I) and enabling compressed collectives (see core/remote.py).

Since the plugin compiler (DESIGN.md §7) a plugin may additionally expose an
``emit`` hook: the same transform expressed as a Pallas kernel *stage*,
operating on the in-VMEM logical block so the whole chain lowers into a
single ``pallas_call`` alongside the reader/writer relayout stages
(:mod:`repro.core.plugin_compiler`).  Plugins without ``emit`` keep working —
the compiler falls back to the fused-XLA composition for any chain that
contains one.  Every concrete plugin registers under its ``name`` so
descriptor generators (the differential harness) and config files can draw
from one source of truth.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "Plugin", "Identity", "Transpose", "Cast", "Scale", "BiasAdd",
    "RMSNormPlugin", "Quantize", "Dequantize", "QTensor", "apply_chain",
    "chain_out_shape", "chain_out_dtype",
    "GatherScatter", "Compress", "Decompress", "CTensor", "ReduceStage",
    "register_plugin", "plugin_by_name", "registered_plugins",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """int8 payload + per-row scales travelling together through the tunnel."""

    values: jnp.ndarray   # int8
    scales: jnp.ndarray   # f32, shape = values.shape[:-1] + (1,)

    def tree_flatten(self):
        return (self.values, self.scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.values.shape

    @property
    def dtype(self):
        return self.values.dtype


class Plugin:
    """Base: a pure transform on the logical stream.

    Compiler contract (DESIGN.md §7):

    * ``emit(x, *consts)`` — optional Pallas-stage form of the transform.
      ``x`` is the logical block already resident in VMEM; ``consts`` are the
      arrays returned by :meth:`emit_consts`, streamed in as extra kernel
      operands.  Must be jnp ops legal inside a kernel body and numerically
      identical to ``__call__`` (the differential harness enforces bitwise
      equality against the fused-XLA composition).  ``emit = None`` (the
      default) marks the plugin non-fusible: the compiler falls back.
    * ``streaming`` — True when the transform is row-local on the logical
      (..., M, N) stream *and* shape-preserving, so the compiler may burst it
      ``d_buf`` rows at a time instead of staging the whole array.
    * ``changes_rank`` — a plugin whose ``out_logical_shape`` changes the
      number of dims must declare it, or :func:`chain_out_shape` raises at
      CFG time (instead of a cryptic jit error deep in the engine).
    * ``pytree_payload`` — a plugin whose output is a payload pytree
      (:class:`QTensor`, :class:`CTensor`, or a custom carrier) rather than
      a plain array must declare it: the compiler refuses to fuse such a
      chain as a *remote* endpoint side, because the collective between the
      sides only carries the payload types the remote backends know how to
      split.
    * ``unpacks_payload`` — a plugin that takes such a payload pytree and
      returns a plain array (``Decompress``, ``Dequantize``).  A fused
      kernel may carry a payload pytree between stages, but its output must
      be a plain array, so the compiler needs to see where one is unpacked.
    """

    name: str = "plugin"
    emit: Optional[Callable] = None     # subclasses define a method to opt in
    streaming: bool = False
    changes_rank: bool = False
    pytree_payload: bool = False
    unpacks_payload: bool = False

    def __call__(self, x: Any) -> Any:  # pragma: no cover - interface
        raise NotImplementedError

    def emit_consts(self) -> Tuple[Any, ...]:
        """Arrays the ``emit`` stage needs as extra kernel operands."""
        return ()

    @property
    def supports_emit(self) -> bool:
        return callable(self.emit)

    def out_logical_shape(self, shape: Tuple[int, ...]) -> Tuple[int, ...]:
        return tuple(shape)

    def out_dtype(self, dtype):
        return dtype

    def __repr__(self):
        return self.name


# -- the plugin registry -----------------------------------------------------
# name -> plugin class; the single source of truth the compiler, the
# differential harness's descriptor strategies, and config files draw from.
_REGISTRY: Dict[str, type] = {}


def register_plugin(cls: type) -> type:
    """Class decorator: register ``cls`` under its ``name`` attribute."""
    name = cls.name
    if not isinstance(name, str) or not name:
        raise ValueError(f"plugin {cls!r} needs a non-empty string name")
    existing = _REGISTRY.get(name)
    if existing is not None and existing is not cls:
        raise ValueError(f"plugin name {name!r} already registered to {existing!r}")
    _REGISTRY[name] = cls
    return cls


def plugin_by_name(name: str) -> type:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown plugin {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def registered_plugins() -> Dict[str, type]:
    """Snapshot of the registry (name -> class)."""
    return dict(_REGISTRY)


@register_plugin
class Identity(Plugin):
    name = "identity"
    streaming = True

    def __call__(self, x):
        return x

    def emit(self, x):
        return x


@register_plugin
class Transpose(Plugin):
    """Logical transpose of the trailing (M, N) dims — the paper's Load workload."""

    name = "transpose"

    def __call__(self, x):
        return jnp.swapaxes(x, -1, -2)

    emit = __call__

    def out_logical_shape(self, shape):
        return tuple(shape[:-2]) + (shape[-1], shape[-2])


@register_plugin
@dataclasses.dataclass(frozen=True)
class Cast(Plugin):
    dtype: Any = jnp.bfloat16
    name: str = "cast"
    streaming = True

    def __call__(self, x):
        return x.astype(self.dtype)

    emit = __call__

    def out_dtype(self, dtype):
        return self.dtype


@register_plugin
@dataclasses.dataclass(frozen=True)
class Scale(Plugin):
    alpha: float = 1.0
    name: str = "scale"
    streaming = True

    def __call__(self, x):
        return x * jnp.asarray(self.alpha, dtype=x.dtype)

    emit = __call__


@register_plugin
@dataclasses.dataclass(frozen=True)
class BiasAdd(Plugin):
    bias: Any = 0.0
    name: str = "bias_add"
    streaming = True

    def __call__(self, x):
        return x + jnp.asarray(self.bias, dtype=x.dtype)

    emit = __call__


@register_plugin
@dataclasses.dataclass(frozen=True)
class RMSNormPlugin(Plugin):
    """RMSNorm over the last logical dim, on-stream (paper §III-C Prefill).

    ``weight`` optional learned gain; applied in f32 and cast back.
    Row-local (the norm only reads its own row), hence ``streaming``.
    """

    eps: float = 1e-6
    weight: Any = None
    name: str = "rmsnorm"
    streaming = True

    def __call__(self, x):
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        y = xf * rms
        if self.weight is not None:
            y = y * self.weight.astype(jnp.float32)
        return y.astype(dtype)

    def emit(self, x, *consts):
        if self.weight is None:
            return self(x)
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (xf * rms * consts[0].astype(jnp.float32)).astype(dtype)

    def emit_consts(self):
        return () if self.weight is None else (jnp.asarray(self.weight),)


@register_plugin
@dataclasses.dataclass(frozen=True)
class Quantize(Plugin):
    """Symmetric per-row int8 quantization on the wire (compression plugin)."""

    name: str = "quantize_int8"
    pytree_payload = True               # emits a QTensor

    def __call__(self, x) -> QTensor:
        xf = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
        return QTensor(values=q, scales=scale)

    def out_dtype(self, dtype):
        return jnp.int8


@register_plugin
@dataclasses.dataclass(frozen=True)
class Dequantize(Plugin):
    dtype: Any = jnp.float32
    name: str = "dequantize_int8"
    unpacks_payload = True

    def __call__(self, x: QTensor):
        return (x.values.astype(jnp.float32) * x.scales).astype(self.dtype)

    def out_dtype(self, dtype):
        return self.dtype


# -- compiler-era plugins (DESIGN.md §7) -------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CTensor:
    """Block-compressed payload: dense carrier + per-block occupancy mask.

    ``values`` keeps the logical shape (XLA needs static shapes, so the
    zero-skip is simulated at the cost model, not the buffer); ``mask`` has
    one bool per ``block_rows`` rows and marks blocks that carry any nonzero.
    ``wire_nbytes`` is what the link would actually move: occupied blocks
    plus the mask side-channel — the number the simulator charges.
    """

    values: jnp.ndarray
    mask: jnp.ndarray     # bool, shape = values.shape[:-2] + (M // block_rows,)

    def tree_flatten(self):
        return (self.values, self.mask), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.values.shape

    @property
    def dtype(self):
        return self.values.dtype

    def occupancy(self) -> jnp.ndarray:
        """Fraction of row blocks that carry data (1.0 = dense)."""
        return self.mask.astype(jnp.float32).mean()

    def wire_nbytes(self) -> int:
        """Bytes on the link after zero-skipping (needs a concrete mask and a
        *logical*-layout carrier — the mask blocks index logical rows)."""
        import math
        m = self.values.shape[-2]
        blocks = self.mask.shape[-1]
        if blocks == 0 or m % blocks:
            raise ValueError(
                f"carrier rows {m} don't split into {blocks} mask blocks — "
                "wire_nbytes needs the logical (pre-writer) payload")
        block_bytes = (m // blocks) * self.values.shape[-1] * \
            jnp.dtype(self.values.dtype).itemsize
        occupied = int(jnp.sum(self.mask))
        lead = math.prod(self.values.shape[:-2])
        return occupied * block_bytes + lead * blocks  # 1 byte/mask bit (padded)


@register_plugin
@dataclasses.dataclass(frozen=True)
class GatherScatter(Plugin):
    """Index-driven reorder of logical rows — the im2col / MoE-permute case.

    ``indices`` selects rows along ``axis`` (default: the logical row dim);
    the output has ``len(indices)`` rows, so a gather can expand (im2col
    patch duplication) or shrink (top-k selection) the stream.  The inverse
    scatter is just a gather with the inverse permutation — one plugin covers
    both directions, matching the paper's single reorder datapath.
    """

    indices: Any = None
    axis: int = -2
    name: str = "gather_scatter"

    def __post_init__(self):
        if self.indices is None:
            raise ValueError("GatherScatter needs an index array")

    def __call__(self, x):
        return jnp.take(x, jnp.asarray(self.indices), axis=self.axis)

    def emit(self, x, idx):
        return jnp.take(x, idx, axis=self.axis)

    def emit_consts(self):
        return (jnp.asarray(self.indices),)

    def out_logical_shape(self, shape):
        axis = self.axis % len(shape)
        n = int(jnp.shape(jnp.asarray(self.indices))[0])
        return tuple(shape[:axis]) + (n,) + tuple(shape[axis + 1:])


@register_plugin
@dataclasses.dataclass(frozen=True)
class Compress(Plugin):
    """Block-sparse zero-skipping (the paper's compressed-tunnel case).

    Splits the logical rows into ``block_rows`` blocks and records which
    blocks carry any nonzero; the payload becomes a :class:`CTensor` whose
    ``wire_nbytes`` charges only occupied blocks + the mask side-channel.
    Exact: ``Decompress(Compress(x)) == x`` bitwise (zero blocks are zero).
    """

    block_rows: int = 8
    name: str = "compress_blocksparse"
    pytree_payload = True               # emits a CTensor

    def __call__(self, x) -> CTensor:
        m = x.shape[-2]
        if m % self.block_rows:
            raise ValueError(f"logical rows {m} not divisible by "
                             f"block_rows={self.block_rows}")
        blocks = x.reshape(x.shape[:-2] + (m // self.block_rows,
                                           self.block_rows, x.shape[-1]))
        mask = jnp.any(blocks != 0, axis=(-1, -2))
        return CTensor(values=x, mask=mask)

    def emit(self, x) -> CTensor:
        # Kernel form: Mosaic lowers neither rank-1 vectors nor bool arrays,
        # so in VMEM the mask is a float keep factor per logical row,
        # (..., M, 1), constant over each block.  It never leaves the
        # kernel: a chain whose output is still compressed does not fuse
        # (plugin_compiler), and Decompress.emit consumes this form.
        m, n = x.shape[-2:]
        br = self.block_rows
        nz = (x.astype(jnp.float32) != 0).astype(jnp.float32)
        blocks = nz.reshape(x.shape[:-2] + (m // br, br, n))
        occ = jnp.max(jnp.max(blocks, axis=-1, keepdims=True), axis=-2,
                      keepdims=True)
        keep = jnp.broadcast_to(occ, occ.shape[:-2] + (br, 1))
        return CTensor(values=x, mask=keep.reshape(x.shape[:-2] + (m, 1)))


@register_plugin
@dataclasses.dataclass(frozen=True)
class Decompress(Plugin):
    """Inverse of :class:`Compress`: re-expand the dense carrier.

    Multiplies by the mask so a payload whose zero blocks were dropped on the
    wire reconstructs exactly (the carrier is already zero there, so this is
    the identity on round-trips — bit-identical by construction).
    """

    name: str = "decompress_blocksparse"
    unpacks_payload = True

    def __call__(self, x: CTensor):
        v, mask = x.values, x.mask
        m = v.shape[-2]
        block_rows = m // mask.shape[-1]
        keep = jnp.repeat(mask, block_rows, axis=-1).astype(v.dtype)
        return v * keep[..., :, None]

    def emit(self, x: CTensor):
        # consumes Compress.emit's per-row keep factor; multiplying by an
        # exact 0/1 in f32 is bit-equal to the composition's native product
        v = x.values
        return (v.astype(jnp.float32) * x.mask).astype(v.dtype)


@register_plugin
@dataclasses.dataclass(frozen=True)
class ReduceStage(Plugin):
    """On-the-fly reduction over the logical rows (reduce-endpoint stage).

    ``op`` is ``sum`` or ``max``; with ``keepdims`` (default) the rank is
    preserved — (..., M, N) -> (..., 1, N) — so the stage composes with
    layouts.  ``keepdims=False`` drops the row dim and must (and does)
    declare ``changes_rank``.
    """

    op: str = "sum"
    keepdims: bool = True
    name: str = "reduce_stage"

    def __post_init__(self):
        if self.op not in ("sum", "max"):
            raise ValueError(f"ReduceStage op must be sum|max, got {self.op!r}")

    @property
    def changes_rank(self):
        return not self.keepdims

    def __call__(self, x):
        fn = jnp.sum if self.op == "sum" else jnp.max
        return fn(x, axis=-2, keepdims=self.keepdims)

    emit = __call__

    def out_logical_shape(self, shape):
        if self.keepdims:
            return tuple(shape[:-2]) + (1, shape[-1])
        return tuple(shape[:-2]) + (shape[-1],)


def apply_chain(plugins: Sequence[Plugin], x: Any) -> Any:
    """Cascade plugins (paper: 'one or more plugins can be cascaded')."""
    for p in plugins:
        x = p(x)
    return x


def chain_out_shape(plugins: Sequence[Plugin], shape: Tuple[int, ...]) -> Tuple[int, ...]:
    for p in plugins:
        new = tuple(p.out_logical_shape(tuple(shape)))
        if len(new) != len(shape) and not p.changes_rank:
            raise ValueError(
                f"plugin {p.name!r} changed logical rank {len(shape)} -> "
                f"{len(new)} without declaring it; set changes_rank=True on "
                f"the plugin (or fix its out_logical_shape) so descriptors "
                f"fail at CFG time instead of deep in the lowered program")
        shape = new
    return tuple(shape)


def chain_out_dtype(plugins: Sequence[Plugin], dtype):
    """Dtype after a cascade — the descriptor's compile-time dtype contract."""
    for p in plugins:
        dtype = p.out_dtype(dtype)
    return dtype
