"""xdma.transfer(): the single entry point for every XDMA data movement.

Paper §II-B: software offloads one CSR instruction; the Controller turns it
into an ``XDMACfg``, routes it to the right half-XDMAs, and dispatches tasks
in order.  This module is that Controller.  :func:`transfer` consumes a
:class:`~repro.core.descriptor.XDMADescriptor` and dispatches — *from the
descriptor alone* — to one of the lowering backends:

* local + backend auto        -> one fused Pallas kernel when the plugin
  chain is emit-capable (``plugin_compiler``), else ``engine.xdma_copy``
* local + backend fused       -> ``engine.xdma_copy``   (fused XLA stream)
* local + backend compiled    -> ``plugin_compiler.compile_local`` (forced)
* local + backend pallas      -> ``engine.xdma_copy_pallas`` (TPU kernel)
* dst peer                    -> ``remote.xdma_ppermute``    (tunnel)
* dst all_to_all              -> ``remote.xdma_all_to_all``  (MoE dispatch)
* dst reduce                  -> ``remote.compressed_psum`` / ``lax.psum``
* dst multicast (mesh-axis)   -> ``remote.xdma_ppermute``    (rotating hop)

Node-addressed multicast (``Endpoint.multicast(dsts=...)``) is *not* a
lowering: it is routed as a tree of per-hop local tasks by
``DistributedScheduler.submit_multicast`` (DESIGN.md §14) and raises here.

Remote movements additionally compile each endpoint side's chain into a
single Pallas kernel when possible (``plugin_compiler.maybe_compile_side``).

The CFG phase happens **once per descriptor**: the lowered callable is built
and (for local movements) jitted on first use, then cached by descriptor
identity.  Every later ``transfer`` with the same descriptor is a pure Data
phase — no retracing, no recompilation (see :func:`cache_stats`, which makes
the property testable; ``tests/test_api.py``'s CFG-cache tests check it).

:class:`XDMAQueue` is the Controller's in-order task queue (paper §II-B):
a sequence of descriptors lowered as one fused, ordered program.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.runtime import telemetry as _tm

from . import autotune as _autotune
from . import engine
from . import plugin_compiler
from . import plugins as P
from . import remote
from .descriptor import Endpoint, XDMADescriptor

__all__ = ["transfer", "XDMAQueue", "cache_stats", "clear_cache",
           "cache_capacity", "set_cache_capacity"]


# -- the movement-plane capture slot (DESIGN.md §9) ---------------------------
# The ambient TransferTrace installed by repro.runtime.trace.capture(), or
# None.  It lives here (not in runtime/) so every chokepoint — transfer(),
# XDMAQueue, DistributedScheduler.submit — shares one slot without an import
# cycle; when no capture is open the cost is a single `is None` check.
# (The telemetry session slot follows the same discipline, but lives in
# repro.runtime.telemetry — a leaf module everything can import.)
_CAPTURE = None


# -- the CFG cache: descriptor -> lowered callable ---------------------------
# Counters live in the telemetry plane (DESIGN.md §11): one CSR-style bank
# per domain, read through telemetry.snapshot() alongside every other
# subsystem's counters.  cache_stats() stays as a thin view.
_BANK = _tm.bank("cfg_cache")


class _CacheStats:
    """View over ``telemetry.bank("cfg_cache")`` keeping the historical
    ``cache_stats()`` attribute surface (hits/misses/evictions/size)."""

    __slots__ = ()

    @property
    def hits(self):
        return _BANK.get("hits")

    @property
    def misses(self):
        return _BANK.get("misses")

    @property
    def evictions(self):
        return _BANK.get("evictions")

    @property
    def size(self):
        return len(_CACHE)

    def __repr__(self):
        return (f"_CacheStats(hits={self.hits}, misses={self.misses}, "
                f"evictions={self.evictions}, size={self.size})")


# LRU: key -> (descriptor kept alive so id-keys stay unique, lowered callable).
# Bounded so descriptor churn (per-call descriptors carrying weight arrays,
# id-keyed) cannot grow it without limit; the default is generous enough that
# steady-state workloads never evict.
_CACHE: "collections.OrderedDict[Any, Tuple[XDMADescriptor, Callable]]" = \
    collections.OrderedDict()
_STATS = _CacheStats()
_DEFAULT_CAPACITY = 1024
_CAPACITY = _DEFAULT_CAPACITY


def cache_stats() -> _CacheStats:
    """Hit/miss/eviction counters for the per-descriptor CFG cache.

    .. deprecated:: PR 7
        A thin view over ``telemetry.bank("cfg_cache")``; prefer
        :func:`repro.runtime.telemetry.snapshot`, which reports these
        counters alongside every other subsystem's."""
    return _STATS


def cache_capacity() -> int:
    """Current CFG-cache capacity (entries)."""
    return _CAPACITY


def set_cache_capacity(n: int) -> None:
    """Bound the CFG cache to ``n`` entries (LRU eviction), evicting now if
    already over.  The capacity survives :func:`clear_cache`."""
    global _CAPACITY
    if n < 1:
        raise ValueError("cache capacity must be >= 1")
    _CAPACITY = int(n)
    _evict_to_capacity()


def _evict_to_capacity() -> None:
    while len(_CACHE) > _CAPACITY:
        _CACHE.popitem(last=False)      # least recently used first
        _BANK.inc("evictions")


# Sibling caches holding compositions of the lowerings above (e.g. the
# scheduler's batched-round programs).  They register here so clear_cache()
# cannot leave a stale composition that silently bypasses a freshly cleared
# CFG cache.
_AUX_CACHES: List["collections.OrderedDict"] = []
_AUX_CACHES.append(_autotune._CACHE)      # memoized layout searches
_AUX_CACHES.append(_autotune._RESOLVED)   # memoized auto-descriptor resolutions


def clear_cache() -> None:
    _CACHE.clear()
    _BANK.clear()
    for aux in _AUX_CACHES:
        aux.clear()


def _resolve_auto(desc: XDMADescriptor, x, link=None) -> XDMADescriptor:
    """Substitute tuned concrete layouts for ``auto`` endpoints against the
    input buffer (the Data phase needs a concrete descriptor to dispatch).
    An auto *src* treats the buffer as already logical — the pick there is
    which physical walk to stream it with.  ``link`` is the fabric the
    movement rides (the scheduler threads its routed link in; plain
    ``transfer`` tunes for the default fabric)."""
    if not desc.has_auto:
        return desc
    leaf = x.values if isinstance(x, (P.QTensor, P.CTensor)) else x
    shape = tuple(int(s) for s in leaf.shape)
    if not desc.src.layout.is_auto:
        shape = desc.src.layout.logical_shape(shape)
    return _autotune.resolve_descriptor(desc, shape, leaf.dtype, link=link)


def _compiled_or(desc: XDMADescriptor,
                 compiled: Optional[Callable]) -> Callable:
    """Compiled fused kernel with a structural escape hatch: payload pytrees
    (QTensor/CTensor inputs) re-enter through the XLA composition, which
    handles them natively.  The branch is on pytree structure, so it is
    jit-stable."""
    def run(x):
        if compiled is None or isinstance(x, (P.QTensor, P.CTensor)):
            return engine.xdma_copy(x, desc)
        return compiled(x)
    return jax.jit(run)


def _lower(desc: XDMADescriptor) -> Callable:
    """Build the Data-phase callable for a descriptor (the CFG phase).
    Kernels compile for a TPU and interpret on the CPU backend
    (:func:`repro.kernels.interpret_mode`)."""
    movement = desc.movement
    if movement == "local":
        if desc.backend == "pallas":
            def run(x):
                return engine.xdma_copy_pallas(x, desc)
            return run
        if desc.backend == "compiled":
            # forced single-kernel lowering: raises on non-fusible chains
            return jax.jit(plugin_compiler.compile_local(desc))
        if desc.backend == "auto":
            # plugin-compiler policy: fuse emit-capable plugin chains into
            # one Pallas kernel; everything else keeps the XLA composition
            # (see plugin_compiler.cfg_stats() for the fused/fallback tally)
            compiled = plugin_compiler.maybe_compile_local(desc)
            if compiled is not None:
                return _compiled_or(desc, compiled)
        # fused path: jit here so repeated transfers share one executable
        return jax.jit(lambda x: engine.xdma_copy(x, desc))

    # Remote movements run inside the caller's shard_map/jit: lower to a
    # plain callable (reader -> pre host -> link -> post host -> writer).
    # Each endpoint side with a fully emit-capable chain is compiled into a
    # single Pallas kernel (reader+pre / post+writer); other sides keep the
    # composition the remote backends apply around the collective.
    ep = desc.remote
    if movement == "multicast" and ep is None:
        # node-addressed multicast has no single-collective lowering: the
        # scheduler forks it into per-hop tree tasks
        raise ValueError(
            "node-addressed multicast descriptors are routed by "
            "DistributedScheduler.submit_multicast (they fork into per-hop "
            "tree tasks), not lowered by transfer(); use "
            "Endpoint.multicast_axis for the mesh-axis collective spelling")
    src_side = dst_side = None
    if movement in ("peer", "all_to_all", "multicast"):
        src_side = plugin_compiler.maybe_compile_side(
            desc.src.layout, desc.pre, side="src", d_buf=desc.d_buf)
        dst_side = plugin_compiler.maybe_compile_side(
            desc.dst.layout, desc.post, side="dst", d_buf=desc.d_buf)

    def run_remote(x):
        fuse_src = (src_side is not None
                    and not isinstance(x, (P.QTensor, P.CTensor)))
        if fuse_src and len(x.shape) >= 2:   # reduce-style flat payloads skip
            desc.validate(desc.src.layout.logical_shape(x.shape))
        if fuse_src:
            logical = src_side(x)            # one kernel: reader + pre chain
            pre = ()
        else:
            logical = engine.reader(x, desc.src.layout)
            pre = desc.pre
            if getattr(logical, "ndim", 0) >= 2:
                desc.validate(logical.shape)
        post = desc.post if dst_side is None else ()
        if movement in ("peer", "multicast"):
            # mesh-axis multicast is the rotating one-hop broadcast: the same
            # collective permute as peer, recorded as multicast in the ledger
            y = remote.xdma_ppermute(logical, ep.axis, list(ep.perm),
                                     pre=pre, post=post)
        elif movement == "all_to_all":
            y = remote.xdma_all_to_all(logical, ep.axis,
                                       split_axis=ep.split_axis,
                                       concat_axis=ep.concat_axis,
                                       pre=pre, post=post)
        elif movement == "reduce":
            # A Quantize/Dequantize pair around the link is the wire codec:
            # compressed_psum owns it (its two-phase decomposition re-quantizes
            # internally).  Any other pre/post plugins run as normal hosts —
            # a Dequantize without a matching pre Quantize is NOT a codec and
            # stays on the post host (applying it to a non-QTensor then fails
            # loudly instead of silently breaking the dtype contract).
            pre_rest = tuple(p for p in desc.pre if not isinstance(p, P.Quantize))
            codec = len(pre_rest) != len(desc.pre)
            post_rest = (tuple(p for p in desc.post
                               if not isinstance(p, P.Dequantize))
                         if codec else desc.post)
            y = P.apply_chain(pre_rest, logical)
            if codec:
                deq = [p for p in desc.post if isinstance(p, P.Dequantize)]
                out_dtype = deq[0].dtype if deq else y.dtype
                y = remote.compressed_psum(y, ep.axis, ep.axis_size,
                                           out_dtype=out_dtype)
            else:
                y = remote.xdma_psum(y, ep.axis)
            y = P.apply_chain(post_rest, y)
        else:  # pragma: no cover - movement is validated by the descriptor
            raise ValueError(f"unknown movement {movement!r}")
        if movement in ("peer", "all_to_all", "multicast") and dst_side is not None:
            if not isinstance(y, (P.QTensor, P.CTensor)):
                return dst_side(y)           # one kernel: post chain + writer
            y = P.apply_chain(desc.post, y)  # pytree payload: composition
        if isinstance(y, P.QTensor):
            return P.QTensor(values=engine.writer(y.values, desc.dst.layout),
                             scales=y.scales)
        if isinstance(y, P.CTensor):
            return P.CTensor(values=engine.writer(y.values, desc.dst.layout),
                             mask=y.mask)
        return engine.writer(y, desc.dst.layout)

    return run_remote


def _lowered(desc: XDMADescriptor) -> Callable:
    key = desc.cache_key()
    entry = _CACHE.get(key)
    if entry is not None:
        _BANK.inc("hits")
        _CACHE.move_to_end(key)
        return entry[1]
    _BANK.inc("misses")
    fn = _lower(desc)
    _CACHE[key] = (desc, fn)
    _evict_to_capacity()
    return fn


def transfer(x: jnp.ndarray, desc: XDMADescriptor) -> Any:
    """Execute one XDMA task described entirely by ``desc``.

    ``x`` is the physical buffer at the src endpoint; the return value is the
    physical buffer at the dst endpoint (a :class:`~repro.core.plugins.QTensor`
    when the surviving chain ends in ``Quantize``).  Remote movements must be
    called inside ``shard_map`` (or jit with sharded inputs), exactly like
    the backend functions they lower to.

    When a :func:`repro.runtime.trace.capture` scope is open, every call is
    recorded into the ambient :class:`~repro.runtime.trace.TransferTrace`;
    when a :func:`repro.runtime.telemetry.session` is open, the call is
    additionally timed as an ``xdma.transfer`` span.  Both hooks are a
    single ``is None`` check when off.
    """
    desc = _resolve_auto(desc, x)
    tel = _tm._ACTIVE
    if tel is None:
        out = _lowered(desc)(x)
    else:
        with tel.span("xdma.transfer", track="transfer",
                      desc=desc.summary(), movement=desc.movement):
            out = _lowered(desc)(x)
    if _CAPTURE is not None:
        _CAPTURE.record_transfer(x, desc, out)
    return out


# -- the Controller's in-order task queue (paper §II-B) ----------------------
class XDMAQueue:
    """An ordered sequence of XDMA tasks lowered as one program.

    ``run(x)`` chains every task in submission order — for all-local queues
    the whole chain is jitted as a *single* fused executable (one CFG phase
    for the queue), mirroring the Controller popping its task FIFO in order.
    ``run_task(x, i)`` executes one task through the same cache, for call
    sites that interleave compute between tasks (e.g. MoE dispatch -> expert
    FFN -> MoE return).
    """

    def __init__(self, descriptors: Sequence[XDMADescriptor] = (),
                 name: str = "queue"):
        self.name = name
        self._descs: List[XDMADescriptor] = []
        self._fused: Optional[Callable] = None
        self._tasks: Dict[Tuple, Callable] = {}
        for d in descriptors:
            self.submit(d)

    def submit(self, desc: XDMADescriptor) -> int:
        """Append a task; returns its index in dispatch order."""
        if not isinstance(desc, XDMADescriptor):
            raise TypeError(f"XDMAQueue.submit takes a descriptor, got {type(desc)}")
        self._descs.append(desc)
        self._fused = None              # new CFG phase needed for the chain
        return len(self._descs) - 1

    @property
    def descriptors(self) -> Tuple[XDMADescriptor, ...]:
        return tuple(self._descs)

    def __len__(self) -> int:
        return len(self._descs)

    def __iter__(self):
        return iter(self._descs)

    @property
    def is_local(self) -> bool:
        return all(not d.is_remote for d in self._descs)

    # -- compile-time contracts ---------------------------------------------
    def out_logical_shape(self, in_logical_shape: Sequence[int]) -> Tuple[int, ...]:
        shape = tuple(in_logical_shape)
        for d in self._descs:
            shape = d.out_logical_shape(shape)
        return shape

    def out_dtype(self, in_dtype):
        dtype = in_dtype
        for d in self._descs:
            dtype = d.out_dtype(dtype)
        return dtype

    # -- execution ----------------------------------------------------------
    def _task(self, i: int,
              desc: Optional[XDMADescriptor] = None) -> Callable:
        # Queue-local memo (not the global CFG cache): queues are routinely
        # rebuilt per trace inside shard_map bodies, and id-keyed global
        # entries would accumulate; the queue's own lifetime bounds these.
        # Auto descriptors resolve per input shape, so their resolved form
        # joins the key (resolve_descriptor memoizes, keeping ids stable).
        base = self._descs[i]
        if desc is None:
            desc = base
        key = (i,) if desc is base else (i, desc.cache_key())
        fn = self._tasks.get(key)
        if fn is None:
            fn = _lower(desc)
            self._tasks[key] = fn
        return fn

    def run_task(self, x, i: int):
        """Dispatch task ``i`` alone (in-order use is the caller's contract)."""
        desc = _resolve_auto(self._descs[i], x)
        tel = _tm._ACTIVE
        if tel is None:
            out = self._task(i, desc)(x)
        else:
            with tel.span("XDMAQueue.run_task", track="queue",
                          queue=self.name, task=i):
                out = self._task(i, desc)(x)
        if _CAPTURE is not None:
            _CAPTURE.record_transfer(x, desc, out, source="queue",
                                     label=f"{self.name}[{i}]")
        return out

    def run(self, x):
        """Dispatch the whole queue in order as one fused program."""
        if not self._descs:
            return x
        fused = self._fused
        if fused is None:
            descs = tuple(self._descs)

            def chain(v):
                for i, d in enumerate(descs):
                    d = _resolve_auto(d, v)            # concrete per trace
                    if d.movement == "local" and d.backend != "pallas":
                        v = engine.xdma_copy(v, d)     # fuse into the chain
                    else:
                        v = self._task(i, d)(v)
                return v

            fused = jax.jit(chain) if self.is_local else chain
            self._fused = fused
        tel = _tm._ACTIVE
        if tel is None:
            out = fused(x)
        else:
            with tel.span("XDMAQueue.run", track="queue",
                          queue=self.name, tasks=len(self)):
                out = fused(x)
        if _CAPTURE is not None:
            _CAPTURE.record_queue(self, x, out)
        return out

    def submit_to(self, sched, x, *, link=None, tenant: str = "",
                  deps: Sequence = ()):
        """Post the whole queue through a scheduler's descriptor rings: one
        ring post (doorbell) per task, chained in order — the async analogue
        of :meth:`run`, value-identical to it because both sides dispatch
        through the same per-descriptor cached lowering.

        ``link=None`` routes the *first* task by the scheduler's round-robin
        policy and pins the rest of the chain to the same link, preserving
        the in-order single-FIFO semantics of :meth:`run`.  Returns the
        final task's :class:`~repro.runtime.scheduler.XDMAFuture`.
        """
        if not self._descs:
            raise ValueError(f"XDMAQueue {self.name!r} is empty: nothing to "
                             "submit")
        fut = None
        for i, d in enumerate(self._descs):
            fut = sched.submit(x if fut is None else fut, d, link=link,
                               deps=tuple(deps) if fut is None else (),
                               tenant=tenant, label=f"{self.name}[{i}]")
            if link is None:
                # pin the rest of the chain to the routed link: a chain
                # scattered round-robin would serialize on deps anyway but
                # misreport per-link traffic
                link = sched._tasks[fut.task_id].resource
        return fut

    def summary(self) -> str:
        lines = [f"XDMAQueue({self.name!r}, {len(self)} tasks)"]
        lines += [f"  [{i}] {d.summary()}" for i, d in enumerate(self._descs)]
        return "\n".join(lines)
