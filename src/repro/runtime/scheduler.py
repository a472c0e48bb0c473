"""Async XDMA dispatch: per-link descriptor rings, futures, batched rounds.

Paper §II-B gives each *link* its own Controller task queue: tasks on one
link dispatch strictly in order, tasks on different links dispatch
concurrently.  :class:`DistributedScheduler` is that Controller distributed
across a :class:`~repro.runtime.topology.Topology`, with the production
submission shape (DESIGN.md §12): fixed-depth **descriptor rings** instead
of unbounded FIFOs.

* ``submit(x, desc, link=..., deps=..., tenant=...)`` posts one descriptor
  into a per-(link, tenant) :class:`~repro.runtime.ring.DescriptorRing` and
  rings its doorbell — the CSR write the simulator prices via
  ``Link.csr_write_cost``, separately from the data transfer.  It returns an
  :class:`XDMAFuture` immediately — the token other tasks name as a
  dependency (the CFG phase stays compile-time: lowering reuses the
  per-descriptor cache in :mod:`repro.core.api`).  A post consumes a ring
  *credit*; when the ring is full, the ``block`` policy (default) drains
  scheduling rounds until a completion returns one, and the ``error`` policy
  raises :class:`~repro.runtime.ring.WouldBlock` for the caller to handle.
* ``submit_compute(fn, ...)`` enqueues interleaved compute (expert FFN, host
  preprocessing) on a named compute engine so transfer/compute overlap is
  visible to the simulator.
* ``step()`` runs one *scheduling round*: it takes one ready ring head per
  resource — round-robin over that resource's tenant rings, which is what
  keeps a starved tenant near its fair share under adversarial load — and
  dispatches them together.  Local concrete-array tasks run as batched XLA
  programs, one per group of equal (descriptor, shape, dtype), in
  power-of-two chunks of at most ``_CHUNK_MAX`` tasks, each one cached
  ``jit_sched_round`` program; everything else dispatches alone.  Every
  task runs exactly the cached lowering ``xdma.transfer`` uses, so results
  are bit-identical to a serial replay of the same descriptors.
* ``flush()`` drains the rings in the same rounds, but its rounds are
  *logical*: it plans them (ring pops, round numbers, stall accounting)
  exactly as repeated ``step()`` would, and carries the batched tasks
  across rounds, so a drain of hundreds of page ops runs as a few
  programs.  The batch runs early before a task that reads one of its
  values and before any task that cannot batch.  Completions, makespan,
  the replay and every counter match a drain by repeated ``step()``; only
  the number of programs launched differs.

Every dispatch retires its ring head into a completion queue
(``scheduler.completions``) carrying the simulated span — which resolves
futures, returns the credit, and keeps an *incremental* makespan that is
bit-equal to the full event-driven replay once the rings are drained.
``sim_tasks()`` / ``report()`` still replay the schedule through
:mod:`repro.runtime.simulator` for the full timeline.

The scheduler is trace-transparent: submitting tracers (inside ``shard_map``
or ``jit``) simply threads the symbolic values through the same round
structure, skipping only the round-batching jit — the recorded schedule is
identical, which is how the MoE a2a/FFN overlap gets simulated.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import api as _api
from repro.core import autotune as _autotune
from repro.core import layouts as _L
from repro.core.descriptor import XDMADescriptor, describe

from . import telemetry as _tm
from .ring import DEFAULT_RING_DEPTH, Completion, DescriptorRing, WouldBlock
from .simulator import SimReport, SimTask, simulate
from .topology import MulticastTree, Topology

__all__ = ["XDMAFuture", "MulticastFuture", "DistributedScheduler"]

# CSR-style counter banks (DESIGN.md §11): per-link byte/burst/stall tallies,
# per-resource queue-occupancy high-water marks, and the ring plane's
# doorbell / credit / fairness counters.  Always counting — the increments
# are dict adds, same cost class as the old ad-hoc stats — while per-task
# span timing stays gated on an active telemetry session.
_LINKS = _tm.bank("links")
_QUEUES = _tm.bank("queues")
_RINGS = _tm.bank("rings")
# The multicast plane (DESIGN.md §14): trees built, hops/forks posted, and
# the wire bytes shared hops avoid moving vs N private unicast copies.
_MCAST = _tm.bank("multicast")
# XDMA tasks that ran inside a fused round program (``batched_tasks``) and
# XDMA programs launched (``programs``: fused rounds or chunks, and single
# dispatches); all dispatched tasks are the ``links`` bank's
# ``tasks:<resource>`` counters.
_SCHED = _tm.bank("sched")

# Batched-round programs, shared by every scheduler instance: keyed by the
# group's descriptor identity (same scheme as the CFG cache) and the chunk's
# width, so a fresh scheduler per step replays compiled rounds instead of
# retracing them.
# Bounded LRU for the same reason the CFG cache is: id-keyed descriptor
# churn must not pin programs (and captured weight arrays) forever.
_ROUND_CACHE: "collections.OrderedDict[Any, Callable]" = collections.OrderedDict()
_ROUND_CACHE_CAPACITY = 256
# Widest fused program the scheduler launches for a group of equal descriptors:
# a group runs as chunks of power-of-two widths up to this, so a descriptor
# compiles at most log2(_CHUNK_MAX) + 1 round programs per input geometry.
_CHUNK_MAX = 64
# Round programs inline CFG-cache lowerings, so xdma.clear_cache() must drop
# them too — a stale round program would bypass the cleared cache.
_api._AUX_CACHES.append(_ROUND_CACHE)


def _burst_bytes(desc: XDMADescriptor, value: Any) -> Optional[int]:
    """Pattern-contiguity burst of one dispatched task, from the descriptor's
    composed affine pattern (None when no pattern applies — payload pytrees,
    plugin chains, remote links — which keeps the one-burst pricing)."""
    shape = getattr(value, "shape", None)
    dtype = getattr(value, "dtype", None)
    if shape is None or dtype is None or len(shape) < 2:
        return None
    try:
        return desc.burst_bytes(desc.src.layout.logical_shape(shape), dtype)
    except (ValueError, KeyError):
        return None


def _nbytes(value: Any) -> int:
    """Payload bytes of an array / QTensor / pytree (works on tracers)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(value):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * int(np.dtype(dtype).itemsize)
    return total


def _round_program(fns: Tuple[Callable, ...]) -> Callable:
    """One jitted program running a round's batched lowerings (the profile
    names it ``jit_sched_round``)."""
    def sched_round(xs):
        return tuple(f(x) for f, x in zip(fns, xs))
    return jax.jit(sched_round)


def _chunk_widths(n: int) -> List[int]:
    """The fused-program widths a group of ``n`` equal tasks runs as: full
    ``_CHUNK_MAX`` chunks, then the remainder's binary digits, largest first
    (200 -> 64, 64, 64, 8)."""
    widths = [_CHUNK_MAX] * (n // _CHUNK_MAX)
    rest = n % _CHUNK_MAX
    while rest:
        w = 1 << (rest.bit_length() - 1)
        widths.append(w)
        rest -= w
    return widths


def _signature(x: Any) -> Any:
    """The input geometry one group shares: an array's shape, dtype and
    placement, a payload pytree's structure and leaf geometries."""
    if isinstance(x, jax.Array):
        return x.shape, x.dtype, x.sharding
    leaves, tree = jax.tree_util.tree_flatten(x)
    return tree, tuple((getattr(l, "shape", None), getattr(l, "dtype", None))
                       for l in leaves)


class XDMAFuture:
    """Handle for a submitted task: a dependency token and a deferred result."""

    __slots__ = ("_sched", "task_id")

    def __init__(self, sched: "DistributedScheduler", task_id: int):
        self._sched = sched
        self.task_id = task_id

    def done(self) -> bool:
        return self._sched._tasks[self.task_id].done

    def result(self) -> Any:
        """Drain the scheduler until *this* task has dispatched, then return
        its output (the physical dst buffer, exactly as ``xdma.transfer``).
        Later independent tasks stay pending — ``result()`` runs scheduling
        rounds only until this task's completion retires; use ``flush()`` to
        drain everything."""
        t = self._sched._tasks[self.task_id]
        while not t.done:
            self._sched.step()
        return t.value

    def __repr__(self):
        state = "done" if self.done() else "pending"
        return f"XDMAFuture(task={self.task_id}, {state})"


class MulticastFuture:
    """Handle for one tree-routed multicast: the fan of per-destination
    delivery futures plus the synthesized :class:`MulticastTree`.

    ``result()`` returns the per-destination dst buffers in the descriptor's
    destination order; the multicast *completes* only when every leaf hop
    has retired (all-leaves semantics — intermediate forwarding hops alone
    do not complete it)."""

    __slots__ = ("_sched", "tree", "_delivery")

    def __init__(self, sched: "DistributedScheduler", tree: MulticastTree,
                 delivery: "collections.OrderedDict[str, XDMAFuture]"):
        self._sched = sched
        self.tree = tree
        self._delivery = delivery

    @property
    def dsts(self) -> Tuple[str, ...]:
        return tuple(self._delivery)

    def future(self, dst: str) -> XDMAFuture:
        """The delivery future for one destination node."""
        return self._delivery[dst]

    def done(self) -> bool:
        return all(f.done() for f in self._delivery.values())

    def result(self) -> Tuple[Any, ...]:
        """Drain until every destination's delivery hop has dispatched, then
        return the per-destination buffers (descriptor destination order)."""
        return tuple(f.result() for f in self._delivery.values())

    def result_at(self, dst: str) -> Any:
        return self._delivery[dst].result()

    def dst_descriptors(self) -> Dict[str, XDMADescriptor]:
        """The (possibly auto-resolved) delivery-hop descriptor per
        destination — how each dst's layout actually resolved against its
        routed link."""
        return {d: self._sched._tasks[f.task_id].desc
                for d, f in self._delivery.items()}

    def __repr__(self):
        state = "done" if self.done() else "pending"
        return (f"MulticastFuture({len(self._delivery)} dsts, "
                f"{len(self.tree.hops)} hops, {state})")


@dataclasses.dataclass
class _Task:
    id: int
    kind: str                            # "xdma" | "compute"
    resource: str
    deps: Tuple[int, ...]
    desc: Optional[XDMADescriptor] = None
    fn: Optional[Callable] = None
    inputs: Tuple[Any, ...] = ()         # arrays or XDMAFutures
    cost_s: float = 0.0
    nbytes: Optional[int] = None
    burst_bytes: Optional[int] = None    # pattern contiguity (link pricing)
    label: str = ""
    tenant: str = ""                     # which per-tenant ring holds it
    csr_writes: int = 0                  # doorbell CSR writes to price
    done: bool = False
    value: Any = None
    round: int = -1
    event: Any = None                    # TraceEvent when a capture was open
    trace: Any = None                    # the TransferTrace owning `event`


class DistributedScheduler:
    """The distributed Controller: descriptor rings per (resource, tenant).

    ``ring_depth`` bounds every ring (credits = free slots); ``backpressure``
    picks the full-ring policy — ``"block"`` (default) drains scheduling
    rounds inside ``submit`` until a credit frees, ``"error"`` raises
    :class:`~repro.runtime.ring.WouldBlock` for the caller to handle.
    Blocking can never deadlock: dependencies must already be submitted, so
    the oldest pending task always sits dep-satisfied at its ring head and
    every round retires at least one descriptor."""

    def __init__(self, topology: Topology, *,
                 name: str = "sched", ring_depth: int = DEFAULT_RING_DEPTH,
                 backpressure: str = "block"):
        if backpressure not in ("block", "error"):
            raise ValueError(f"backpressure must be 'block' or 'error', "
                             f"got {backpressure!r}")
        self.topology = topology
        self.name = name
        self.ring_depth = int(ring_depth)
        self.backpressure = backpressure
        self._tasks: Dict[int, _Task] = {}
        # resource -> tenant -> its descriptor ring (created on first post)
        self._rings: Dict[str, Dict[str, DescriptorRing]] = {
            n: {} for n in topology.link_names}
        self._rr: Dict[str, int] = {}    # per-resource tenant-arbitration cursor
        self._dispatched: Dict[str, List[int]] = {}  # per-resource pop order
        self.completions: List[Completion] = []      # the completion queue
        self._sim_end: Dict[int, float] = {}         # task id -> simulated end
        self._sim_free: Dict[str, float] = {}        # resource -> busy-until
        self._makespan_inc = 0.0         # incremental makespan (== replay)
        # per-geometry memos of a dispatch's simulated duration and its
        # ``links`` counter increments (pure functions of the key)
        self._link_time: Dict[Tuple, float] = {}
        self._link_incs: Dict[Tuple, Tuple] = {}
        self._desc_facts: Dict[int, Tuple] = {}   # see _facts
        self._pending = 0
        self._next_id = 0
        self._next_link = 0              # round-robin routing cursor
        self._rounds = 0

    def _ring(self, resource: str, tenant: str) -> DescriptorRing:
        rings = self._rings.setdefault(resource, {})
        ring = rings.get(tenant)
        if ring is None:
            who = f"{resource}/{tenant}" if tenant else resource
            ring = DescriptorRing(who, self.ring_depth)
            rings[tenant] = ring
        return ring

    # -- submission ----------------------------------------------------------
    def _route(self, desc: XDMADescriptor, link: Optional[str]) -> str:
        if link is not None:
            self.topology.link(link)     # raises on unknown names
            return link
        # Default policy: round-robin over the fabric — the Controller's
        # load-balancing when the descriptor does not pin a link.
        names = self.topology.link_names
        if not names:
            raise ValueError(f"topology {self.topology.name!r} has no links")
        name = names[self._next_link % len(names)]
        self._next_link += 1
        return name

    def _enqueue(self, task: _Task) -> XDMAFuture:
        for d in task.deps:
            if d not in self._tasks:
                raise ValueError(f"dependency on unknown task {d}")
        ring = self._ring(task.resource, task.tenant)
        if ring.is_full:
            _RINGS.inc(f"full:{task.resource}")
            if self.backpressure == "error":
                raise WouldBlock(task.resource, task.tenant, ring.depth)
            # block: drain scheduling rounds until a completion returns a
            # credit.  The ring's own head is pending, so step() always
            # progresses (or raises on a genuine dependency cycle).
            while ring.is_full:
                self.step()
        self._tasks[task.id] = task
        self._pending += 1
        ring.post(task.id)               # descriptor write + doorbell
        _RINGS.inc(f"doorbells:{task.resource}")
        occupied = sum(r.occupancy
                       for r in self._rings[task.resource].values())
        _QUEUES.record_max(f"occupancy_hw:{task.resource}", occupied)
        _RINGS.record_max(f"credits_hw:{task.resource}", occupied)
        return XDMAFuture(self, task.id)

    def _dep_events(self, deps: Tuple[int, ...]) -> Tuple[int, ...]:
        """Ledger event ids of dependency tasks.  Unknown dep ids are left
        for _enqueue's validation to reject with its designed error."""
        return tuple(t.event.id for t in
                     (self._tasks.get(d) for d in deps)
                     if t is not None and t.event is not None)

    @staticmethod
    def _dep_ids(inputs: Sequence[Any], deps: Sequence) -> Tuple[int, ...]:
        ids: List[int] = []
        for obj in list(inputs) + list(deps):
            if isinstance(obj, XDMAFuture):
                if obj.task_id not in ids:
                    ids.append(obj.task_id)
        return tuple(ids)

    def submit(self, x: Any, desc: XDMADescriptor, *,
               link: Optional[str] = None, deps: Sequence = (),
               nbytes: Optional[int] = None, label: str = "",
               tenant: str = "") -> XDMAFuture:
        """Post one XDMA descriptor into a per-(link, tenant) ring; returns
        its future.

        ``x`` is the src physical buffer or the :class:`XDMAFuture` of the
        task producing it; ``deps`` adds ordering-only dependency tokens.
        ``link`` pins the task to a named link (round-robin otherwise).
        ``tenant`` names the submitter's ring on that link — per-tenant rings
        are arbitrated round-robin at dispatch, so one tenant flooding its
        ring cannot starve another.  The post consumes a ring credit; see the
        class docstring for the full-ring ``backpressure`` policy.
        """
        tel = _tm._ACTIVE
        if tel is None:
            return self._submit(x, desc, link, deps, nbytes, label, tenant)
        with tel.span("DistributedScheduler.submit", track="scheduler",
                      desc=desc.summary() if isinstance(desc, XDMADescriptor)
                      else repr(desc)):
            return self._submit(x, desc, link, deps, nbytes, label, tenant)

    def _submit(self, x, desc, link, deps, nbytes, label,
                tenant="") -> XDMAFuture:
        if not isinstance(desc, XDMADescriptor):
            raise TypeError(f"submit takes a descriptor, got {type(desc)}")
        if desc.movement == "multicast" and desc.dst.dsts is not None:
            raise ValueError(
                "node-addressed multicast descriptors fork into per-hop tree "
                "tasks: use submit_multicast(x, desc, src=...) instead of "
                "submit()")
        resource = self._route(desc, link)
        desc = self._resolve_auto(desc, x, resource)
        tid = self._next_id
        self._next_id += 1
        task = _Task(id=tid, kind="xdma", resource=resource,
                     deps=self._dep_ids((x,), deps), desc=desc, inputs=(x,),
                     nbytes=nbytes, label=label or desc.summary(),
                     tenant=tenant, csr_writes=1)
        fut = self._enqueue(task)        # validate before the ledger records:
        cap = _api._CAPTURE              # a rejected submit must not leave a
        if cap is not None:              # phantom event (DESIGN.md §9)
            task.event = cap.record_submit(
                x if not isinstance(x, XDMAFuture) else None, desc,
                task.resource, deps=self._dep_events(task.deps),
                label=task.label,
                ring_occupancy=self._rings[task.resource][tenant].occupancy)
            task.trace = cap
        return fut

    def submit_compute(self, fn: Callable, *inputs: Any,
                       resource: str = "compute0", deps: Sequence = (),
                       cost_s: float = 0.0, label: str = "",
                       tenant: str = "") -> XDMAFuture:
        """Enqueue interleaved compute on a named engine (in-order per
        engine).  ``cost_s`` is its duration in the simulated timeline."""
        tel = _tm._ACTIVE
        if tel is None:
            return self._submit_compute(fn, inputs, resource, deps, cost_s,
                                        label, tenant)
        with tel.span("DistributedScheduler.submit_compute",
                      track="scheduler", resource=resource,
                      label=label or getattr(fn, "__name__", "compute")):
            return self._submit_compute(fn, inputs, resource, deps, cost_s,
                                        label, tenant)

    def _submit_compute(self, fn, inputs, resource, deps, cost_s,
                        label, tenant="") -> XDMAFuture:
        if resource in self.topology:
            raise ValueError(f"{resource!r} is a link; compute engines must "
                             "use a non-link resource name")
        tid = self._next_id
        self._next_id += 1
        task = _Task(id=tid, kind="compute", resource=resource,
                     deps=self._dep_ids(inputs, deps), fn=fn, inputs=inputs,
                     cost_s=float(cost_s), tenant=tenant,
                     label=label or getattr(fn, "__name__", "compute"))
        fut = self._enqueue(task)
        cap = _api._CAPTURE
        if cap is not None:
            task.event = cap.record_compute(resource, task.cost_s,
                                            deps=self._dep_events(task.deps),
                                            label=task.label)
            task.trace = cap
        return fut

    # -- multicast (DESIGN.md §14) -------------------------------------------
    def submit_multicast(self, x: Any, desc: XDMADescriptor, *, src: str,
                         deps: Sequence = (), tenant: str = "",
                         label: str = "",
                         policy: str = "tree") -> MulticastFuture:
        """Fork one node-addressed multicast descriptor into per-hop tasks
        over :meth:`Topology.multicast_tree`.

        ``x`` is the payload at ``src`` (or the :class:`XDMAFuture`
        producing it); ``desc.dst`` must be ``Endpoint.multicast(dsts=...)``.
        Every tree hop becomes one ordinary ring post on its own link — one
        doorbell CSR write and one ring credit per hop, exactly the PR-8
        submission machinery — with each non-root hop data-dependent on the
        hop that feeds it, so a shared edge carries the payload once and the
        simulator prices it once.  A destination layout spelled ``"auto"``
        resolves independently against that destination's routed delivery
        link.  Returns a :class:`MulticastFuture` completing when all leaves
        retire."""
        tel = _tm._ACTIVE
        if tel is None:
            return self._submit_multicast(x, desc, src, deps, tenant, label,
                                          policy)
        with tel.span("DistributedScheduler.submit_multicast",
                      track="scheduler", desc=desc.summary()
                      if isinstance(desc, XDMADescriptor) else repr(desc)):
            return self._submit_multicast(x, desc, src, deps, tenant, label,
                                          policy)

    def _submit_multicast(self, x, desc, src, deps, tenant, label,
                          policy) -> MulticastFuture:
        if not isinstance(desc, XDMADescriptor):
            raise TypeError(f"submit_multicast takes a descriptor, "
                            f"got {type(desc)}")
        if desc.movement != "multicast" or desc.dst.dsts is None:
            raise ValueError("submit_multicast needs a node-addressed "
                             "multicast descriptor (Endpoint.multicast)")
        if desc.pre or desc.post:
            raise ValueError("multicast hops are pure relayouts; plugin "
                             "chains are not supported on multicast "
                             "descriptors yet")
        spec_map = dict(desc.dst.dsts)
        tree = self.topology.multicast_tree(
            src, [n for n, _ in desc.dst.dsts], policy=policy)
        transit = (desc.src.layout if not desc.src.layout.is_auto else _L.MN)
        # the payload geometry, when known at submit: lets per-dst "auto"
        # layouts resolve eagerly against their delivery links, so a child
        # hop can chain off its parent's *resolved* physical layout
        logical = dtype = None
        if not isinstance(x, XDMAFuture):
            leaf = getattr(x, "values", x)
            shape = getattr(leaf, "shape", None)
            if shape is not None and getattr(leaf, "dtype", None) is not None:
                shape = tuple(int(s) for s in shape)
                try:
                    logical = (transit.logical_shape(shape)
                               if not desc.src.layout.is_auto else shape)
                except (ValueError, KeyError):
                    logical = shape
                dtype = leaf.dtype
        forwards = {h.src for h in tree.hops}
        gid = self._next_id              # group id: unique, pre-allocation
        futs: List[XDMAFuture] = []
        out_layouts: List[_L.Layout] = []
        hop_events: List[Any] = []
        base = label or "mcast"
        for hop in tree.hops:
            lay = spec_map.get(hop.dst, transit)
            if lay.is_auto:
                if logical is not None:
                    probe = describe(_L.MN, lay, d_buf=desc.d_buf)
                    resolved = _autotune.resolve_descriptor(
                        probe, logical, dtype,
                        link=self.topology.link(hop.link))
                    lay = resolved.dst.layout
                elif hop.dst in forwards:
                    raise ValueError(
                        f"destination {hop.dst!r} forwards to other hops, so "
                        "its 'auto' layout needs a concrete payload at "
                        "submit time (future-fed multicast resolves auto "
                        "only on leaf destinations)")
            in_lay = (transit if hop.parent is None
                      else out_layouts[hop.parent])
            hop_desc = describe(in_lay, lay, d_buf=desc.d_buf)
            fut = self._submit(
                x if hop.parent is None else futs[hop.parent], hop_desc,
                hop.link, tuple(deps) if hop.parent is None else (), None,
                f"{base}/{hop.src}->{hop.dst}", tenant)
            futs.append(fut)
            out_layouts.append(lay)
            task = self._tasks[fut.task_id]
            if task.event is not None:
                ev = task.event
                ev.endpoint = "multicast"
                ev.multicast_group = gid
                ev.multicast_hop = (hop.src, hop.dst)
                ev.multicast_serves = len(hop.serves)
                hop_events.append(ev)
        if hop_events:
            # the anchor: enough to re-synthesize the tree on any fabric
            hop_events[0].multicast_spec = (
                src, tuple((n, l.name) for n, l in desc.dst.dsts), desc.d_buf)
        _MCAST.inc("trees")
        _MCAST.inc("hops", len(tree.hops))
        _MCAST.inc("forks", tree.fork_count)
        _MCAST.inc("shared_hops", tree.shared_hop_count)
        if tree.kind == "chain":
            _MCAST.inc("chain_fallbacks")
        if not isinstance(x, XDMAFuture):
            _MCAST.inc("saved_hop_bytes", tree.bytes_saved(_nbytes(x)))
        delivery = collections.OrderedDict(
            (d, futs[tree.delivery(d)]) for d in tree.dsts)
        return MulticastFuture(self, tree, delivery)

    def _resolve_auto(self, desc: XDMADescriptor, x: Any,
                      resource: str) -> XDMADescriptor:
        """Thread the *routed link* into the layout autotuner: an ``auto``
        endpoint tunes for the fabric the task actually rides (DESIGN.md
        §13), so the same descriptor picks differently on a wide-beat link
        than on a narrow one.  Future inputs defer to dispatch time — their
        shape is unknown until the producer retires."""
        if (desc is None or not desc.has_auto
                or isinstance(x, XDMAFuture)):
            return desc
        leaf = getattr(x, "values", x)          # QTensor/CTensor payloads
        if getattr(leaf, "shape", None) is None \
                or getattr(leaf, "dtype", None) is None:
            return desc
        link = (self.topology.link(resource)
                if resource in self.topology else None)
        try:
            return _api._resolve_auto(desc, x, link)
        except ValueError:
            return desc                          # lowering reports the error

    # -- dispatch ------------------------------------------------------------
    def _resolve(self, obj: Any) -> Any:
        if isinstance(obj, XDMAFuture):
            return self._tasks[obj.task_id].value
        return obj

    def _ready_heads(self) -> List[_Task]:
        """One ready ring head per resource, round-robin over its tenants.

        The rotating cursor is the credit arbitration: each round a resource
        serves the next tenant (in first-post order) whose head is
        dependency-ready, so a tenant flooding its ring gets at most one
        dispatch per round like everyone else.  With a single tenant this is
        exactly the old FIFO-head behavior, including stall accounting."""
        ready = []
        for res, rings in self._rings.items():
            tenants = [tn for tn, r in rings.items() if not r.is_empty]
            if not tenants:
                continue
            cursor = self._rr.get(res, 0)
            picked = None
            for k in range(len(tenants)):
                tn = tenants[(cursor + k) % len(tenants)]
                t = self._tasks[rings[tn].head()]
                if all(self._tasks[d].done for d in t.deps):
                    picked = t
                    self._rr[res] = (cursor + k + 1) % len(tenants)
                    break
            if picked is not None:
                ready.append(picked)
            else:
                # every occupied ring's head blocked on a dependency while
                # the resource idles: one stall round on this resource
                _LINKS.inc(f"stall_rounds:{res}")
        return ready

    def _facts(self, desc: XDMADescriptor) -> Tuple[bool, bool]:
        """``(has_auto, local)`` of a descriptor, worked out once per
        descriptor object (the memo holds the object, so its id stays
        unique while the scheduler lives).  ``local``: the task may run
        inside a round program whatever its lowering — the XLA composition
        and the plugin-compiler's fused Pallas programs (backend
        auto/compiled) both jit into it; only the raw pallas relayout
        backend keeps its own dispatch path."""
        hit = self._desc_facts.get(id(desc))
        if hit is None:
            hit = self._desc_facts[id(desc)] = (
                desc, desc.has_auto,
                desc.movement == "local" and desc.backend != "pallas")
        return hit[1], hit[2]

    def _batchable(self, t: _Task, x: Any) -> bool:
        return (t.kind == "xdma" and t.desc is not None
                and self._facts(t.desc)[1]
                and not isinstance(x, jax.core.Tracer))

    def _input(self, t: _Task) -> Any:
        """The task's resolved input.  An ``auto`` descriptor fed by a future
        resolves here, against the producer's now-known output and the
        task's routed link."""
        x = self._resolve(t.inputs[0]) if t.inputs else None
        if t.kind == "xdma" and t.desc is not None and self._facts(t.desc)[0]:
            t.desc = self._resolve_auto(t.desc, x, t.resource)
        return x

    def _run_single(self, t: _Task, x: Any) -> Any:
        """Dispatch one task on its own: an XDMA task through the cached
        lowering ``xdma.transfer`` uses, a compute task's function."""
        if t.kind == "xdma":
            _SCHED.inc("programs")
            return _api._lowered(t.desc)(x)
        return t.fn(*(self._resolve(a) for a in t.inputs))

    def _plan_round(self, ready: List[_Task],
                    batch: Dict[int, Tuple[_Task, Any]]) -> None:
        """Dispatch one round's tasks in ready order.  Local concrete-array
        tasks retire at once and wait in ``batch`` (task id -> task, input);
        the batch runs first (:meth:`_run_deferred`) whenever a task reads
        one of its values or a task that cannot batch is picked (compute,
        ``pallas`` backend, remote, tracers), so every task is accounted in
        plan order."""
        for t in ready:
            src = t.inputs[0] if t.inputs else None
            if batch and (t.kind != "xdma" or (isinstance(src, XDMAFuture)
                                               and src.task_id in batch)):
                self._run_deferred(batch)
            x = self._input(t)
            if self._batchable(t, x):
                self._retire(t)
                batch[t.id] = (t, x)
                continue
            self._run_deferred(batch)
            value = self._run_single(t, x)
            self._retire(t)
            self._finish(t, x, value)
        self._rounds += 1

    def _run_deferred(self, batch: Dict[int, Tuple[_Task, Any]]) -> None:
        """Execute the deferred tasks (plan order) as grouped programs, then
        empty ``batch``.

        Tasks group by equal (descriptor, input geometry); each group runs as
        :func:`_chunk_widths` chunks, one cached ``jit_sched_round`` program
        each, and its lowering, payload bytes and burst are worked out once.
        The per-task accounting then runs in plan order."""
        if not batch:
            return
        tasks = list(batch.values())
        batch.clear()
        by_desc: Dict[Any, List[int]] = {}
        for i, (t, x) in enumerate(tasks):
            by_desc.setdefault((id(t.desc), _signature(x)), []).append(i)
        groups: Dict[Any, List[int]] = {}
        for (_, sig), idxs in by_desc.items():  # equal descriptor objects merge
            groups.setdefault((tasks[idxs[0]][0].desc.cache_key(), sig),
                              []).extend(idxs)
        values: List[Any] = [None] * len(tasks)
        sizes: List[Any] = [None] * len(tasks)
        programs = 0
        for (key, _), idxs in groups.items():
            desc, x0 = tasks[idxs[0]][0].desc, tasks[idxs[0]][1]
            lo = 0
            for w in _chunk_widths(len(idxs)):
                part = idxs[lo:lo + w]
                lo += w
                ckey = (key, w)
                fused = _ROUND_CACHE.get(ckey)
                if fused is None:
                    fused = _round_program((_api._lowered(desc),) * w)
                    _ROUND_CACHE[ckey] = fused
                    while len(_ROUND_CACHE) > _ROUND_CACHE_CAPACITY:
                        _ROUND_CACHE.popitem(last=False)
                else:
                    _ROUND_CACHE.move_to_end(ckey)
                for i, out in zip(part, fused(tuple(tasks[i][1]
                                                    for i in part))):
                    values[i] = out
                programs += 1
            group_sizes = (_nbytes(x0) + _nbytes(values[idxs[0]]),
                           _burst_bytes(desc, x0))
            for i in idxs:
                sizes[i] = group_sizes
        _SCHED.inc("batched_tasks", len(tasks))
        _SCHED.inc("programs", programs)
        for (t, x), value, size in zip(tasks, values, sizes):
            self._finish(t, x, value, size)

    def _retire(self, t: _Task) -> None:
        """Pop a dispatched task's ring head (returning its credit) and mark
        it done in the current round."""
        popped = self._rings[t.resource][t.tenant].pop()
        assert popped == t.id, (popped, t.id)
        self._dispatched.setdefault(t.resource, []).append(t.id)
        self._pending -= 1
        t.done = True
        t.round = self._rounds

    def _finish(self, t: _Task, x: Any, value: Any,
                sizes: Optional[Tuple[int, Optional[int]]] = None) -> None:
        """Land a retired task's value: payload bytes and burst (``sizes``
        when its group worked them out), the ledger row, the per-link
        counters and the completion."""
        t.value = value
        if t.kind == "xdma":
            if t.nbytes is None or t.burst_bytes is None:
                nbytes, burst = sizes or (_nbytes(x) + _nbytes(value),
                                          _burst_bytes(t.desc, x))
                if t.nbytes is None:
                    t.nbytes = nbytes
                if t.burst_bytes is None:
                    t.burst_bytes = burst
            if t.event is not None:
                # finalize the ledger row with the measured payload, and
                # register this task's output provenance with the trace that
                # OWNS the event (not whatever capture happens to be ambient
                # at flush time — a lazily-drained scheduler must not leak
                # its event ids into an unrelated trace)
                t.trace.finalize(t.event, nbytes=t.nbytes,
                                 burst_bytes=t.burst_bytes, value=x)
                t.trace.register_value(t.event, value)
            self._count_dispatch(t)
        elif t.nbytes is None:
            t.nbytes = 0
        self._complete(t)

    def _complete(self, t: _Task) -> None:
        """Push a finished task's completion-queue entry and advance the
        incremental makespan.

        The span arithmetic mirrors ``simulator.simulate`` operation for
        operation (same dep-max, same ``transfer_time`` call, same doorbell
        add), and per-resource completion order IS the replay's queue order,
        so ``_makespan_inc`` is bit-equal to ``report().makespan`` whenever
        the rings are drained."""
        ready = max((self._sim_end[d] for d in t.deps), default=0.0)
        start = max(ready, self._sim_free.get(t.resource, 0.0))
        if t.resource in self.topology:
            key = (t.resource, int(t.nbytes or 0), t.burst_bytes,
                   t.desc.d_buf if t.desc is not None else 1, t.csr_writes)
            dur = self._link_time.get(key)
            if dur is None:              # equal page ops price alike
                link = self.topology.link(t.resource)
                dur = link.transfer_time(key[1], key[2], issue_overhead=None,
                                         pipeline_depth=key[3])
                if t.csr_writes:
                    dur += t.csr_writes * link.csr_write_cost
                self._link_time[key] = dur
        else:
            dur = max(0.0, float(t.cost_s))
        stop = start + dur
        self._sim_end[t.id] = stop
        self._sim_free[t.resource] = stop
        if stop > self._makespan_inc:
            self._makespan_inc = stop
        self.completions.append(Completion(t.id, t.resource, t.tenant,
                                           t.round, start, stop))
        _RINGS.inc(f"tenant_dispatch:{t.tenant or 'default'}")

    def _count_dispatch(self, t: _Task) -> None:
        """Per-link CSR counters for one finalized dispatch: payload bytes
        (exactly the ledger's ``per_link_bytes`` contribution), wire bytes,
        generated bursts, and the amortized address-issue overhead the cost
        model charges (``bursts * burst_overhead / d_buf``)."""
        nbytes = int(t.nbytes or 0)
        wire = (int(t.event.wire_nbytes)
                if t.event is not None and t.event.wire_nbytes is not None
                else nbytes)
        key = (t.resource, nbytes, wire, t.burst_bytes,
               t.desc.d_buf if t.desc is not None else 1)
        incs = self._link_incs.get(key)
        if incs is None:                 # equal page ops count alike
            incs = self._link_incs[key] = self._dispatch_incs(*key)
        for name, n in incs:
            _LINKS.inc(name, n)

    def _dispatch_incs(self, res: str, nbytes: int, wire: int,
                       burst: Optional[int], depth: int):
        """The ``links`` increments of one dispatch of this geometry."""
        if burst and nbytes > 0:
            n_bursts = -(-nbytes // int(burst))
        else:
            n_bursts = 1 if nbytes > 0 else 0
        incs = [(f"tasks:{res}", 1), (f"bytes:{res}", nbytes),
                (f"wire_bytes:{res}", wire), (f"bursts:{res}", n_bursts)]
        if res in self.topology and n_bursts and burst:
            link = self.topology.link(res)
            incs.append((f"issue_ns:{res}",
                         int(round(n_bursts * link.burst_overhead * 1e9
                                   / max(1, int(depth))))))
        return tuple(incs)

    def step(self) -> bool:
        """Run one scheduling round, executed at once; returns False when
        nothing is pending."""
        ready = self._ready_heads()
        if not ready:
            self._check_drained()
            return False
        batch: Dict[int, Tuple[_Task, Any]] = {}
        self._plan_round(ready, batch)
        self._run_deferred(batch)
        return True

    def flush(self) -> None:
        """Drain every ring, inside a ``sched.flush`` span.

        The rounds are logical: planning runs them exactly as repeated
        :meth:`step` would (tenant arbitration, stall counts, ring pops,
        ``t.round``), but the deferred batch carries across rounds, so the
        local concrete-array tasks of many rounds run as a few programs
        grouped by descriptor (:meth:`_plan_round`).  Values, completions,
        makespan and every counter but the ``sched`` bank's equal a drain by
        ``step()``."""
        with _tm.span("sched.flush", "scheduler", tasks=self._pending):
            batch: Dict[int, Tuple[_Task, Any]] = {}
            while True:
                ready = self._ready_heads()
                if not ready:
                    break
                self._plan_round(ready, batch)
            self._run_deferred(batch)
            self._check_drained()

    def _check_drained(self) -> None:
        """With no ready ring head, every task must have dispatched."""
        if self.pending:
            raise ValueError(
                f"scheduler deadlocked with {self.pending} pending tasks "
                "(dependency cycle across rings?)")

    @property
    def pending(self) -> int:
        return self._pending

    # -- replay --------------------------------------------------------------
    def _sim_order(self) -> List[int]:
        """Task ids in global submission-order slots, each resource's slots
        re-filled in its actual dispatch order (pending tasks keep submission
        order after the dispatched prefix).  With a single tenant per
        resource, dispatch order IS submission order, so this is the
        identity — the replay contract existing call sites pin."""
        ids = sorted(self._tasks)
        per_res: Dict[str, List[int]] = {}
        for tid in ids:
            per_res.setdefault(self._tasks[tid].resource, []).append(tid)
        fill: Dict[str, collections.deque] = {}
        for res, tids in per_res.items():
            done = list(self._dispatched.get(res, ()))
            pend = [i for i in tids if not self._tasks[i].done]
            fill[res] = collections.deque(done + pend)
        return [fill[self._tasks[tid].resource].popleft() for tid in ids]

    def sim_tasks(self) -> List[SimTask]:
        """The recorded schedule as simulator tasks (dispatch order per
        resource — see :meth:`_sim_order`)."""
        out = []
        for tid in self._sim_order():
            t = self._tasks[tid]
            out.append(SimTask(id=t.id, resource=t.resource,
                               nbytes=int(t.nbytes or 0), deps=t.deps,
                               cost_s=t.cost_s, label=t.label,
                               burst_bytes=t.burst_bytes,
                               pipeline_depth=(t.desc.d_buf if t.desc is not None
                                               else 1),
                               csr_writes=t.csr_writes))
        return out

    def report(self) -> SimReport:
        """Deterministic replay of everything dispatched so far.

        .. deprecated:: PR 7
            The per-link byte/burst/stall totals this replay derives are
            mirrored live in ``telemetry.bank("links")`` and surface as
            ``snapshot()["surfaces"]["scheduler_links"]``; keep ``report()``
            for the full timeline (spans, utilization, makespan).
        """
        return simulate(self.sim_tasks(), self.topology)

    def makespan(self) -> float:
        """Simulated seconds to drain everything dispatched so far — the
        serving engines' per-step clock advance.

        O(1) when the rings are drained: the completion queue maintains the
        makespan incrementally with the replay's exact arithmetic.  With
        tasks still pending it falls back to the full replay (which prices
        the undispatched tail too)."""
        if self._pending:
            return self.report().makespan
        return self._makespan_inc

    def release(self) -> None:
        """Drop the input and output buffers of every dispatched task.

        A task's inputs hold the futures of its dependencies and a future
        holds its scheduler, so a scheduler and its tasks sit in reference
        cycles until a full collection: a caller that has taken every result
        it needs frees the device buffers now.  The timeline (``report``,
        ``makespan``) stays."""
        for t in self._tasks.values():
            if t.done:
                t.inputs, t.value = (), None

    def summary(self) -> str:
        lines = [f"DistributedScheduler({self.name!r}, "
                 f"{len(self._tasks)} tasks, {self._rounds} rounds, "
                 f"{len(self.completions)} completions)"]
        for res, rings in self._rings.items():
            for tn, ring in rings.items():
                total = ring.occupancy + sum(
                    1 for tid in self._dispatched.get(res, ())
                    if self._tasks[tid].tenant == tn)
                if total:
                    lines.append(f"  {ring.name}: {total} tasks "
                                 f"({total - ring.occupancy} dispatched, "
                                 f"{ring.credits}/{ring.depth} credits)")
        return "\n".join(lines)
