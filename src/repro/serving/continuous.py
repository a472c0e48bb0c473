"""Continuous batching over the paged-KV pool: admission, composition,
preemption — every KV byte moving as a page descriptor.

The engine holds no per-request cache tensors.  A request's KV state lives
in :class:`~repro.serving.paged.PagedKVPool` pages — the *valid prefix* of
each sequence-indexed cache leaf, paged as fixed-row tiles — plus an integer
position.  Each serving step:

1. **re-admission** — preempted requests restore their pages (oldest first)
   when slots free up;
2. **admission** — arrived requests join while the batch has room and the
   pool can hold their prompt pages;
3. **prefill** — admitted prompts run the existing jitted ``lm.prefill``
   (grouped by prompt length), and the valid prefix of every cache leaf
   scatters into fresh pages (one ``engine_scatter`` program per group);
4. **preemption** — if the next decode's page growth exceeds the free pool,
   the youngest requests evict wholesale to host (Compress wire codec)
   until the rest fit;
5. **decode** — active pages gather and one ``engine_compose`` program
   lays them out as a batch cache (page-table indirection in reverse), one
   jitted ``lm.decode_step`` advances every active request — a scalar
   position when the batch is aligned (the exact compiled program
   ``ServingEngine`` runs, which is what makes the parity tests bit-exact)
   or a per-request position vector when ragged — and one
   ``engine_scatter`` program cuts the dirty pages from the batched cache
   for the pool to store;
6. the simulated clock advances by the step's scheduler makespan.

Each step is one ``engine.step`` span on the host clock, with its phases
(``engine.admit``, ``prefill``, ``preempt``, ``gather``, ``compose``,
``decode``, ``scatter``, ``defrag``) nested inside it: recorded by an open
telemetry session and, while ``jax.profiler`` traces, annotated on the
profile beside the device ops (:func:`repro.runtime.telemetry.span`).
``ServeReport``'s latency fields read the simulated clock's token stamps;
with a session open, its ``ttft_s``/``tbt_s`` histograms take host-clock
stamps.

A MoE config's programs on the local (no mesh) path also return their
routing counts; the engine adds them into one device total per ``serve()``
and reads it once at the end, inside an ``engine.moe_counts`` span, into
the ``moe`` counter bank (``MOE_COUNTERS``).

``StaticBatchEngine`` is the baseline: same pool, same kernels, but gang
admission only (a new batch forms only when the previous one fully drains,
and finished members keep occupying batch rows and page traffic until the
gang completes).  ``tests/test_paged_serving.py`` checks that continuous
batching outpaces it on the simulated clock under load.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.runtime import DistributedScheduler, telemetry as _tm
from repro.runtime.topology import HW_FLOPS
from repro.serving.paged import (PagedKVPool, default_serving_topology,
                                 pages_for_rows, DEFAULT_PAGE_ROWS)
from repro.serving.requests import Request

__all__ = ["ContinuousBatchingEngine", "StaticBatchEngine", "ServeReport"]

# Serving SLO counters (DESIGN.md §11): queue-depth high-water, preemption
# and step tallies — always counting, like every CSR bank.
_SERVING = _tm.bank("serving")

# The ``moe`` bank's counters, in the order of the programs' routing counts
# (``repro.layers.moe.routing_counts``): assignments (tokens x top_k) and
# those to held experts, over prefill and decode; held experts reached, over
# decode steps and layers.
MOE_COUNTERS = ("assignments", "assignments_held", "decode_experts_touched")


# ---------------------------------------------------------------------------
# cache-leaf geometry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _LeafMeta:
    """How one cache leaf pages: where its batch/sequence axes are and the
    canonical (rows, cols) matrix view the pool stores.

    kind: 'pos' (the shared position counter), 'const' (no batch axis —
    broadcast from the template), 'seq' (sequence-indexed: only the valid
    prefix pages, so memory grows with decoded tokens), 'state' (per-request
    but not sequence-indexed — SSM states, rolling-window caches — paged
    whole every step)."""

    index: int
    kind: str
    batch_axis: int = -1
    seq_axis: int = -1              # in the full (batched) leaf
    rpt: int = 1                    # canonical rows per token (seq leaves)
    rows: int = 0                   # total canonical rows (B=1 leaf)
    cols: int = 1

    def seq_axis_nb(self) -> int:
        """Sequence axis after the batch axis is removed."""
        return self.seq_axis - (1 if self.batch_axis < self.seq_axis else 0)


def _leaf_metas(cfg, max_len: int, cache_dtype) -> Tuple[List[_LeafMeta], Any]:
    """Classify every cache leaf by probing ``init_cache`` shapes at
    (B=1, L), (B=2, L) and (B=1, 2L) — the axis that moves with B is the
    batch axis, the one that moves with L is the sequence axis.  Leaves
    invariant to L (rolling windows shorter than max_len, SSM states) page
    whole.  Returns (metas, B=1 shape template)."""
    probe = lambda b, l: jax.eval_shape(
        functools.partial(lm.init_cache, cfg, b, l, cache_dtype))
    t1, t2, tl = probe(1, max_len), probe(2, max_len), probe(1, 2 * max_len)
    p1, tree = jax.tree_util.tree_flatten_with_path(t1)
    l2 = jax.tree_util.tree_leaves(t2)
    ll = jax.tree_util.tree_leaves(tl)
    metas: List[_LeafMeta] = []
    for i, ((path, a), b, c) in enumerate(zip(p1, l2, ll)):
        keys = jax.tree_util.keystr(path)
        if "pos" in keys and a.ndim == 0:
            metas.append(_LeafMeta(i, "pos"))
            continue
        batch_ax = next((j for j in range(a.ndim)
                         if a.shape[j] != b.shape[j]), -1)
        if batch_ax < 0:
            metas.append(_LeafMeta(i, "const"))
            continue
        nb = a.shape[:batch_ax] + a.shape[batch_ax + 1:]
        if len(nb) < 1:
            raise NotImplementedError(f"cache leaf {keys} has no state "
                                      "beyond the batch axis")
        cols = int(nb[-1])
        seq_ax = next((j for j in range(a.ndim)
                       if a.shape[j] != c.shape[j]), -1)
        if seq_ax < 0:
            rows = int(np.prod(nb[:-1], dtype=np.int64)) if len(nb) > 1 else 1
            metas.append(_LeafMeta(i, "state", batch_axis=batch_ax,
                                   rows=rows, cols=cols))
            continue
        seq_nb = seq_ax - (1 if batch_ax < seq_ax else 0)
        S = int(a.shape[seq_ax])
        rest = tuple(d for j, d in enumerate(nb) if j != seq_nb)
        if not rest:
            raise NotImplementedError(f"cache leaf {keys}: sequence axis is "
                                      "the only non-batch axis")
        cols = int(rest[-1])
        rpt = int(np.prod(rest[:-1], dtype=np.int64)) if len(rest) > 1 else 1
        metas.append(_LeafMeta(i, "seq", batch_axis=batch_ax, seq_axis=seq_ax,
                               rpt=rpt, rows=S * rpt, cols=cols))
    return metas, t1


def _bucket(n: int, bits: int = 1) -> int:
    """Pages a scatter or compose program takes for one leaf: ``n`` rounded
    up to a number whose binary digits past the first ``bits`` are zero (a
    power of two by default; 0 stays 0), so a serve compiles a handful of
    programs."""
    step = 1 << max(0, n.bit_length() - bits)
    return -(-n // step) * step


def _page_of(meta: _LeafMeta, R: int, leaf: jnp.ndarray, b, j) -> jnp.ndarray:
    """Page ``j`` of batch row ``b`` of one batched cache leaf: rows
    ``[j*R, (j+1)*R)`` of the row's canonical (rows, cols) matrix, the rows
    past its end zero-filled.  A sequence leaf reads only the tokens that
    cover the page (a window of at most ``ceil(R / rpt) + 1``, moved back
    where it would run past the sequence's end) and reorders just those into
    canonical rows.  ``b`` and ``j`` may be traced."""
    if meta.kind == "seq":
        rpt, S = meta.rpt, leaf.shape[meta.seq_axis]
        # the page's first row lies at most rpt - gcd(R, rpt) rows into its
        # first token, so this many tokens always cover it
        T = min(S, -(-(R + rpt - math.gcd(R, rpt)) // rpt))
        start = jnp.minimum(j * R // rpt, S - T)
        starts = [0] * leaf.ndim
        starts[meta.batch_axis], starts[meta.seq_axis] = b, start
        sizes = list(leaf.shape)
        sizes[meta.batch_axis], sizes[meta.seq_axis] = 1, T
        win = jnp.squeeze(jax.lax.dynamic_slice(leaf, starts, sizes),
                          meta.batch_axis)
        mat = jnp.moveaxis(win, meta.seq_axis_nb(), 0).reshape(
            T * rpt, meta.cols)
        off = j * R - start * rpt
    else:
        row = jnp.squeeze(jax.lax.dynamic_slice_in_dim(
            leaf, b, 1, axis=meta.batch_axis), meta.batch_axis)
        mat = row.reshape(meta.rows, meta.cols)
        off = j * R
    mat = jnp.pad(mat, ((0, R), (0, 0)))
    return jax.lax.dynamic_slice_in_dim(mat, off, R)


def _scatter_program(metas: Sequence[_LeafMeta], R: int):
    """The engine's page scatter as one jitted program, named so that a
    profile calls it ``jit_engine_scatter``.  It takes the paged leaves of a
    batched cache (``metas`` order), an int32 (n, 2) array of (batch row,
    page index) entries and the static number of entries of each leaf, and
    returns each leaf's pages as separate (R, cols) outputs.  A loop cuts
    one page an iteration, so compiling takes about as long for 512 pages
    as for one."""

    def engine_scatter(leaves, entries, counts):
        out, k = [], 0
        for m, leaf, n in zip(metas, leaves, counts):
            mine = entries[k:k + n]
            k += n

            def cut(i, pages, m=m, leaf=leaf, mine=mine):
                return pages.at[i].set(
                    _page_of(m, R, leaf, mine[i, 0], mine[i, 1]))
            pages = jax.lax.fori_loop(
                0, n, cut, jnp.zeros((n, R, m.cols), leaf.dtype))
            out.append(tuple(pages[i] for i in range(n)))
        return tuple(out)

    return jax.jit(engine_scatter, static_argnums=(2,))


def _from_canonical(meta: _LeafMeta, mat: jnp.ndarray,
                    nb_shape: Tuple[int, ...]) -> jnp.ndarray:
    """A request's canonical (rows, cols) matrix -> its leaf (batch axis
    removed).  Sequence leaves hold the token axis outermost in the matrix,
    so the valid prefix is a row prefix."""
    if meta.kind == "seq":
        seq_nb = meta.seq_axis_nb()
        S = nb_shape[seq_nb]
        rest = tuple(d for j, d in enumerate(nb_shape) if j != seq_nb)
        return jnp.moveaxis(mat.reshape((S,) + rest), 0, seq_nb)
    return mat.reshape(nb_shape)


def _compose_program(metas: Sequence[_LeafMeta], template, R: int):
    """The engine's batch composition as one jitted program, named so that a
    profile calls it ``jit_engine_compose``.  For each paged leaf (``kind``
    'seq' or 'state', ``metas`` order) it takes the step's loaded pages as
    one flat tuple in (request, page) order, padded to a bucketed count, and
    an int32 (B, 2) array of each batch row's first page in that tuple and
    its number of pages.  Stacked end to end, the pages hold each request's
    canonical matrix as one run of rows; a loop writes one batch row an
    iteration: the run's first P*R rows (P the pages of a full leaf), those
    past the row's pages zeroed, cut to the leaf's rows and laid out as the
    row's leaf.  Returns the batched paged leaves and the zero-filled
    ``const`` leaves."""
    t_leaves = jax.tree_util.tree_leaves(template)
    paged = [m for m in metas if m.kind in ("seq", "state")]
    consts = [t_leaves[m.index] for m in metas if m.kind == "const"]

    def engine_compose(pages, rows):
        out = []
        for m, pg, row in zip(paged, pages, rows):
            t = t_leaves[m.index]
            nb_shape = t.shape[:m.batch_axis] + t.shape[m.batch_axis + 1:]
            P = pages_for_rows(m.rows, R)
            # P zero pages at the end: no row's run reads past the stack
            flat = jnp.concatenate(pg + (jnp.zeros((P * R, m.cols), t.dtype),))
            shape = list(t.shape)
            shape[m.batch_axis] = row.shape[0]

            def put(i, leaf, m=m, nb_shape=nb_shape, P=P, flat=flat, row=row):
                mat = jax.lax.dynamic_slice_in_dim(flat, row[i, 0] * R, P * R)
                held = jnp.arange(P * R)[:, None] < row[i, 1] * R
                mat = jnp.where(held, mat, jnp.zeros_like(mat))[:m.rows]
                one = jnp.expand_dims(_from_canonical(m, mat, nb_shape),
                                      m.batch_axis)
                return jax.lax.dynamic_update_slice_in_dim(
                    leaf, one, i, m.batch_axis)
            out.append(jax.lax.fori_loop(0, row.shape[0], put,
                                         jnp.zeros(shape, t.dtype)))
        return tuple(out), tuple(jnp.zeros(t.shape, t.dtype) for t in consts)

    return jax.jit(engine_compose)


def _counts_moe(cfg, mesh) -> bool:
    """Whether the programs return routing counts: a MoE config on the
    local (no mesh) path."""
    return mesh is None and any(spec.moe for spec in cfg.layer_specs)


def _engine_programs(cfg, mesh, moe: bool):
    """The engine's jitted prefill and decode, built from named functions so
    that a profile names their programs ``jit_engine_prefill`` and
    ``jit_engine_decode``.  With ``moe`` they also return their routing
    counts (``lm.prefill``'s and ``lm.decode_step``'s ``moe_counts``)."""

    def engine_prefill(params, batch, cache):
        return lm.prefill(cfg, params, batch, cache, mesh=mesh, moe_counts=moe)

    def engine_decode(params, tokens, cache):
        return lm.decode_step(cfg, params, tokens, cache, mesh=mesh,
                              moe_counts=moe)

    return (jax.jit(engine_prefill),
            jax.jit(engine_decode, donate_argnums=(2,)))


# ---------------------------------------------------------------------------
# request state + report
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _ReqState:
    req: Request
    status: str = "queued"          # queued | active | preempted | done
    pos: int = 0                    # tokens resident in the (logical) cache
    generated: List[int] = dataclasses.field(default_factory=list)
    pages: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    finish_s: float = -1.0
    # simulated-clock stamp of every generated token (ServeReport's SLO
    # fields: TTFT is token_times[0] - arrival, TBT the successive gaps)
    token_times: List[float] = dataclasses.field(default_factory=list)
    # with a telemetry session open, the same on the session's host clock:
    # when the engine first saw the request arrived, and when its latest
    # token reached the host (the session's ttft_s/tbt_s histograms)
    host_arrival_s: float = -1.0
    host_last_s: float = -1.0

    @property
    def done_tokens(self) -> bool:
        return len(self.generated) >= self.req.max_new


@dataclasses.dataclass
class ServeReport:
    """What a serve() run produced: per-request tokens plus the load-side
    aggregates (simulated time base — the scheduler's costed timeline)."""

    engine: str
    n_requests: int
    total_tokens: int
    elapsed_s: float
    tokens_per_s: float
    p50_s: float
    p99_s: float
    steps: int
    preemptions: int
    pool_stats: Dict[str, int]
    tokens: Dict[int, np.ndarray]
    # SLO latency aggregates on the simulated clock: time-to-first-token and
    # time-between-tokens percentiles over completed requests
    ttft_p50_s: float = 0.0
    ttft_p99_s: float = 0.0
    tbt_p50_s: float = 0.0
    tbt_p99_s: float = 0.0

    def summary(self) -> str:
        return (f"{self.engine}: {self.n_requests} reqs, "
                f"{self.total_tokens} toks in {self.elapsed_s * 1e6:.1f}us "
                f"-> {self.tokens_per_s:,.0f} tok/s, "
                f"p50 {self.p50_s * 1e6:.1f}us p99 {self.p99_s * 1e6:.1f}us, "
                f"ttft p99 {self.ttft_p99_s * 1e6:.1f}us, "
                f"{self.preemptions} preemptions")


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
class ContinuousBatchingEngine:
    """Serve a request stream with per-step admission over a paged-KV pool.

    The decode program is the same jitted ``lm.decode_step`` the fixed-batch
    :class:`~repro.serving.engine.ServingEngine` runs — when every active
    request sits at the same position the composed cache uses a scalar
    ``pos`` and the compiled program (and thus every generated token) is
    bit-identical to the fixed-batch engine's.
    """

    name = "continuous"

    def __init__(self, cfg, params, max_len: int, *, max_batch: int = 4,
                 cache_dtype=jnp.float32, topology=None,
                 pool: Optional[PagedKVPool] = None,
                 page_rows: int = DEFAULT_PAGE_ROWS,
                 capacity_pages: Optional[int] = None,
                 defrag: bool = True, mesh=None,
                 ring_depth: Optional[int] = None,
                 backpressure: str = "block"):
        if cfg.encoder_layers:
            raise NotImplementedError("continuous batching serves decoder "
                                      "LMs; encoder-decoder configs use "
                                      "ServingEngine")
        self.cfg = cfg
        self.params = params
        self.max_len = int(max_len)
        self.max_batch = int(max_batch)
        self.cache_dtype = cache_dtype
        self.topology = topology if topology is not None \
            else default_serving_topology()
        self.auto_defrag = defrag
        self.pool = pool if pool is not None else PagedKVPool(
            capacity_pages if capacity_pages is not None else 64, page_rows)
        self.metas, self._template = _leaf_metas(cfg, max_len, cache_dtype)
        self._paged = [m for m in self.metas if m.kind in ("seq", "state")]
        self._scatter_program = _scatter_program(self._paged,
                                                 self.pool.page_rows)
        self._compose_program = _compose_program(self.metas, self._template,
                                                 self.pool.page_rows)
        self._moe = _counts_moe(cfg, mesh)
        self._prefill, self._decode = _engine_programs(cfg, mesh, self._moe)
        self._moe_total = None          # device routing counts of a serve()
        self._n_params = sum(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params)
            if getattr(l, "ndim", 0) >= 1)
        self.ring_depth = ring_depth
        self.backpressure = backpressure
        self.last_scheduler = None
        self.steps = 0
        self.preemptions = 0

    def _new_scheduler(self) -> DistributedScheduler:
        """One fresh per-step scheduler, carrying the engine's ring knobs
        (``ring_depth=None`` keeps the scheduler default — deep enough that
        a serving step never backpressures between flushes; shallow rings
        exercise page movement under credit pressure)."""
        kw = {} if self.ring_depth is None else {"ring_depth": self.ring_depth}
        return DistributedScheduler(self.topology, name="serving-cb",
                                    backpressure=self.backpressure, **kw)

    # -- page accounting -----------------------------------------------------
    def _pages_at(self, meta: _LeafMeta, pos: int) -> int:
        """Pool pages leaf ``meta`` occupies when ``pos`` tokens are valid."""
        if meta.kind == "seq":
            rows = min(pos, self.max_len) * meta.rpt
        elif meta.kind == "state":
            rows = meta.rows
        else:
            return 0
        return pages_for_rows(rows, self.pool.page_rows)

    def _footprint(self, pos: int) -> int:
        return sum(self._pages_at(m, pos) for m in self.metas)

    def _growth(self, pos: int) -> int:
        return self._footprint(pos + 1) - self._footprint(pos)

    def _run(self, program, *args):
        """Call the prefill or decode program; a MoE config's routing counts
        add into the serve's device total, with no host sync."""
        if not self._moe:
            return program(*args)
        logits, cache, counts = program(*args)
        self._moe_total = self._moe_total + counts
        return logits, cache

    def _read_moe_counts(self) -> None:
        """The serve's routing counts, read once into the ``moe`` bank."""
        with _tm.span("engine.moe_counts", "engine"):
            n = np.asarray(self._moe_total)
            bank = _tm.bank("moe")
            for name, v in zip(MOE_COUNTERS, n):
                bank.inc(name, int(v))

    # -- page scatter/gather -------------------------------------------------
    def _plan_scatter(self, group: List[_ReqState],
                      written: Optional[List[int]] = None):
        """The pages to store for ``group`` (row i of the batched cache is
        ``group[i]``), allocating new ones: (leaf meta, row, page index,
        page id) in store order — request, then leaf, then page.  With
        ``written`` (each row's decoded position), sequence leaves only
        store the pages overlapping rows written at/after it — one decode
        step dirties a single page per leaf in the common case; state leaves
        store whole."""
        R = self.pool.page_rows
        dtype_name = str(jnp.dtype(self.cache_dtype))
        plan = []
        for i, st in enumerate(group):
            for m in self._paged:
                plist = st.pages.setdefault(m.index, [])
                want = self._pages_at(m, st.pos)
                first = 0
                if m.kind == "seq" and written is not None:
                    first = (min(written[i], self.max_len - 1) * m.rpt) // R
                for j in range(first, want):
                    if j >= len(plist):
                        plist.append(self.pool.alloc(m.cols, dtype_name))
                    plan.append((m, i, j, plist[j]))
        return plan

    def _scatter_pages(self, cache, plan) -> List[jnp.ndarray]:
        """Cut the planned pages from the batched ``cache`` in one
        ``engine_scatter`` program; each leaf's page count is bucketed to a
        power of two and the padded outputs are dropped.  Returns the pages
        in plan order."""
        per_leaf = {m.index: [] for m in self._paged}   # (row, page)
        slot = []                       # (leaf, position in its outputs)
        for m, i, j, _ in plan:
            slot.append((m.index, len(per_leaf[m.index])))
            per_leaf[m.index].append((i, j))
        counts = tuple(_bucket(len(per_leaf[m.index])) for m in self._paged)
        entries = np.zeros((sum(counts), 2), np.int32)
        k = 0
        for m, n in zip(self._paged, counts):
            got = np.asarray(per_leaf[m.index], np.int32).reshape(-1, 2)
            entries[k:k + len(got)] = got
            k += n
        leaves = jax.tree_util.tree_leaves(cache)
        outs = self._scatter_program(
            tuple(leaves[m.index] for m in self._paged), entries, counts)
        _SERVING.inc("scatter_programs")
        by_leaf = {m.index: o for m, o in zip(self._paged, outs)}
        return [by_leaf[li][k] for li, k in slot]

    def _scatter(self, group: List[_ReqState], cache, *, deps=(),
                 written: Optional[List[int]] = None,
                 label: str = "store") -> None:
        """Store ``group``'s pages from the batched ``cache`` that prefill or
        decode returned: plan them (:meth:`_plan_scatter`), cut them in one
        program, then one ``pool.store`` each."""
        plan = self._plan_scatter(group, written)
        if not plan:
            return
        for (_, _, _, pid), page in zip(plan, self._scatter_pages(cache, plan)):
            self.pool.store(pid, page, deps=deps, label=label)

    def _gather(self, st: _ReqState):
        """Reassemble one request's cache leaves from its pages.  Returns
        (futures keyed by leaf index, each a list of page futures)."""
        futs: Dict[int, List[Any]] = {}
        for m in self.metas:
            if m.kind in ("pos", "const"):
                continue
            futs[m.index] = [self.pool.load(pid)
                             for pid in st.pages.get(m.index, [])]
        return futs

    # -- batch composition ---------------------------------------------------
    def _compose_args(self, gathered: List[Dict[int, List[Any]]]):
        """The compose program's arguments: per paged leaf, the loaded pages
        in (request, page) order, padded to a count with three leading
        binary digits (at most a quarter padding), and each batch row's
        (first page, number of pages) in them."""
        pages, rows = [], []
        for m in self._paged:
            flat = [f.result() for g in gathered for f in g[m.index]]
            counts = [len(g[m.index]) for g in gathered]
            rows.append(np.stack([np.cumsum([0] + counts[:-1]), counts],
                                 axis=1).astype(np.int32))
            # any page pads: the program zeroes rows past each row's pages
            pad = _bucket(len(flat), bits=3) - len(flat)
            pages.append(tuple(flat + flat[:1] * pad))
        return tuple(pages), tuple(rows)

    def _compose_cache(self, active: List[_ReqState],
                       gathered: List[Dict[int, List[Any]]]):
        """Per-request pages -> one batched decode cache, composed in one
        ``engine_compose`` program.  Scalar ``pos`` when the batch is
        position-aligned (identical compiled program to the fixed-batch
        engine), per-request vector otherwise."""
        paged, consts = self._compose_program(*self._compose_args(gathered))
        _SERVING.inc("compose_programs")
        t_leaves, treedef = jax.tree_util.tree_flatten(self._template)
        out = list(t_leaves)
        const_metas = [m for m in self.metas if m.kind == "const"]
        for m, leaf in zip(self._paged + const_metas, paged + consts):
            out[m.index] = leaf
        for m in self.metas:
            if m.kind == "pos":
                poss = [min(st.pos, self.max_len) for st in active]
                out[m.index] = (jnp.asarray(poss[0], jnp.int32)
                                if len(set(poss)) == 1
                                else jnp.asarray(poss, jnp.int32))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- admission policy ----------------------------------------------------
    def _admit(self, active, preempted, queue, clock):
        """Default (continuous) policy: restore preempted oldest-first, then
        admit arrivals while the batch and the pool have room."""
        restored = []
        while preempted and len(active) < self.max_batch:
            st = preempted[0]
            need = sum(len(v) for v in st.pages.values())
            if need > self.pool.free_pages:
                break
            preempted.pop(0)
            for plist in st.pages.values():
                for pid in plist:
                    self.pool.restore(pid)
            st.status = "active"
            active.append(st)
            restored.append(st)
        admitted = []
        # prompt pages are allocated after prefill, so this step's earlier
        # admissions still count against the free pool
        budget = self.pool.free_pages
        while queue and len(active) < self.max_batch:
            st = queue[0]
            if st.req.arrival_s > clock:
                break
            need = self._footprint(st.req.prompt_len)
            if need > budget:
                break
            budget -= need
            queue.pop(0)
            st.status = "active"
            active.append(st)
            admitted.append(st)
        return restored, admitted

    def _gang_done(self, active) -> bool:     # continuous: free immediately
        return False

    @staticmethod
    def _stamp(st: _ReqState, now: float, tel) -> None:
        """Host-clock stamp of a token that has just reached the host, into
        the open session's ``ttft_s``/``tbt_s`` histograms."""
        if st.host_last_s < 0:
            tel.record_value("ttft_s", now - st.host_arrival_s)
        else:
            tel.record_value("tbt_s", now - st.host_last_s)
        st.host_last_s = now

    # -- the serving loop ----------------------------------------------------
    def serve(self, requests: Sequence[Request], *,
              max_steps: int = 10_000) -> ServeReport:
        for r in requests:
            if r.total_len > self.max_len:
                raise ValueError(f"request {r.rid}: prompt {r.prompt_len} + "
                                 f"max_new {r.max_new} exceeds max_len "
                                 f"{self.max_len}")
        queue = [_ReqState(r) for r in
                 sorted(requests, key=lambda r: (r.arrival_s, r.rid))]
        states = {st.req.rid: st for st in queue}
        arrivals = list(queue)                     # arrival order
        n_arrived = 0
        active: List[_ReqState] = []
        preempted: List[_ReqState] = []
        clock = 0.0
        self.steps = 0
        self.preemptions = 0
        tel = _tm.active()
        if self._moe:
            self._moe_total = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)

        while (queue or active or preempted) and self.steps < max_steps:
            if not active and not preempted and queue \
                    and queue[0].req.arrival_s > clock:
                clock = queue[0].req.arrival_s     # idle: jump to next arrival
            if tel is not None:
                # a request's host-clock arrival: the first step that sees it
                now = tel.clock()
                while n_arrived < len(arrivals) \
                        and arrivals[n_arrived].req.arrival_s <= clock:
                    arrivals[n_arrived].host_arrival_s = now
                    n_arrived += 1
            with _tm.span("engine.step", "engine", step=self.steps,
                          engine=self.name):
                clock += self._step(clock, queue, active, preempted, tel)
                self.last_scheduler.release()     # the step's page buffers
                self.steps += 1

                # stamp every token generated this step at the post-step
                # clock (prefill's first token and decode's next token both
                # land when the step's movement drains — the simulated-clock
                # base of ServeReport's SLO fields)
                for st in states.values():
                    while len(st.token_times) < len(st.generated):
                        st.token_times.append(clock)

                # completions: continuous frees a request the step it drains;
                # a static gang keeps its finished rows resident (finish time
                # still stamped at their own last token) until everyone drains
                holds = self._gang_holds(active)
                for st in [s for s in active if s.done_tokens]:
                    if holds:
                        if st.finish_s < 0:
                            st.finish_s = clock
                    else:
                        self._finish(st, active, clock)

        if self._moe:
            self._read_moe_counts()
        return self._report(states, clock)

    def _step(self, clock: float, queue: List[_ReqState],
              active: List[_ReqState], preempted: List[_ReqState],
              tel) -> float:
        """One serving step, each phase in an ``engine.<phase>`` span on the
        host clock.  Returns the step's scheduler makespan, the simulated
        clock's advance (0 when nothing is active after admission)."""
        sched = self._new_scheduler()
        self.last_scheduler = sched
        self.pool.bind(sched)
        _SERVING.inc("steps")
        _SERVING.record_max("queue_depth_hw", len(queue))

        with _tm.span("engine.admit", "engine"):
            restored, admitted = self._admit(active, preempted, queue, clock)
            if restored:
                sched.flush()
                self.pool.commit()                 # restored pages land now

        # prefill new admissions, grouped by prompt length so one jitted
        # program covers each group (and a gang of equal prompts runs the
        # exact fixed-batch prefill program)
        if admitted:
            with _tm.span("engine.prefill", "engine", requests=len(admitted)):
                by_len: Dict[int, List[_ReqState]] = {}
                for st in admitted:
                    by_len.setdefault(st.req.prompt_len, []).append(st)
                for plen, group in sorted(by_len.items()):
                    toks = jnp.asarray(
                        np.stack([st.req.tokens for st in group]), jnp.int32)
                    cache0 = lm.init_cache(self.cfg, len(group), self.max_len,
                                           self.cache_dtype)
                    logits, cache = self._run(self._prefill, self.params,
                                              {"tokens": toks}, cache0)
                    cost = 2.0 * self._n_params * len(group) * plen / HW_FLOPS
                    cfut = sched.submit_compute(
                        lambda *a: None, cost_s=cost,
                        label=f"compute:prefill:{plen}")
                    nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
                    now = tel.clock() if tel is not None else 0.0
                    for i, st in enumerate(group):
                        st.pos = plen
                        st.generated.append(int(nxt[i]))
                        if tel is not None:
                            self._stamp(st, now, tel)
                    self._scatter(group, cache, deps=(cfut,), label="store")
                sched.flush()
                self.pool.commit()

        if not active:
            return 0.0

        # memory pressure: will the next decode's page growth fit?
        with _tm.span("engine.preempt", "engine"):
            decoding = [st for st in active if not st.done_tokens
                        or self._gang_member(st)]
            growth = sum(self._growth(st.pos) for st in decoding)
            while growth > self.pool.free_pages and len(active) > 1:
                victim = max(active, key=lambda s: s.req.arrival_s)
                active.remove(victim)
                for plist in victim.pages.values():
                    for pid in plist:
                        self.pool.evict(pid)
                victim.status = "preempted"
                preempted.append(victim)
                preempted.sort(key=lambda s: s.req.arrival_s)
                self.preemptions += 1
                _SERVING.inc("preemptions")
                sched.flush()
                self.pool.commit()                 # slots free for the rest
                decoding = [st for st in active if not st.done_tokens
                            or self._gang_member(st)]
                growth = sum(self._growth(st.pos) for st in decoding)

        # gather -> compose -> decode -> scatter dirty pages
        with _tm.span("engine.gather", "engine"):
            gathered = [self._gather(st) for st in active]
            sched.flush()
        with _tm.span("engine.compose", "engine"):
            cache = self._compose_cache(active, gathered)
        # the program call through the argmax read-back: the step's one
        # host-device sync
        with _tm.span("engine.decode", "engine", batch=len(active)):
            toks = jnp.asarray([[st.generated[-1]] for st in active],
                               jnp.int32)
            logits, cache = self._run(self._decode, self.params, toks, cache)
            gfuts = [f for g in gathered for fl in g.values() for f in fl]
            cost = 2.0 * self._n_params * len(active) / HW_FLOPS
            cfut = sched.submit_compute(lambda *a: None, *gfuts, cost_s=cost,
                                        label="compute:decode")
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            now = tel.clock() if tel is not None else 0.0
        with _tm.span("engine.scatter", "engine"):
            written = [st.pos for st in active]    # decode wrote these slots
            for i, st in enumerate(active):
                st.pos = min(st.pos + 1, self.max_len)
                if not st.done_tokens:
                    st.generated.append(int(nxt[i]))
                    if tel is not None:
                        self._stamp(st, now, tel)
            self._scatter(active, cache, deps=(cfut,), written=written,
                          label="decode")
            sched.flush()
            self.pool.commit()
        if self.auto_defrag and self.pool.fragmentation():
            with _tm.span("engine.defrag", "engine"):
                self.pool.defrag()
                sched.flush()
                self.pool.commit()
        return sched.makespan()

    def _gang_member(self, st: _ReqState) -> bool:
        return False                               # continuous: no gangs

    def _gang_holds(self, active) -> bool:
        return False                               # continuous: no gangs

    def _finish(self, st: _ReqState, active: List[_ReqState],
                clock: float) -> None:
        active.remove(st)
        st.status = "done"
        if st.finish_s < 0:
            st.finish_s = clock
        for plist in st.pages.values():
            for pid in plist:
                self.pool.free(pid)
        st.pages.clear()

    def _report(self, states, clock) -> ServeReport:
        done = [st for st in states.values() if st.status == "done"]
        lats = np.asarray([st.finish_s - st.req.arrival_s for st in done]) \
            if done else np.asarray([0.0])
        total = sum(len(st.generated) for st in done)
        ttfts = np.asarray([st.token_times[0] - st.req.arrival_s
                            for st in done if st.token_times]) \
            if done else np.asarray([])
        tbts = np.asarray([b - a for st in done
                           for a, b in zip(st.token_times, st.token_times[1:])])
        if ttfts.size == 0:
            ttfts = np.asarray([0.0])
        if tbts.size == 0:
            tbts = np.asarray([0.0])
        return ServeReport(
            engine=self.name, n_requests=len(done), total_tokens=total,
            elapsed_s=clock, tokens_per_s=total / clock if clock else 0.0,
            p50_s=float(np.percentile(lats, 50)),
            p99_s=float(np.percentile(lats, 99)),
            steps=self.steps, preemptions=self.preemptions,
            pool_stats=dict(self.pool.stats),
            tokens={st.req.rid: np.asarray(st.generated, np.int32)
                    for st in done},
            ttft_p50_s=float(np.percentile(ttfts, 50)),
            ttft_p99_s=float(np.percentile(ttfts, 99)),
            tbt_p50_s=float(np.percentile(tbts, 50)),
            tbt_p99_s=float(np.percentile(tbts, 99)))


class StaticBatchEngine(ContinuousBatchingEngine):
    """The fixed-gang baseline: admission only when the engine is empty, and
    the gang holds its batch rows (decode compute + full page traffic) until
    every member drains — the serving shape ``ServingEngine.generate``
    implements, extended with arrivals and queueing."""

    name = "static"

    def _admit(self, active, preempted, queue, clock):
        if active:                                 # gang still draining
            return [], []
        return super()._admit(active, preempted, queue, clock)

    def _gang_member(self, st: _ReqState) -> bool:
        return True                                # finished rows keep going

    def _gang_holds(self, active) -> bool:
        return not all(st.done_tokens for st in active)
