"""The XDMA telemetry plane: CSR-style counter banks, spans, one snapshot.

Real DMA engines (the modular iDMA of Benz et al., DataMaestro's decoupled
streamers) expose per-channel CSR performance counters so the numbers a
paper reports — link utilization, per-transfer control overhead, end-to-end
latency — are observable in *deployment*, not just in a harness.  This
module is that CSR file for the whole reproduction (DESIGN.md §11):

* :class:`CounterBank` — one bank of named monotonic counters per domain.
  Banks are registered globally (:func:`bank`), increments are plain dict
  arithmetic (always on, exactly as cheap as the ad-hoc stats dicts they
  replace), and the five legacy stats surfaces —
  ``repro.core.api.cache_stats()``, ``repro.kernels.agu.agu_stats()``,
  ``repro.core.plugin_compiler.cfg_stats()``, the scheduler's per-link
  accounting, ``PagedKVPool.stats`` — are now thin views over these banks.
  The ring plane (DESIGN.md §12) adds a ``rings`` bank: doorbell posts,
  ring-full events, credits-in-flight high-water, per-tenant dispatches.
* :class:`Telemetry` — a *session*: host-clock spans (context managers)
  and value histograms (serving TTFT/TBT).  Sessions follow the same
  ambient discipline as :func:`repro.runtime.trace.capture`: :func:`session`
  installs one, and the per-task chokepoints (``xdma.transfer``,
  ``XDMAQueue.run``, ``DistributedScheduler.submit``/``submit_compute``)
  guard on a single ``is None`` check — with no session open they cost
  nothing and :func:`snapshot` returns ``{}``.
* :func:`span` — the layer spans (the serving engine's step phases, the
  scheduler's flush, the page pool's commit) have a second sink: while
  ``jax.profiler`` traces, each is also a ``TraceAnnotation`` of the same
  name, on the profile's clock beside the device ops.
* :func:`snapshot` — the one read port: every counter bank, every span,
  every histogram, plus the legacy surfaces re-exported verbatim, in one
  JSON-ready dict.  :mod:`repro.runtime.chrometrace` turns the spans (and
  any :class:`~repro.runtime.simulator.SimReport` replay) into Chrome
  trace-event JSON loadable in Perfetto.

This module is intentionally a *leaf*: it imports only the standard library
at module scope (the profiler hook resolves on first use), so the low-level
modules it instruments (``core/api``, ``kernels/agu``,
``core/plugin_compiler``) can import it without cycles.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

__all__ = ["CounterBank", "SpanEvent", "Telemetry", "bank", "banks",
           "register", "reset", "session", "active", "span", "record_value",
           "snapshot"]


# ---------------------------------------------------------------------------
# counter banks (always on — the CSR file)
# ---------------------------------------------------------------------------
class CounterBank:
    """One domain's named counters: monotonic counts plus high-water marks.

    Counter names are flat strings; structured counters use a ``:`` suffix
    convention (``bytes:<link>``, ``reason:<why>``) that
    :meth:`with_prefix` can strip back into a sub-dict.
    """

    __slots__ = ("domain", "_c")

    def __init__(self, domain: str):
        self.domain = domain
        self._c: Dict[str, int] = {}

    def inc(self, name: str, n: int = 1) -> None:
        """Add ``n`` to counter ``name`` (creating it at 0)."""
        self._c[name] = self._c.get(name, 0) + n

    def record_max(self, name: str, value: int) -> None:
        """High-water mark: keep the maximum ever seen for ``name``."""
        if value > self._c.get(name, 0):
            self._c[name] = value

    def set(self, name: str, value: int) -> None:
        self._c[name] = value

    def get(self, name: str, default: int = 0) -> int:
        return self._c.get(name, default)

    def __getitem__(self, name: str) -> int:
        return self._c.get(name, 0)

    def __contains__(self, name: str) -> bool:
        return name in self._c

    def __len__(self) -> int:
        return len(self._c)

    def as_dict(self) -> Dict[str, int]:
        """All counters, name-sorted (a stable JSON-ready view)."""
        return {k: self._c[k] for k in sorted(self._c)}

    def with_prefix(self, prefix: str) -> Dict[str, int]:
        """Counters named ``<prefix><rest>`` as ``{rest: value}``."""
        n = len(prefix)
        return {k[n:]: v for k, v in sorted(self._c.items())
                if k.startswith(prefix)}

    def clear(self) -> None:
        self._c.clear()

    def __repr__(self):
        return f"CounterBank({self.domain!r}, {len(self._c)} counters)"


_BANKS: Dict[str, CounterBank] = {}


def bank(domain: str) -> CounterBank:
    """Get (or create and register) the counter bank for ``domain``."""
    b = _BANKS.get(domain)
    if b is None:
        b = _BANKS[domain] = CounterBank(domain)
    return b


def register(b: CounterBank) -> CounterBank:
    """Register (or replace) a caller-owned bank under its domain.  Used by
    per-instance owners (one :class:`~repro.serving.paged.PagedKVPool` per
    engine): the owner keeps its own bank object — its stats view survives —
    while the registry always exposes the most recent instance."""
    _BANKS[b.domain] = b
    return b


def banks() -> Dict[str, CounterBank]:
    """Every registered bank, by domain (live objects, not copies)."""
    return dict(_BANKS)


def reset(domain: Optional[str] = None) -> None:
    """Zero one domain's counters, or every registered bank's."""
    if domain is not None:
        if domain in _BANKS:
            _BANKS[domain].clear()
        return
    for b in _BANKS.values():
        b.clear()


# ---------------------------------------------------------------------------
# spans + histograms (session-scoped — zero-cost when no session is open)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class SpanEvent:
    """One timed region.  ``track`` groups spans into timeline rows
    (``transfer`` / ``queue`` / ``scheduler`` for the chokepoints,
    ``engine`` for serving-step phases, ``scheduler`` and ``pool`` for the
    flush and commit); ``depth``/``parent`` encode the
    nesting observed at record time (host-clock spans nest by the Python
    ``with`` stack — under jit/shard_map that is trace-time nesting, once
    per compilation, exactly like :func:`repro.runtime.trace.capture`)."""

    name: str
    track: str
    start_s: float
    end_s: float
    depth: int = 0
    parent: int = -1                # index into Telemetry.spans, -1 = root
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return self.end_s - self.start_s

    def as_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "track": self.track,
                "start_s": self.start_s, "end_s": self.end_s,
                "depth": self.depth, "parent": self.parent,
                "args": dict(self.args)}


class Telemetry:
    """One telemetry session: spans and value histograms.

    ``clock`` supplies the span timestamps (default ``time.perf_counter``).
    """

    def __init__(self, name: str = "telemetry",
                 clock: Callable[[], float] = time.perf_counter):
        self.name = name
        self.clock = clock
        self.spans: List[SpanEvent] = []
        self.values: Dict[str, List[float]] = {}
        self._stack: List[int] = []     # indices of open host-clock spans

    # -- spans ---------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, track: str = "host", **args: Any
             ) -> Iterator[SpanEvent]:
        """Time a region on the host clock.  Nesting follows the ``with``
        stack: the yielded span's ``depth``/``parent`` point at the
        enclosing open span."""
        ev = SpanEvent(name=name, track=track, start_s=self.clock(),
                       end_s=0.0, depth=len(self._stack),
                       parent=self._stack[-1] if self._stack else -1,
                       args=dict(args))
        idx = len(self.spans)
        self.spans.append(ev)
        self._stack.append(idx)
        try:
            yield ev
        finally:
            self._stack.pop()
            ev.end_s = self.clock()

    def spans_on(self, track: str) -> List[SpanEvent]:
        return [s for s in self.spans if s.track == track]

    # -- histograms ----------------------------------------------------------
    def record_value(self, name: str, value: float) -> None:
        """Append one sample to histogram ``name`` (TTFT/TBT seconds...)."""
        self.values.setdefault(name, []).append(float(value))

    def percentile(self, name: str, q: float) -> float:
        """Nearest-rank percentile of histogram ``name``: the smallest
        recorded sample with at least ``q``% of the samples at or below it
        (``ceil(n*q/100)``-th order statistic) — always an actual sample,
        never an interpolated value, so a 1-sample p99 is that sample and a
        2-sample p99 is the max.  0.0 when the histogram is empty."""
        vals = sorted(self.values.get(name, ()))
        if not vals:
            return 0.0
        k = max(1, math.ceil(len(vals) * float(q) / 100.0))
        return vals[min(k, len(vals)) - 1]

    def histogram_summary(self, name: str) -> Dict[str, float]:
        vals = self.values.get(name, ())
        if not vals:
            return {"count": 0}
        return {"count": len(vals), "mean": sum(vals) / len(vals),
                "min": min(vals), "max": max(vals),
                "p50": self.percentile(name, 50),
                "p99": self.percentile(name, 99)}

    def summary(self) -> str:
        return (f"Telemetry({self.name!r}, {len(self.spans)} spans, "
                f"{sum(len(v) for v in self.values.values())} samples, "
                f"{len(_BANKS)} counter banks)")


# -- the ambient session slot (same `is None` discipline as trace._CAPTURE) --
_ACTIVE: Optional[Telemetry] = None
_NULL = contextlib.nullcontext()


def active() -> Optional[Telemetry]:
    """The ambient telemetry session, or None when telemetry is off."""
    return _ACTIVE


@contextlib.contextmanager
def session(tel: Optional[Telemetry] = None, *, name: str = "telemetry",
            clock: Callable[[], float] = time.perf_counter
            ) -> Iterator[Telemetry]:
    """Open a telemetry session: the chokepoints' span hooks and the serving
    SLO recorders write into the yielded :class:`Telemetry`.  Nested
    sessions shadow the outer one (innermost wins), mirroring
    :func:`repro.runtime.trace.capture`."""
    global _ACTIVE
    t = tel if tel is not None else Telemetry(name=name, clock=clock)
    prev = _ACTIVE
    _ACTIVE = t
    try:
        yield t
    finally:
        _ACTIVE = prev


_ANNOTATION = None          # jax.profiler.TraceAnnotation, once resolved


def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use (this module
    keeps to the standard library at module scope)."""
    global _ANNOTATION
    from jax.profiler import TraceAnnotation
    _ANNOTATION = TraceAnnotation
    return TraceAnnotation


@contextlib.contextmanager
def _both(tel: Telemetry, ann, name: str, track: str,
          args: Dict[str, Any]) -> Iterator[SpanEvent]:
    with ann(name, **args), tel.span(name, track=track, **args) as ev:
        yield ev


def span(name: str, track: str = "host", **args: Any):
    """Module-level span hook with two sinks: an open session records a
    :class:`SpanEvent`, and a running ``jax.profiler`` trace gets a
    ``TraceAnnotation`` named ``name`` whose stats are ``args``, on the
    same clock as the device ops.  With neither it returns a shared no-op
    context (one ``is None`` check and one ``is_enabled()`` call, nothing
    allocated)."""
    a = _ACTIVE
    ann = _ANNOTATION or _annotation()
    if ann.is_enabled():
        if a is None:
            return ann(name, **args)
        return _both(a, ann, name, track, args)
    if a is None:
        return _NULL
    return a.span(name, track=track, **args)


def record_value(name: str, value: float) -> None:
    """Module-level histogram hook (no-op without an open session)."""
    a = _ACTIVE
    if a is not None:
        a.record_value(name, value)


# ---------------------------------------------------------------------------
# the one read port
# ---------------------------------------------------------------------------
def snapshot() -> Dict[str, Any]:
    """Everything the telemetry plane knows, as one JSON-ready dict — or
    ``{}`` when no session is open (telemetry disabled: nothing to read,
    nothing computed).

    ``counters`` holds every registered bank; ``surfaces`` re-exports the
    five legacy stats surfaces *verbatim* (they are views over the same
    banks, so the reconciliation is structural, not coincidental) plus the
    ring plane's ``scheduler_rings`` bank; ``spans``/``histograms`` are the
    session's timing data.
    """
    a = _ACTIVE
    if a is None:
        return {}
    # lazy imports: the legacy surfaces live in modules that import *us*
    from repro.core import api as _api
    from repro.core import autotune as _at
    from repro.core import plugin_compiler as _pc
    from repro.kernels import agu as _agu

    cs = _api.cache_stats()
    surfaces: Dict[str, Any] = {
        "cache_stats": {"hits": cs.hits, "misses": cs.misses,
                        "evictions": cs.evictions, "size": cs.size},
        "agu_stats": _agu.agu_stats(),
        "autotune_stats": _at.autotune_stats(),
        "cfg_stats": _pc.cfg_stats(),
        "scheduler_links": bank("links").as_dict(),
        "scheduler_rings": bank("rings").as_dict(),
        "multicast_stats": bank("multicast").as_dict(),
        "pool_stats": {d[len("pool:"):]: b.as_dict()
                       for d, b in _BANKS.items() if d.startswith("pool:")},
    }
    return {
        "session": a.name,
        "counters": {d: b.as_dict() for d, b in _BANKS.items()},
        "surfaces": surfaces,
        "spans": [s.as_dict() for s in a.spans],
        "histograms": {k: a.histogram_summary(k) for k in sorted(a.values)},
    }
