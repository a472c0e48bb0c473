"""A profiler trace reduced to the numbers the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each TPU is a plane named ``/device:TPU:<n>`` whose ``XLA Ops``
line holds one event per executed HLO op and whose ``XLA Modules`` line
holds one event per program run; host threads are lines of ``/host:CPU``.
The benchmark wraps its traced window in a host span (``WINDOW_SPAN``),
and every number here is clipped to that span.
"""
from __future__ import annotations

import bisect
import collections
import glob
import heapq
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, non-overlapping union of [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union_ns(intervals: Iterable[Interval], lo: int, hi: int) -> int:
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def gaps(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


class Event(collections.namedtuple("Event", "start end name stats")):
    __slots__ = ()


class Trace:
    """Device op and module events per chip, host events, and the window."""

    def __init__(self, ops: Dict[str, List[Event]],
                 modules: Dict[str, List[Event]], host: List[Event],
                 window: Interval):
        self.ops, self.modules, self.host = ops, modules, host
        self.lo, self.hi = window

    # -- construction --------------------------------------------------------
    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(paths[-1]))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops: Dict[str, List[Event]] = {}
        modules: Dict[str, List[Event]] = {}
        host: List[Event] = []
        for plane in pd.planes:
            device = plane.name.startswith("/device:") and "CPU" not in plane.name
            for line in plane.lines:
                if device and line.name in (OPS_LINE, MODULES_LINE):
                    dest = (ops if line.name == OPS_LINE else modules)
                    dest.setdefault(plane.name, []).extend(
                        _events(line.events))
                elif plane.name == "/host:CPU":
                    host.extend(_events(line.events))
        spans = [e for e in host if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace has no {WINDOW_SPAN!r} host span")
        w = max(spans, key=lambda e: e.end - e.start)
        return cls(ops, modules, host, (w.start, w.end))

    # -- reductions ----------------------------------------------------------
    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(union_ns(((e.start, e.end) for e in evs), self.lo, self.hi)
                   for evs in self.ops.values()) / len(self.ops) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def module_seconds(self, keep: Callable[[str], bool]) -> float:
        """Summed device time of the program runs whose module name ``keep``
        selects, averaged over the chips."""
        if not self.modules:
            return 0.0
        total = sum(e - s for evs in self.modules.values()
                    for s, e in clip(((x.start, x.end) for x in evs
                                      if keep(module_name(x.name))),
                                     self.lo, self.hi))
        return total / len(self.modules) / 1e9

    def span_busy_s(self, span: str) -> float:
        """Device busy time inside the host spans named ``span``, averaged
        over the chips."""
        spans = [(h.start, h.end) for h in self.host if h.name == span]
        if not self.ops or not spans:
            return 0.0
        total = 0
        for evs in self.ops.values():
            iv = [(e.start, e.end) for e in evs]
            total += sum(union_ns(iv, max(s, self.lo), min(e, self.hi))
                         for s, e in merge(spans) if min(e, self.hi) > max(s, self.lo))
        return total / len(self.ops) / 1e9

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` ops with the most device time (per chip), each named
        ``<program>/<instruction> <opcode>``; an op belongs to the program
        run that covers its start."""
        acc: Dict[str, float] = collections.Counter()
        for dev, evs in self.ops.items():
            runs = sorted(self.modules.get(dev, []), key=lambda m: m.start)
            starts = [m.start for m in runs]
            for x in evs:
                s, e = max(x.start, self.lo), min(x.end, self.hi)
                if e <= s:
                    continue
                k = bisect.bisect_right(starts, x.start) - 1
                prog = (module_name(runs[k].name)
                        if k >= 0 and runs[k].end >= x.start else "?")
                acc[f"{prog}/{short_name(x.name)}"] += (e - s) / 1e9 / len(self.ops)
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle device time of the first chip, summed by what the host was
        doing at each gap's midpoint (the shortest host event covering it),
        the ``n`` largest."""
        if not self.ops:
            return []
        first = self.ops[self.devices[0]]
        host = sorted((e for e in self.host if e.name != WINDOW_SPAN),
                      key=lambda e: e.start)
        acc: Dict[str, float] = collections.Counter()
        # sweep the gaps' midpoints in order; ``live`` holds the host events
        # begun so far, shortest first, and drops those that have ended
        live: List[tuple] = []
        i = 0
        for s, e in gaps(((x.start, x.end) for x in first), self.lo, self.hi):
            mid = (s + e) // 2
            while i < len(host) and host[i].start <= mid:
                h = host[i]
                heapq.heappush(live, (h.end - h.start, h.end, h.name))
                i += 1
            while live and live[0][1] <= mid:
                heapq.heappop(live)
            acc[live[0][2] if live else "(no host event)"] += (e - s) / 1e9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def short_name(hlo_text: str) -> str:
    """``%run.144 = bf16[..] custom-call(..), custom_call_target="x"`` ->
    ``run.144 custom-call:x``; ``%fusion.3 = (..) fusion(..), kind=kLoop``
    -> ``fusion.3 fusion:kLoop``."""
    if " = " not in hlo_text:
        return hlo_text[:80]
    name, rest = hlo_text.split(" = ", 1)
    if rest.startswith("("):                       # tuple type: skip to ')'
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else rest
    opcode = rest.strip().split("(", 1)[0]
    detail = ""
    for key in ('custom_call_target="', "kind="):
        if key in rest:
            detail = ":" + rest.split(key, 1)[1].split('"', 1)[0].split(",", 1)[0]
            break
    return f"{name.lstrip('%')} {opcode}{detail}"


def module_name(event_name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return event_name.split("(", 1)[0]


def _events(events) -> List[Event]:
    out = []
    for e in events:
        start = int(e.start_ns)
        out.append(Event(start, start + int(e.duration_ns), e.name, {}))
    return out


def from_events(ops: Dict[str, Sequence[tuple]], host: Sequence[tuple],
                window: Interval, modules: Dict[str, Sequence[tuple]] = None
                ) -> Trace:
    """A Trace from plain (start, end, name[, stats]) tuples (tests)."""
    mk = lambda t: Event(t[0], t[1], t[2], t[3] if len(t) > 3 else {})
    return Trace({d: [mk(t) for t in evs] for d, evs in ops.items()},
                 {d: [mk(t) for t in evs] for d, evs in (modules or {}).items()},
                 [mk(t) for t in host], window)
