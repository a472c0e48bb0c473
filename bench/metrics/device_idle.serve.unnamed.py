"""Share of the first chip's idle time in the traced window that no
program span names: an idle gap counts as unnamed when its midpoint (as in
``Trace.idle_gaps``) lies in no host span named ``engine.*``, ``sched.*``
or ``pool.*`` other than the container ``engine.step`` (device)."""
import bisect

from bench.trace.reduce import gaps, merge

PREFIXES = ("engine.", "sched.", "pool.")
CONTAINER = "engine.step"


def read(run):
    tr = run.trace_data
    if tr is None or not tr.ops:
        return None
    if not any(e.name == CONTAINER for e in tr.host):
        return None                       # the program has no such spans
    named = merge((e.start, e.end) for e in tr.host
                  if e.name.startswith(PREFIXES) and e.name != CONTAINER)
    starts = [s for s, _ in named]
    idle = unnamed = 0
    first = tr.ops[tr.devices[0]]
    for s, e in gaps(((x.start, x.end) for x in first), tr.lo, tr.hi):
        mid = (s + e) // 2
        k = bisect.bisect_right(starts, mid) - 1
        idle += e - s
        if k < 0 or named[k][1] <= mid:
            unnamed += e - s
    if not idle:
        return None
    return 100.0 * unnamed / idle
