#!/usr/bin/env python3
"""Idle device time of a serving cell, by engine phase.

    python3 scripts/trace_phases.py --workload serve-qwen3-1.7b-chat --seed 7

Runs the cell's set-up as ``bench/run.py`` does, then one profiled window
of the mix's ``trace_seconds`` (Python tracer off), and prints one JSON
object: for each ``engine.*`` phase its host seconds, the device's busy
seconds inside it, and the idle seconds whose gap midpoint (as in
``Trace.idle_gaps``) lies in it, with the part of those that also lies in a
``sched.flush`` or ``pool.commit`` span.  Unlike the benchmark's traced
run, the engine's decode calls are not wrapped: that wrapper blocks on the
composed cache before each decode, which moves compose's device wait into
``engine.decode``.  Needs a TPU; :func:`by_phase` alone runs on any trace.
"""
import argparse
import bisect
import json
import pathlib
import sys
import tempfile
from typing import Dict, List, Tuple

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench.trace.reduce import WINDOW_SPAN, Trace, gaps, merge  # noqa: E402

STEP = "engine.step"
PHASES = ("engine.admit", "engine.prefill", "engine.preempt", "engine.gather",
          "engine.compose", "engine.decode", "engine.scatter", "engine.defrag")
FLUSHES = ("sched.flush", "pool.commit")
BETWEEN, OUTSIDE = "engine.step (between phases)", "outside steps"


def _covering(spans: List[Tuple[int, int, str]]):
    """A lookup: the name of the span in ``spans`` (disjoint) covering a
    time, or None."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]

    def find(t: int):
        k = bisect.bisect_right(starts, t) - 1
        return spans[k][2] if k >= 0 and t < spans[k][1] else None
    return find


def by_phase(tr: Trace) -> Dict[str, object]:
    """Host, busy and idle seconds of each engine phase in ``tr``'s window
    (the first chip's idle gaps)."""
    inwin = [e for e in tr.host if tr.lo <= e.start < tr.hi]
    host_s: Dict[str, float] = {}
    for e in inwin:
        if e.name in PHASES + (STEP,) + FLUSHES:
            host_s[e.name] = host_s.get(e.name, 0.0) + (e.end - e.start) / 1e9
    phase = _covering([(e.start, e.end, e.name) for e in tr.host
                       if e.name in PHASES])
    step = _covering([(s, e, STEP) for s, e in merge(
        (e.start, e.end) for e in tr.host if e.name == STEP)])
    flush = _covering([(s, e, "flush") for s, e in merge(
        (e.start, e.end) for e in tr.host if e.name in FLUSHES)])
    idle_s: Dict[str, float] = {}
    idle_in_flush_s: Dict[str, float] = {}
    first = tr.ops[tr.devices[0]] if tr.ops else []
    for s, e in gaps(((x.start, x.end) for x in first), tr.lo, tr.hi):
        mid = (s + e) // 2
        name = phase(mid) or (BETWEEN if step(mid) else OUTSIDE)
        idle_s[name] = idle_s.get(name, 0.0) + (e - s) / 1e9
        if flush(mid):
            idle_in_flush_s[name] = idle_in_flush_s.get(name, 0.0) + (e - s) / 1e9
    return {
        "window_s": tr.window_s,
        "steps": sum(1 for e in inwin if e.name == STEP),
        "busy_s": tr.busy_s(),
        "host_s": host_s,
        "busy_in_phase_s": {p: tr.span_busy_s(p) for p in PHASES
                            if p in host_s},
        "idle_s": idle_s,
        "idle_in_flush_s": idle_in_flush_s,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness

    run, driver = harness.prepare(args.workload, args.seed, 0.0, False)
    session = driver.Session(run)             # set-up: weights, warm-up
    with tempfile.TemporaryDirectory(prefix="trace_phases_") as tdir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                facts = session.window(float(run.mix["trace_seconds"]))
        finally:
            jax.profiler.stop_trace()
        tr = Trace.from_dir(tdir)
    print(json.dumps(dict(cell=args.workload, rounds=facts["rounds"],
                          **by_phase(tr))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
