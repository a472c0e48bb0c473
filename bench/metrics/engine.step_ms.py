"""Mean host-clock length of an engine step, in milliseconds: the
``engine.step`` spans that start in the traced window (server loop)."""


def read(run):
    tr = run.trace_data
    if tr is None:
        return None
    steps = [e.end - e.start for e in tr.host
             if e.name == "engine.step" and tr.lo <= e.start < tr.hi]
    if not steps:
        return None
    return sum(steps) / len(steps) / 1e6
