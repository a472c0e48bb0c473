"""Share of the engine steps' host-clock time spent moving pages: the
``engine.gather``, ``engine.compose``, ``engine.scatter`` and
``engine.defrag`` spans over the ``engine.step`` spans, each counted where
it starts in the traced window (server loop)."""

PAGE_PHASES = ("engine.gather", "engine.compose", "engine.scatter",
               "engine.defrag")


def read(run):
    tr = run.trace_data
    if tr is None:
        return None
    step = page = 0
    for e in tr.host:
        if not tr.lo <= e.start < tr.hi:
            continue
        if e.name == "engine.step":
            step += e.end - e.start
        elif e.name in PAGE_PHASES:
            page += e.end - e.start
    if not step:
        return None
    return 100.0 * page / step
