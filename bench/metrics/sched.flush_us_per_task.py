"""Host-clock microseconds of the scheduler's flushes per XDMA task they
dispatched: the ``sched.flush`` spans that start in the traced window over
the window's ``links`` bank ``tasks:<resource>`` counters (scheduler and
transfer API)."""


def read(run):
    tr = run.trace_data
    tasks = sum(v for k, v in run.banks.get("links", {}).items()
                if k.startswith("tasks:"))
    if tr is None or not tasks:
        return None
    ns = sum(e.end - e.start for e in tr.host
             if e.name == "sched.flush" and tr.lo <= e.start < tr.hi)
    if not ns:
        return None
    return ns / 1e3 / tasks
