"""XDMA tasks the window dispatched per XDMA program the scheduler launched:
the ``links`` bank's ``tasks:<resource>`` counters over the ``sched`` bank's
``programs`` (scheduler and transfer API)."""


def read(run):
    programs = run.banks.get("sched", {}).get("programs", 0)
    if not programs:
        return None                       # the program has no such counter
    tasks = sum(v for k, v in run.banks.get("links", {}).items()
                if k.startswith("tasks:"))
    return tasks / programs
