"""Share of the window's dispatched XDMA tasks that ran inside a fused
round program: the ``sched`` bank's ``batched_tasks`` over the ``links``
bank's ``tasks:<resource>`` counters (scheduler and transfer API)."""


def read(run):
    if "sched" not in run.banks:
        return None                       # the program has no such counter
    tasks = sum(v for k, v in run.banks.get("links", {}).items()
                if k.startswith("tasks:"))
    if not tasks:
        return None
    return 100.0 * run.banks["sched"].get("batched_tasks", 0) / tasks
