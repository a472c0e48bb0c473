"""Share of the traced window in which no op ran on the device."""


def read(run):
    tr = run.trace_data
    if tr is None or not tr.ops:
        return None
    return 100.0 * tr.idle_share()
