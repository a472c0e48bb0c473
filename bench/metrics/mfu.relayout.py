"""The least HBM bytes of the traced window's relayouts (inputs read once,
outputs written once) over the window's length times the chip's HBM
bandwidth: the whole request's share of the peak that bounds it (a
relayout does no arithmetic, so its peak is bandwidth)."""


def read(run):
    if not run.peaks:                      # no chip, no device number
        return None
    tr = run.trace_data
    if tr is None or not run.facts.get("hbm_bytes"):
        return None
    return 100.0 * run.facts["hbm_bytes"] / (tr.window_s * run.peaks["hbm_bytes_per_s"])
