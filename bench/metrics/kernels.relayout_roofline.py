"""The least HBM bytes of the traced window's relayouts (inputs read once,
outputs written once) over the chip's HBM bandwidth, as a share of the
device time of the relayout program: its Pallas store and load kernels and
the XLA copies between them.  Kernel time alone cannot be the
denominator: XLA places the kernels' operands in VMEM, so one kernel can
move its bytes faster than HBM allows."""


def read(run):
    if not run.peaks:                      # no chip, no device number
        return None
    tr = run.trace_data
    if tr is None or not run.facts.get("hbm_bytes"):
        return None
    seconds = tr.module_seconds(lambda name: name == "jit_roundtrip")
    if seconds <= 0:
        return None
    return 100.0 * run.facts["hbm_bytes"] / run.peaks["hbm_bytes_per_s"] / seconds
