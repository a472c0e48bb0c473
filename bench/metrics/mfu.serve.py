"""Model FLOPs of the traced window's prefill and decode tokens over the
window's length times the chip's bf16 peak (whole serving step)."""
from bench import weights
from bench.costs import lm as costs


def read(run):
    if not run.peaks:                      # no chip, no device number
        return None
    f, tr = run.facts, run.trace_data
    if tr is None or not f.get("prefill_lens"):
        return None
    m = weights.dims(run.config)
    flops = (sum(costs.prefill_flops(m, n) for n in f["prefill_lens"])
             + sum(costs.decode_flops(m, p) for p in f.get("decode_rows", [])))
    return 100.0 * flops / (tr.window_s * run.peaks["bf16_flops"])
