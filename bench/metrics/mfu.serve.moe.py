"""Model FLOPs of the traced window's prefill and decode tokens over the
window's length times the chip's bf16 peak (whole serving step), for a
MoE model at one chip's expert share: attention, router and head on every
token, the held experts on the assignments they received (the ``moe``
bank's ``assignments_held``).  None without the bank."""
from bench import weights_moe
from bench.costs import moe as costs


def read(run):
    if not run.peaks:                      # no chip, no device number
        return None
    f, tr = run.facts, run.trace_data
    held = (run.banks.get("moe") or {}).get("assignments_held")
    if tr is None or not f.get("prefill_lens") or not held:
        return None
    m = weights_moe.dims(run.config)
    flops = (sum(costs.prefill_flops(m, n) for n in f["prefill_lens"])
             + sum(costs.decode_flops(m, p) for p in f.get("decode_rows", []))
             + costs.expert_flops(m, held))
    return 100.0 * flops / (tr.window_s * run.peaks["bf16_flops"])
