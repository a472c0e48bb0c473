"""Least HBM bytes of the window's decode steps over the chip's bandwidth,
as a share of the device time of the decode steps: the traced run runs
each step alone on the device inside a ``bench.decode`` host span.

The bytes are every weight once per step plus, for each row, its valid
cache positions read and one written; the program reads its whole
composed cache, so the share stays under 100%."""
import jax.numpy as jnp

from bench import weights
from bench.costs import lm as costs


def read(run):
    if not run.peaks:                      # no chip, no device number
        return None
    f, tr = run.facts, run.trace_data
    rows = f.get("decode_rows")
    if not rows or tr is None:
        return None
    seconds = tr.span_busy_s("bench.decode")
    if seconds <= 0:
        return None
    m = weights.dims(run.config)
    item = jnp.dtype(run.config["served_dtype"]).itemsize
    need = (f["decode_steps"] * costs.param_count(m) * item
            + sum(p + 1 for p in rows) * costs.kv_bytes_per_position(m, item))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
