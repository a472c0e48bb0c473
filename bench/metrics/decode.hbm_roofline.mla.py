"""Least HBM bytes of the window's decode steps over the chip's bandwidth,
as a share of the device time of the decode steps (each runs alone inside a
``bench.decode`` host span), for an MLA model at one chip's expert share.

The bytes: attention, norms, routers, shared experts and head once a step;
one read of a held expert for each (step, layer) in which some token
reached it (the ``moe`` bank's ``decode_experts_touched``); for each row
its valid latent positions read and one written, ``r + dr`` columns a
layer.  Held experts no token reached are not counted, so the share stays
under 100%.  None without the bank."""
import jax.numpy as jnp

from bench import weights_mla
from bench.costs import mla as costs


def read(run):
    if not run.peaks:                      # no chip, no device number
        return None
    f, tr = run.facts, run.trace_data
    rows = f.get("decode_rows")
    touched = (run.banks.get("moe") or {}).get("decode_experts_touched")
    if not rows or tr is None or not touched:
        return None
    seconds = tr.span_busy_s("bench.decode")
    if seconds <= 0:
        return None
    m = weights_mla.dims(run.config)
    item = jnp.dtype(run.config["served_dtype"]).itemsize
    need = (costs.decode_weight_bytes(m, item, f["decode_steps"], touched)
            + sum(p + 1 for p in rows) * costs.latent_bytes_per_position(m, item))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
