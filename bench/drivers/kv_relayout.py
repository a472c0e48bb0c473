"""KV relayout round trips through the plane's store and load kernels.

Each request is one prefill's K and V, one (1, S, KV, hd) array per layer
and per K or V, as a prefill leaves them.  One jitted program per sequence
length stores every layer's K and V with
``kv_prefill_store`` (RMSNorm + tile) and loads them back with
``kv_load_transposed``.  Requests run one at a time and block at their end.
Set-up draws a cycle of requests from the seed (lengths in the mix's
proportions, inputs on the device) and runs each length once, which
compiles its program; the window walks the cycle until the first request
boundary at or after ``--seconds``.

The check compares the last output of every request in the cycle with a
plain ``jax.numpy`` RMSNorm and transpose in float32.
"""
from __future__ import annotations

import time
from typing import Dict

from bench import generate, weights
from bench.costs import relayout as costs
from bench.reference import relayout as ref


class Session:
    def __init__(self, run):
        import jax
        import jax.numpy as jnp
        from repro.serving.transfer import kv_load_transposed, kv_prefill_store

        self.run = run
        m = weights.dims(run.config)
        self.L, self.KV, self.hd = m["L"], m["KV"], m["hd"]
        self.dtype = jnp.dtype(run.config["served_dtype"])
        self.eps = 1e-6                      # kv_prefill_store's default
        self.lens = generate.relayout_requests(run.mix, run.seed)

        def draw(key, S):
            """One request: the K and V of every layer, separate arrays."""
            keys = jax.random.split(key, 2 * self.L)
            return [jax.random.normal(k, (1, S, self.KV, self.hd), self.dtype)
                    for k in keys]
        draw = jax.jit(draw, static_argnums=1)
        base = weights.seed_key(run.seed)
        self.inputs = [draw(jax.random.fold_in(base, i), S)
                       for i, S in enumerate(self.lens)]

        def roundtrip(kvs):
            return [kv_load_transposed(kv_prefill_store(x)) for x in kvs]
        self.program = jax.jit(roundtrip)
        self.outputs = [None] * len(self.lens)
        for S in sorted(set(self.lens)):                   # compile each length
            jax.block_until_ready(self.program(self.inputs[self.lens.index(S)]))

    def request_bytes(self, S: int) -> int:
        return costs.request_bytes(self.L, S, self.KV, self.hd,
                                   self.dtype.itemsize)

    def window(self, seconds: float) -> dict:
        import jax
        n, moved, least, done = len(self.lens), 0, 0, 0
        t0 = time.perf_counter()
        while True:
            i = done % n
            self.outputs[i] = jax.block_until_ready(self.program(self.inputs[i]))
            moved += self.request_bytes(self.lens[i])
            least += costs.request_min_hbm_bytes(self.L, self.lens[i], self.KV,
                                                 self.hd, self.dtype.itemsize)
            done += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        return {"window_s": elapsed, "attempted": done, "requests": done,
                "bytes": moved, "hbm_bytes": least}

    def end_to_end(self, facts: dict) -> Dict[str, float]:
        return {"relayout_gb_s": facts["bytes"] / facts["window_s"] / 1e9}

    def release(self):
        self.program = None

    def check(self, operand_dtype=None):
        """(checks, failed): the widest relative error of the loaded values;
        with ``operand_dtype``, of the reference rounded to it (the control)."""
        worst, failed = 0.0, 0
        limit = float(self.run.limits["relayout_err"])
        for kvs, outs in zip(self.inputs, self.outputs):
            if outs is None:
                continue
            if operand_dtype is None:
                errs = (ref.worst_error(x, y, self.eps) for x, y in zip(kvs, outs))
            else:
                errs = (ref.control_error(x, self.eps, operand_dtype) for x in kvs)
            err = max(errs)
            failed += err > limit
            worst = max(worst, err)
        return {"relayout_err": (worst, limit)}, failed
