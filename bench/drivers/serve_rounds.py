"""Offline rounds served by ``ContinuousBatchingEngine`` over a paged-KV pool.

Set-up draws the weights and one round of requests from the seed
(``bench/generate.py``), builds the engine with a pool that holds the whole
round, and serves the round once, which compiles every program the round
needs.  The window replays that round until the first round boundary at or
after ``--seconds``: the same page counts every round, so nothing compiles
inside it.

After every round this module runs Python's cyclic garbage collector, inside
the window, so its time counts: the engine leaves each round's device
arrays in reference cycles (about 6-12 GB of HBM after a long-prompt
round on a v5e, 4 GB after a collection), and without a collection the
third round runs the chip out of memory.  Set-up's objects are frozen
(``gc.freeze``) so that no collection in the window walks them again.

The check runs the plain float32 reference (``bench/reference/lm.py``) over
every distinct (prompt, served tokens) pair the window finished and reads,
at each served token, how far its reference logit lies below the
reference's best.  Greedy decoding would pick the best; rounding in the
served precision may pick a near-tie.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from bench import generate, weights
from bench.reference import lm as ref_lm

SEQ_BUCKET = 512          # reference sequences pad to a multiple of this
MAX_CHECKED = 24          # distinct requests the check compares at most
DECODE_SPAN = "bench.decode"


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a Hugging Face Qwen2/Qwen3 config."""
    from repro.configs.base import ATTN, LayerSpec, ModelConfig
    m = weights.dims(cfg)
    return ModelConfig(
        name=cfg["model_type"], family="dense", d_model=m["d"], n_heads=m["H"],
        n_kv_heads=m["KV"], head_dim=m["hd"], d_ff=m["F"], vocab=m["V"],
        period=(LayerSpec(ATTN),), n_periods=m["L"], rope_theta=m["theta"],
        qk_norm=m["qk_norm"], qkv_bias=m["qkv_bias"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]), norm_eps=m["eps"])


def program_params(w: dict, model_cfg):
    """The benchmark's weights arranged as the program's parameter tree
    (the same arrays, no copy); the tree must match ``lm.init_params``."""
    import jax
    from repro.models import lm
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm",
                              "bq", "bk", "bv") if k in w}
    params = {
        "embed": {"embed": w["embed"]},
        "blocks": ({"norm_mix": {"scale": w["ln1"]}, "attn": attn,
                    "norm_ffn": {"scale": w["ln2"]},
                    "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}},),
        "tail": (),
        "norm_final": {"scale": w["final_norm"]},
    }
    want = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), model_cfg))
    got_s, want_s = (jax.tree.structure(t) for t in (params, want))
    if got_s != want_s:
        raise ValueError(f"parameter tree {got_s} != program's {want_s}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if a.shape != b.shape:
            raise ValueError(f"parameter shape {a.shape} != {b.shape}")
    return params


class Session:
    def __init__(self, run):
        import jax.numpy as jnp
        from repro.serving import ContinuousBatchingEngine, PagedKVPool, Request

        self.run = run
        cfg, mix = run.config, run.mix
        self.dims = weights.dims(cfg)
        self.dtype = jnp.dtype(cfg["served_dtype"])
        self.w = weights.make(cfg, run.seed, cfg["served_dtype"])
        model_cfg = program_config(cfg)
        round_ = generate.serve_round(mix, self.dims["V"], run.seed)
        self.reqs = [Request(rid=i, arrival_s=0.0, tokens=t, max_new=a)
                     for i, (t, a) in enumerate(round_)]
        max_len = max(r.total_len for r in self.reqs)
        tpp = int(cfg["assumed"]["tokens_per_page"])
        rows_per_token = self.dims["L"] * self.dims["KV"]
        # K and V each page their valid prefix: the pool holds the round
        pages = sum(2 * -(-r.total_len // tpp) for r in self.reqs)
        self.pool = PagedKVPool(pages, tpp * rows_per_token)
        self.engine = ContinuousBatchingEngine(
            model_cfg, program_params(self.w, model_cfg), max_len,
            max_batch=int(mix["max_batch"]), cache_dtype=self.dtype,
            pool=self.pool)
        self.decode_rows: List[np.ndarray] = []
        if run.trace:
            self._record_decodes()
        self._serve_round()                   # compiles the round's programs
        gc.collect()
        gc.freeze()
        self.decode_rows.clear()

    def _record_decodes(self):
        """Traced runs note each decode step's cache positions (one small
        device-to-host read per step) and run the step alone on the device
        inside a ``DECODE_SPAN`` host span, for the decode roofline."""
        import jax
        inner = self.engine._decode

        def decode(params, toks, cache):
            pos = np.asarray(cache["pos"])
            self.decode_rows.append(np.broadcast_to(pos, (toks.shape[0],)))
            jax.block_until_ready(cache)          # composition has finished
            with jax.profiler.TraceAnnotation(DECODE_SPAN):
                out = jax.block_until_ready(inner(params, toks, cache))
            return out
        self.engine._decode = decode

    def _serve_round(self) -> dict:
        rep = self.engine.serve(self.reqs)
        done = sum(1 for r in self.reqs
                   if len(rep.tokens.get(r.rid, ())) == r.max_new)
        return {"tokens": rep.tokens, "generated": rep.total_tokens,
                "steps": rep.steps, "preemptions": rep.preemptions,
                "failed": len(self.reqs) - done}

    def window(self, seconds: float) -> dict:
        rounds: List[dict] = []
        gc_s = 0.0
        t0 = time.perf_counter()
        while True:
            rounds.append(self._serve_round())
            t_gc = time.perf_counter()
            gc.collect()                      # frees the round's cycles
            gc_s += time.perf_counter() - t_gc
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.rounds = rounds
        return {
            "window_s": elapsed,
            "gc_s": gc_s,
            "rounds": len(rounds),
            "attempted": len(rounds) * len(self.reqs),
            "generated_tokens": sum(r["generated"] for r in rounds),
            "prefill_tokens": len(rounds) * sum(r.prompt_len for r in self.reqs),
            "requests": len(rounds) * len(self.reqs),
            "engine_steps": sum(r["steps"] for r in rounds),
            "preemptions": sum(r["preemptions"] for r in rounds),
            "failed": sum(r["failed"] for r in rounds),
            "decode_steps": len(self.decode_rows),
            "decode_rows": [int(p) for rows in self.decode_rows for p in rows],
            "prefill_lens": [r.prompt_len for r in self.reqs] * len(rounds),
        }

    def end_to_end(self, facts: dict) -> Dict[str, float]:
        return {"serve_tokens_per_s": facts["generated_tokens"] / facts["window_s"]}

    def release(self):
        """Free the program's state; the benchmark's weights stay."""
        self.engine = None
        self.pool = None
        gc.unfreeze()

    def _checked(self):
        """The distinct (rid, served tokens) pairs the check compares."""
        distinct = {}
        for r in self.rounds:
            for rid, toks in r["tokens"].items():
                distinct.setdefault((rid, toks.tobytes()), (rid, toks))
        items = list(distinct.values())
        if len(items) > MAX_CHECKED:
            longest = max(items, key=lambda it: self.reqs[it[0]].total_len)
            g = generate.rng(self.run.seed, "check")
            pick = g.choice(len(items), MAX_CHECKED - 1, replace=False)
            items = [longest] + [items[i] for i in pick if items[i] is not longest]
        return items

    def check(self, operand_dtype=None):
        """(checks, failed): the widest logit gap of a served token; with
        ``operand_dtype``, of the tokens the reference in that precision
        puts first (the control)."""
        dims = tuple(sorted(self.dims.items()))
        worst = 0.0
        for rid, served in self._checked():
            worst = max(worst, served_gap(self.w, self.reqs[rid].tokens,
                                          served, dims, operand_dtype))
        failed = sum(r["failed"] for r in self.rounds)
        return {"logit_gap": (worst, float(self.run.limits["logit_gap"]))}, failed


def reference_inputs(prompt: np.ndarray, served: np.ndarray):
    """Padded token sequence and the rows whose logits chose ``served``."""
    n, p = len(served), len(prompt)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    padded = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
    seq = np.pad(seq, (0, padded - len(seq)))
    return seq, np.arange(p - 1, p - 1 + n, dtype=np.int32)


def served_gap(w, prompt, served, dims, operand_dtype=None) -> float:
    """Widest reference-logit gap below the best over one request's served
    tokens (``operand_dtype`` set: of the tokens that precision puts first)."""
    import jax.numpy as jnp
    seq, rows = reference_inputs(prompt, served)
    ref = ref_lm.logits_at(w, jnp.asarray(seq), jnp.asarray(rows), dims=dims)
    if operand_dtype is None:
        chosen = jnp.asarray(served, jnp.int32)
    else:
        low = ref_lm.logits_at(w, jnp.asarray(seq), jnp.asarray(rows),
                               dims=dims, operand_dtype=operand_dtype)
        chosen = jnp.argmax(low, axis=-1)
    gap = ref.max(-1) - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    return float(gap.max())
