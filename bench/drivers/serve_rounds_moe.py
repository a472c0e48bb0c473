"""Offline rounds of a Qwen3-MoE model at one chip's expert share, served by
``ContinuousBatchingEngine`` over a paged-KV pool.

The rounds of ``serve_rounds.py`` with their own set-up and check: the
weights are ``bench/weights_moe.py``'s, the program's config holds the
experts of ``experts_held`` and routes over all of the router's outputs,
and the check runs the plain reference of ``bench/reference/moe.py``.  The
round, the window, the end-to-end metric and the traced decode steps are
``serve_rounds.Session``'s; the window's facts also carry the totals of the
engine's ``moe`` counter bank (``moe_<counter>``), which the cell's
per-layer readers read from the harness's bank deltas.
"""
from __future__ import annotations

import gc
from typing import List

import numpy as np

from bench import generate, weights_moe
from bench.drivers import serve_rounds
from bench.reference import moe as ref_moe


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a Qwen3-MoE config file."""
    from repro.configs.base import ATTN, LayerSpec, ModelConfig
    m = weights_moe.dims(cfg)
    return ModelConfig(
        name=cfg["model_type"], family="moe", d_model=m["d"], n_heads=m["H"],
        n_kv_heads=m["KV"], head_dim=m["hd"], d_ff=cfg["intermediate_size"],
        vocab=m["V"], period=(LayerSpec(ATTN, moe=True),), n_periods=m["L"],
        rope_theta=m["theta"], qk_norm=True, qkv_bias=m["qkv_bias"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]), norm_eps=m["eps"],
        n_experts=m["E"], top_k=m["k"], d_ff_expert=m["F"],
        experts_held=(m["first"], m["n"]))


def program_params(w: dict, model_cfg):
    """The benchmark's weights as the program's parameter tree (the same
    arrays, no copy); the tree must match ``lm.init_params``."""
    import jax
    from repro.models import lm
    params = {
        "embed": {"embed": w["embed"], "head": w["head"]},
        "blocks": ({"norm_mix": {"scale": w["ln1"]},
                    "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo",
                                               "q_norm", "k_norm")},
                    "norm_ffn": {"scale": w["ln2"]},
                    "ffn": {k: w[k] for k in ("router", "w_gate", "w_up",
                                              "w_down")}},),
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


def moe_bank() -> dict:
    from repro.runtime import telemetry
    return telemetry.bank("moe").as_dict()


class Session(serve_rounds.Session):
    def __init__(self, run):
        import jax.numpy as jnp
        from repro.serving import ContinuousBatchingEngine, PagedKVPool, Request

        self.run = run
        cfg, mix = run.config, run.mix
        self.dims = weights_moe.dims(cfg)
        self.dtype = jnp.dtype(cfg["served_dtype"])
        self.w = weights_moe.make(cfg, run.seed, cfg["served_dtype"])
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

    def window(self, seconds: float) -> dict:
        before = moe_bank()
        facts = super().window(seconds)
        facts.update({f"moe_{k}": v - before.get(k, 0)
                      for k, v in moe_bank().items()})
        return facts

    def check(self, operand_dtype=None):
        """(checks, failed) as ``serve_rounds.Session.check``, against the
        MoE reference."""
        dims = tuple(sorted(self.dims.items()))
        worst = 0.0
        for rid, served in self._checked():
            worst = max(worst, served_gap(self.w, self.reqs[rid].tokens,
                                          served, dims, operand_dtype))
        failed = sum(r["failed"] for r in self.rounds)
        return {"logit_gap": (worst, float(self.run.limits["logit_gap"]))}, failed


def served_gap(w, prompt, served, dims, operand_dtype=None) -> float:
    """``serve_rounds.served_gap`` with the MoE reference."""
    import jax.numpy as jnp
    seq, rows = serve_rounds.reference_inputs(prompt, served)
    seq, rows = jnp.asarray(seq), jnp.asarray(rows)
    ref = ref_moe.logits_at(w, seq, rows, dims=dims)
    if operand_dtype is None:
        chosen = jnp.asarray(served, jnp.int32)
    else:
        low = ref_moe.logits_at(w, seq, rows, dims=dims,
                                operand_dtype=operand_dtype)
        chosen = jnp.argmax(low, axis=-1)
    gap = ref.max(-1) - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    return float(gap.max())
