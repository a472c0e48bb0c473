"""Offline rounds of a DeepSeek-V3-architecture model (multi-head latent
attention over a latent cache, sigmoid-routed experts beside shared ones,
leading dense layers) at one chip's expert share, served by
``ContinuousBatchingEngine`` over a paged-KV pool.

The rounds of ``serve_rounds.py`` with their own set-up and check: the
weights are ``bench/weights_mla.py``'s, the program's config holds the
experts of ``experts_held`` and routes over all of the router's outputs,
and the check runs the plain reference of ``bench/reference/mla.py``
and reads the widest and the mean logit gap of the served tokens.  The
cache is one latent leaf per layer group (the leading layers', and the
scanned sparse layers'), each paging its valid prefix; a pool page holds
``tokens_per_page`` tokens of the sparse layers' leaf.  The round, the
window, the end-to-end metric and the traced decode steps are
``serve_rounds.Session``'s; the window's facts also carry the totals of the
engine's ``moe`` counter bank (``moe_<counter>``), as in
``serve_rounds_moe.py``.
"""
from __future__ import annotations

import gc
from typing import List

import numpy as np

from bench import generate, weights_mla
from bench.drivers import serve_rounds, serve_rounds_moe
from bench.reference import mla as ref_mla


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a DeepSeek-V3-architecture config
    file."""
    from repro.configs.base import MLA, LayerSpec, ModelConfig
    m = weights_mla.dims(cfg)
    return ModelConfig(
        name=cfg["model_type"], family="moe", d_model=m["d"], n_heads=m["H"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=m["dn"] + m["dr"],
        d_ff=m["Fd"], vocab=m["V"], lead=(LayerSpec(MLA),) * m["Ld"],
        period=(LayerSpec(MLA, moe=True),), n_periods=m["L"] - m["Ld"],
        kv_lora_rank=m["r"], qk_nope_head_dim=m["dn"],
        qk_rope_head_dim=m["dr"], v_head_dim=m["dv"], rope_theta=m["theta"],
        tie_embeddings=False, norm_eps=m["eps"],
        n_experts=m["E"], top_k=m["k"], d_ff_expert=m["F"],
        d_ff_shared=m["Fs"], router_score="sigmoid", routed_scale=m["scale"],
        experts_held=(m["first"], m["n"]))


def program_params(w: dict, model_cfg):
    """The benchmark's weights as the program's parameter tree (the same
    arrays, no copy); the tree must match ``lm.init_params``."""
    import jax
    from repro.models import lm

    def slot(pre, ffn):
        return {"norm_mix": {"scale": w[pre + "ln1"]},
                "attn": {k: w[pre + k] for k in ("wq", "wkv_a", "kv_norm",
                                                 "wkv_b", "wo")},
                "norm_ffn": {"scale": w[pre + "ln2"]}, "ffn": ffn}

    lead = tuple(slot(f"lead{i}.", {k: w[f"lead{i}.{k}"]
                                    for k in weights_mla.DENSE_KEYS})
                 for i in range(len(model_cfg.lead)))
    sparse = {k: w[k] for k in ("router", "router_bias", "w_gate", "w_up",
                                "w_down")}
    sparse["shared"] = {"w_gate": w["shared_gate"], "w_up": w["shared_up"],
                        "w_down": w["shared_down"]}
    params = {
        "embed": {"embed": w["embed"], "head": w["head"]},
        "lead": lead,
        "blocks": (slot("", sparse),),
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


class Session(serve_rounds_moe.Session):
    def __init__(self, run):
        import jax.numpy as jnp
        from repro.serving import ContinuousBatchingEngine, PagedKVPool, Request

        self.run = run
        cfg, mix = run.config, run.mix
        self.dims = weights_mla.dims(cfg)
        self.dtype = jnp.dtype(cfg["served_dtype"])
        model_cfg = program_config(cfg)       # before the weights: fails fast
        self.w = weights_mla.make(cfg, run.seed, cfg["served_dtype"])
        round_ = generate.serve_round(mix, self.dims["V"], run.seed)
        self.reqs = [Request(rid=i, arrival_s=0.0, tokens=t, max_new=a)
                     for i, (t, a) in enumerate(round_)]
        max_len = max(r.total_len for r in self.reqs)
        # a page holds tokens_per_page tokens of the sparse layers' latent
        # leaf (one row a layer and token) and rows_per_page tokens of the
        # leading layers'; each leaf pages its valid prefix: the pool holds
        # the round
        sparse = self.dims["L"] - self.dims["Ld"]
        rows_per_page = int(cfg["assumed"]["tokens_per_page"]) * sparse
        pages = sum(-(-r.total_len * rpt // rows_per_page)
                    for r in self.reqs for rpt in (sparse, self.dims["Ld"]))
        self.pool = PagedKVPool(pages, rows_per_page)
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

    def check(self, operand_dtype=None):
        """(checks, failed): over every served token of the checked
        requests, the widest reference-logit gap below the best
        (``logit_gap``, as ``serve_rounds.Session.check``: a wrong token or
        cache shows here) and the mean gap (``logit_gap_mean``: a lower
        precision shows here).  Near a tie, rounding flips a sigmoid
        router's choice, which moves a whole expert's weight (about
        ``routed_scaling_factor`` / top-k, 0.4 here), so the widest gap of
        the served precision is such a token's and may lie near a lower
        precision's; the mean stays far apart.  With ``operand_dtype``, of
        the tokens the reference in that precision puts first (the
        control)."""
        dims = tuple(sorted(self.dims.items()))
        gaps = np.concatenate([
            served_gaps(self.w, self.reqs[rid].tokens, served, dims,
                        operand_dtype)
            for rid, served in self._checked()])
        failed = sum(r["failed"] for r in self.rounds)
        limits = self.run.limits
        return {"logit_gap": (float(gaps.max()), float(limits["logit_gap"])),
                "logit_gap_mean": (float(gaps.mean()),
                                   float(limits["logit_gap_mean"]))}, failed


def served_gaps(w, prompt, served, dims, operand_dtype=None) -> np.ndarray:
    """``serve_rounds.served_gap`` with the MLA reference, at each served
    token."""
    import jax.numpy as jnp
    seq, rows = serve_rounds.reference_inputs(prompt, served)
    seq, rows = jnp.asarray(seq), jnp.asarray(rows)
    ref = ref_mla.logits_at(w, seq, rows, dims=dims)
    if operand_dtype is None:
        chosen = jnp.asarray(served, jnp.int32)
    else:
        low = ref_mla.logits_at(w, seq, rows, dims=dims,
                                operand_dtype=operand_dtype)
        chosen = jnp.argmax(low, axis=-1)
    gap = ref.max(-1) - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    return np.asarray(gap)
