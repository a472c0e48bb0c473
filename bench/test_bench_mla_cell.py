"""The MLA serving cell at a small size on the CPU: whole runs through the
harness, the program against the plain reference, and the cell's three
readers on a hand-built run (CPU)."""
import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generate, harness, weights_mla
from bench.drivers import serve_rounds_mla as mla_rounds
from bench.reference import mla as ref_mla

CELL = "serve-kanana-2-30b-a3b-rag"
SEED = 2**33 + 23


def config():
    """Widths cut to what the CPU runs in seconds; layer 0 dense and 2
    sparse layers, 16 experts, top-4, this share holding experts 4-7."""
    cfg = dict(harness.read_json(harness.BENCH / "configs" / "kanana-2-30b-a3b.json"))
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               qk_head_dim=24, head_dim=8, v_head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, vocab_size=512, num_hidden_layers=3,
               n_routed_experts=4, num_experts_per_tok=4,
               published={"n_routed_experts": 16}, experts_held=[4, 4])
    return cfg


def mix():
    m = dict(harness.read_json(harness.BENCH / "traffic" / "mla-rag.json"))
    m.update(requests_per_round=4, max_batch=4, prompt_len={"16": 0.5, "32": 0.5},
             answer_len={"4": 0.5, "8": 0.5}, trace_seconds=0.3)
    return m


def limits():
    """The cell's limits scaled to the small size: a logit gap scales with
    the logits' spread, 0.1 sqrt(hidden) (``bench/weights.py``)."""
    lim = dict(harness.read_json(harness.BENCH / "limits" / f"{CELL}.json"))
    shrink = (config()["hidden_size"] / 2048) ** 0.5
    return dict(lim, **{k: lim[k] * shrink for k in ("logit_gap", "logit_gap_mean")})


def run(trace=0, seconds=0.5):
    return harness.execute(CELL, SEED, seconds, trace, t_start=time.perf_counter(),
                           require_tpu=False, config=config(), mix=mix(),
                           limits=limits())


@pytest.mark.parametrize("trace", [0, 1])
def test_mla_cell_runs_correct_on_the_cpu(trace):
    r = run(trace)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert set(r["checks"]) == {"logit_gap", "logit_gap_mean"}
    w = r["window"]
    # every token a program ran, top-4, 2 sparse layers: the prompts in
    # prefill and each served token but the last in decode
    tokens = (16 + 16 + 32 + 32) + (3 + 3 + 7 + 7)
    assert w["moe_assignments"] == w["rounds"] * tokens * 4 * 2
    assert 0 < w["moe_assignments_held"] < w["moe_assignments"]
    assert 0 < w["moe_decode_experts_touched"] <= w["rounds"] * 7 * 2 * 4
    spec = harness.benchmark_spec()
    if trace:
        assert w["compiles_in_window"] == 0
        names = {m["name"] for m in harness.metrics_for(spec, CELL, "per_layer")}
        assert {"mfu.serve.mla", "decode.hbm_roofline.mla",
                "pool.bytes_per_token"} <= names
        assert not names & {"mfu.serve", "decode.hbm_roofline",
                            "mfu.serve.moe", "decode.hbm_roofline.moe"}
        # the engine, pool and scheduler readers find their spans and banks;
        # the CPU records no device ops for the other four
        device_ops = {"mfu.serve.mla", "decode.hbm_roofline.mla",
                      "device_idle.serve", "device_idle.serve.unnamed"}
        assert set(r["metrics"]) == names - device_ops
        assert r["metrics"]["pool.bytes_per_token"]["value"] > 0
    else:
        names = [m["name"] for m in harness.metrics_for(spec, CELL, "end_to_end")]
        assert list(r["metrics"]) == names == ["serve_tokens_per_s", "setup_s"]


def test_a_served_token_altered_where_it_is_produced(monkeypatch):
    from repro.models import lm
    decode = lm.decode_step

    def altered(cfg, params, tokens, cache, **kw):
        logits, cache, counts = decode(cfg, params, tokens, cache, **kw)
        worst = jnp.argmin(logits[0, -1])              # the least likely token
        return logits.at[0, -1, worst].set(1e4), cache, counts
    monkeypatch.setattr(lm, "decode_step", altered)
    r = run()
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def test_program_forward_matches_the_plain_reference():
    """The program's full forward (decompressed attention) at the held share
    against the reference, both float32 on the same weights: they sum the
    same terms in other orders, so they agree to float32 rounding."""
    from repro.models import lm
    cfg = config()
    w = weights_mla.make(cfg, SEED, "float32")
    model_cfg = dataclasses.replace(mla_rounds.program_config(cfg),
                                    dtype=jnp.float32)
    params = mla_rounds.program_params(w, model_cfg)
    toks = generate.rng(SEED, "t").integers(0, 512, 24).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = lm.forward(model_cfg, params, {"tokens": jnp.asarray(toks)[None]})
    dims = tuple(sorted(weights_mla.dims(cfg).items()))
    want = ref_mla.logits_at(w, jnp.asarray(toks), jnp.arange(24), dims=dims)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-4 * float(jnp.abs(want).max()))


def fake_run(banks):
    """One round of the cell's own traffic at its published widths, with a
    10 s window whose 127 decode steps took 2 s of device time (at least
    7 ms each: a step reads over 5.9 GB at 819 GB/s)."""
    cfg = harness.read_json(harness.BENCH / "configs" / "kanana-2-30b-a3b.json")
    m = harness.read_json(harness.BENCH / "traffic" / "mla-rag.json")
    round_ = generate.serve_round(m, 128256, 1)
    prompts = [len(t) for t, _ in round_]
    rows, steps = [], 0
    for t in range(max(a for _, a in round_) - 1):       # lockstep decode
        live = [p + t for p, (_, a) in zip(prompts, round_) if t < a - 1]
        rows += live
        steps += 1
    trace = types.SimpleNamespace(
        window_s=10.0, span_busy_s=lambda name: 2.0 if name == "bench.decode" else 0.0)
    generated = sum(a for _, a in round_)
    return types.SimpleNamespace(
        config=cfg, peaks=harness.peaks("TPU v5 lite"), trace_data=trace,
        facts={"prefill_lens": prompts, "decode_rows": rows, "decode_steps": steps,
               "generated_tokens": generated},
        banks=banks)


@pytest.mark.parametrize("name", ["mfu.serve.mla", "decode.hbm_roofline.mla"])
def test_mla_readers_on_a_hand_built_run(name):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
    run_ = fake_run({"moe": {"assignments": 24_000 * 6 * 47,
                             "assignments_held": 9_000 * 47,
                             "decode_experts_touched": 127 * 47 * 5}})
    value = reader.read(run_)
    assert 0 < value <= 100
    for banks in ({}, {"moe": {}}):
        assert reader.read(fake_run(banks)) is None


def test_pool_bytes_reader_on_a_hand_built_run():
    """Bytes over generated tokens, summed over the pools that count them;
    None where no pool counts bytes (a program without the counter)."""
    reader = harness.load_module(harness.BENCH / "metrics" / "pool.bytes_per_token.py")
    run_ = fake_run({"pool:kvpool": {"movements": 10, "bytes_moved": 55_296 * 1_000},
                     "moe": {}})
    tokens = run_.facts["generated_tokens"]
    assert reader.read(run_) == pytest.approx(55_296 * 1_000 / tokens)
    assert reader.read(fake_run({"pool:kvpool": {"movements": 10}})) is None
    assert reader.read(fake_run({})) is None
