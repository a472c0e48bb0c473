"""Whole runs of each cell at a small size on the CPU, and runs with the
timed path broken underneath, which must come out not correct."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, smoke

SEED = 2**33 + 11


def run(cell, trace=0, seconds=0.5):
    return harness.execute(cell, SEED, seconds, trace, t_start=time.perf_counter(),
                           **smoke.kw(cell))


@pytest.mark.parametrize("cell", [smoke.SERVE_CELL, smoke.RELAYOUT_CELL])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct_on_the_cpu(cell, trace):
    r = run(cell, trace)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    spec = harness.benchmark_spec()
    if trace:
        assert "busy_s" in r["device"] and "breakdown" in r
        names = {m["name"] for m in harness.metrics_for(spec, cell, "per_layer")}
        assert set(r["metrics"]) <= names
        assert r["window"]["compiles_in_window"] == 0
    else:
        names = [m["name"] for m in harness.metrics_for(spec, cell, "end_to_end")]
        assert list(r["metrics"]) == names
        assert all(m["value"] > 0 for m in r["metrics"].values())


def test_serve_counts_are_fixed_by_the_round():
    r = run(smoke.SERVE_CELL, trace=1)
    m = r["metrics"]
    w = r["window"]
    assert m["engine.tokens_per_step"]["value"] == pytest.approx(
        w["generated_tokens"] / w["engine_steps"])
    assert m["pool.page_ops_per_token"]["value"] > 0
    assert m["sched.compiles_in_window"]["value"] == 0
    assert w["preemptions"] == 0
    assert w["generated_tokens"] == w["rounds"] * (4 + 4 + 8 + 8)


def test_a_served_token_altered_where_it_is_produced(monkeypatch):
    from repro.models import lm
    decode = lm.decode_step

    def altered(cfg, params, tokens, cache, **kw):
        logits, cache = decode(cfg, params, tokens, cache, **kw)
        worst = jnp.argmin(logits[0, -1])              # the least likely token
        return logits.at[0, -1, worst].set(1e4), cache
    monkeypatch.setattr(lm, "decode_step", altered)
    r = run(smoke.SERVE_CELL)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def test_half_of_the_requests_left_out(monkeypatch):
    from repro.serving import continuous
    serve = continuous.ContinuousBatchingEngine.serve
    monkeypatch.setattr(continuous.ContinuousBatchingEngine, "serve",
                        lambda self, reqs, **kw: serve(self, reqs[::2], **kw))
    r = run(smoke.SERVE_CELL)
    assert not r["correct"] and r["failed"] == r["attempted"] // 2


def test_a_relayout_answer_altered_where_it_is_produced(monkeypatch):
    from repro.serving import transfer
    load = transfer.kv_load_transposed
    monkeypatch.setattr(transfer, "kv_load_transposed",
                        lambda tiled, **kw: load(tiled, **kw).at[..., 0, 0].mul(1.05))
    r = run(smoke.RELAYOUT_CELL)
    assert not r["correct"] and r["failed"] > 0


def test_half_of_a_relayout_left_out(monkeypatch):
    from repro.serving import transfer
    load = transfer.kv_load_transposed
    monkeypatch.setattr(transfer, "kv_load_transposed",
                        lambda tiled, **kw: load(tiled, **kw)[..., ::2])
    r = run(smoke.RELAYOUT_CELL)
    assert not r["correct"] and r["failed"] > 0
