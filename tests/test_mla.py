"""Multi-head latent attention and the DeepSeek-V3-style expert layer (CPU,
float32): the absorbed decode against decompressed attention, prefill then
decode through the latent cache against the plain reference, the sigmoid
router against a hand computation, the expert shares plus the shared
experts against the uncut layer, and the leading dense layer's and the
sparse layers' latent leaves through the paged-KV pool."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights_mla
from bench.drivers import serve_rounds_mla as mla_rounds
from bench.reference import mla as ref_mla
from repro import configs
from repro.layers import mla as LA
from repro.layers import moe as MOE
from repro.models import lm
from repro.serving import ContinuousBatchingEngine, PagedKVPool, Request
from repro.serving.continuous import _leaf_metas

HI = jax.lax.Precision.HIGHEST


def smoke(**kw):
    return dataclasses.replace(configs.smoke_config("kanana-2-30b-a3b"),
                               dtype=jnp.float32, **kw)


def bench_config():
    """A tiny file of the benchmark's kanana config: layer 0 dense, 2 sparse
    layers, 16 experts of which this share holds 4-7, top-4."""
    import json
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"
            / "kanana-2-30b-a3b.json")
    cfg = json.loads(path.read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
               vocab_size=256, num_hidden_layers=3, n_routed_experts=4,
               num_experts_per_tok=4, published={"n_routed_experts": 16},
               experts_held=[4, 4])
    return cfg


def program(seed=0):
    """(weights, program config, params, reference dims) at float32."""
    cfg = bench_config()
    w = weights_mla.make(cfg, seed, "float32")
    model_cfg = dataclasses.replace(mla_rounds.program_config(cfg),
                                    dtype=jnp.float32)
    params = mla_rounds.program_params(w, model_cfg)
    return w, model_cfg, params, tuple(sorted(weights_mla.dims(cfg).items()))


@pytest.mark.parametrize("ragged", [False, True])
def test_absorbed_decode_equals_decompressed_attention(ragged):
    """One layer: decoding the last token over a latent cache filled by the
    decompressed prefill of the tokens before it gives the decompressed
    attention's output at that position (float32, highest precision: the
    same sums in another order, so 1e-5 relative)."""
    cfg = smoke()
    p = LA.init_mla(jax.random.PRNGKey(0), cfg)
    B, S, smax = 3, 9, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    with jax.default_matmul_precision("highest"):
        want, _ = LA.mla_prefill(cfg, p, x, pos)
        _, lat = LA.mla_prefill(cfg, p, x[:, :S - 1], pos[:, :S - 1])
        cache = LA.write_latent(LA.init_latent_cache(cfg, B, smax, jnp.float32),
                                lat)
        at = jnp.full((B,), S - 1) if ragged else jnp.asarray(S - 1)
        got, cache = LA.mla_decode(cfg, p, x[:, S - 1:], pos[:, S - 1:],
                                   cache, at)
        _, full = LA.mla_prefill(cfg, p, x, pos)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want[:, -1]),
                               rtol=1e-5, atol=1e-5)
    # the decode wrote its own latent where prefill would have
    np.testing.assert_allclose(np.asarray(cache["latent"][:, :S]),
                               np.asarray(full), rtol=1e-6, atol=1e-6)
    assert not np.asarray(cache["latent"][:, S:]).any()


def test_engine_prefill_then_decode_matches_the_reference():
    """The program's prefill then absorbed decode steps through the latent
    cache, against the plain reference's full forward at every position
    (float32 on both sides, highest precision: they sum the same terms in
    other orders, so 1e-4 of the logits' scale covers the rounding)."""
    w, model_cfg, params, dims = program()
    B, P, n = 2, 10, 4
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, P + n), 0,
                              model_cfg.vocab)
    with jax.default_matmul_precision("highest"):
        cache = lm.init_cache(model_cfg, B, P + n, jnp.float32)
        logits, cache, _ = lm.prefill(model_cfg, params,
                                      {"tokens": toks[:, :P]}, cache,
                                      moe_counts=True)
        got = [logits[:, 0]]
        for t in range(n - 1):
            logits, cache, _ = lm.decode_step(model_cfg, params,
                                              toks[:, P + t:P + t + 1], cache,
                                              moe_counts=True)
            got.append(logits[:, 0])
    got = np.stack([np.asarray(g) for g in got], axis=1)      # (B, n, V)
    for b in range(B):
        want = np.asarray(ref_mla.logits_at(
            w, toks[b], jnp.arange(P - 1, P - 1 + n), dims=dims))
        np.testing.assert_allclose(got[b], want, rtol=1e-4,
                                   atol=1e-4 * np.abs(want).max())


def test_engine_serves_through_the_pool_as_the_reference_forward():
    """Served through ``ContinuousBatchingEngine`` over a ``PagedKVPool``
    too small for the batch (ragged prompts; preemption evicts the leading
    layer's and the sparse layers' latent pages to host and restores them):
    every greedy token is the reference's best up to float32 rounding (1e-4
    of the logits' scale), and ``bytes_moved`` counts each movement at the
    page's bytes."""
    w, model_cfg, params, dims = program(seed=1)
    g = np.random.default_rng(4)
    reqs = [Request(rid=i, arrival_s=0.0, max_new=6,
                    tokens=g.integers(0, model_cfg.vocab, p).astype(np.int32))
            for i, p in enumerate([9, 12, 14])]
    R = 8 * model_cfg.n_periods                  # 8 tokens of the sparse leaf
    pool = PagedKVPool(9, R)
    eng = ContinuousBatchingEngine(model_cfg, params, 20, max_batch=3,
                                   cache_dtype=jnp.float32, pool=pool)
    with jax.default_matmul_precision("highest"):
        rep = eng.serve(reqs)
    assert rep.preemptions > 0 and rep.pool_stats["evictions"] > 0
    for r in reqs:
        served = rep.tokens[r.rid]
        assert len(served) == r.max_new
        seq = jnp.asarray(np.concatenate([r.tokens, served[:-1]]))
        rows = np.asarray(ref_mla.logits_at(
            w, seq, jnp.arange(r.prompt_len - 1, len(seq)), dims=dims))
        best = rows.max(-1)
        assert np.all(best - rows[np.arange(len(served)), served]
                      <= 1e-4 * np.abs(rows).max())
    width = model_cfg.kv_lora_rank + model_cfg.qk_rope_head_dim
    assert pool.stats["bytes_moved"] == pool.stats["movements"] * R * width * 4


def test_sigmoid_routing_matches_a_hand_computation():
    """Four experts, top 2: the bias lifts expert 3 over expert 1 in the
    choice, the weights are the unbiased sigmoid scores of the chosen,
    renormalised, times the routed scale."""
    cfg = dataclasses.replace(smoke(), d_model=4, n_experts=4, top_k=2,
                              routed_scale=2.5)
    logits = np.array([[2.0, 1.0, -1.0, 0.5]], np.float32)
    bias = np.array([0.0, 0.0, 0.0, 0.3], np.float32)
    gates, eidx, aux = MOE._route(cfg, jnp.eye(4), jnp.asarray(logits),
                                  jnp.asarray(bias))
    s = 1 / (1 + np.exp(-logits[0]))          # 0.881, 0.731, 0.269, 0.622
    assert sorted(np.asarray(eidx[0]).tolist()) == [0, 3]    # 0.922 > 0.731
    order = np.argsort(np.asarray(eidx[0]))
    want = np.array([s[0], s[3]]) / (s[0] + s[3]) * 2.5
    np.testing.assert_allclose(np.asarray(gates[0])[order], want, rtol=1e-6)
    assert float(aux) == 0.0


def uncut_layer(cfg, p, x):
    """Every token through every expert times its routed weight (0 where not
    chosen), plus the shared experts: the uncut layer, written out plainly
    as HF's DeepseekV3MoE computes it."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HI))
    _, idx = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
    top = jnp.take_along_axis(scores, idx, -1)
    top = top / (top.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale
    T = x.shape[0]
    gate = jnp.zeros((T, cfg.n_experts)).at[jnp.arange(T)[:, None], idx].set(top)
    g = jnp.einsum("td,edf->tef", x, p["w_gate"], precision=HI)
    u = jnp.einsum("td,edf->tef", x, p["w_up"], precision=HI)
    out = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"], precision=HI)
    return jnp.einsum("ted,te->td", out, gate, precision=HI) + shared(p, x)


def shared(p, x):
    s = p["shared"]
    h = (jax.nn.silu(jnp.matmul(x, s["w_gate"], precision=HI))
         * jnp.matmul(x, s["w_up"], precision=HI))
    return jnp.matmul(h, s["w_down"], precision=HI)


@pytest.mark.parametrize("seed", [0, 1])
def test_expert_shares_plus_the_shared_experts_add_up_to_the_uncut_layer(seed):
    """16 chips holding one expert each: the shares' held parts, with the
    shared experts (which every chip computes) counted once, add up to the
    uncut layer; the correction bias is nonzero."""
    E = 16
    cfg = smoke(n_experts=E, top_k=6)
    p = MOE.init_moe(jax.random.PRNGKey(seed), cfg)
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(50 + seed), (E,))
    x = jax.random.normal(jax.random.PRNGKey(100 + seed), (3, 7, cfg.d_model))
    flat = x.reshape(-1, cfg.d_model)
    with jax.default_matmul_precision("highest"):
        total = 0.0
        for first in range(E):
            cs = dataclasses.replace(cfg, experts_held=(first, 1))
            ps = dict(p, **{k: p[k][first:first + 1]
                            for k in ("w_gate", "w_up", "w_down")})
            y, _ = MOE.moe_apply(cs, ps, x)
            total = total + y.reshape(-1, cfg.d_model)
        total = total - (E - 1) * shared(p, flat)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(uncut_layer(cfg, p, flat)),
                               rtol=1e-4, atol=1e-4)


def test_both_latent_leaves_page_as_sequence_leaves():
    """The leading layer's latent leaf (one row a token) and the sparse
    layers' (one row a layer and token) page as sequence leaves of r + dr
    columns: their valid prefixes, growing with the decoded tokens."""
    cfg = smoke(experts_held=(0, 4))
    metas, _ = _leaf_metas(cfg, 20, jnp.float32)
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert sorted((m.kind, m.rpt, m.cols, m.rows) for m in metas) == [
        ("pos", 1, 1, 0), ("seq", 1, width, 20),
        ("seq", cfg.n_periods, width, 20 * cfg.n_periods)]
