"""The dropless expert layer that holds a share of the experts (CPU, float32):
its shares add up to the uncut layer, nothing drops under skew, the served
path through the paged-KV engine matches a full forward, and the ``moe``
bank counts what the routing did."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.layers import moe as MOE
from repro.models import lm
from repro.runtime import telemetry
from repro.serving import ContinuousBatchingEngine, PagedKVPool, Request
from repro.serving.continuous import MOE_COUNTERS

E, K, SHARE = 16, 4, 4


def smoke(**kw):
    return dataclasses.replace(configs.smoke_config("qwen3_moe_30b_a3b"),
                               dtype=jnp.float32, n_experts=E, top_k=K, **kw)


def share(p, first, count):
    """The held experts' slice of an uncut layer's parameters."""
    return dict(p, **{k: p[k][first:first + count]
                      for k in ("w_gate", "w_up", "w_down")})


def dense_reference(cfg, p, tokens):
    """Every token through every expert, times its renormalised top-k gate
    (0 where not routed): the uncut layer, written out plainly."""
    hi = jax.lax.Precision.HIGHEST
    probs = jax.nn.softmax(jnp.matmul(tokens, p["router"], precision=hi), -1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    T = tokens.shape[0]
    gate = jnp.zeros((T, cfg.n_experts)).at[jnp.arange(T)[:, None], idx].set(gates)
    g = jnp.einsum("td,edf->tef", tokens, p["w_gate"], precision=hi)
    u = jnp.einsum("td,edf->tef", tokens, p["w_up"], precision=hi)
    out = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"], precision=hi)
    return jnp.einsum("ted,te->td", out, gate, precision=hi), idx


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_expert_shares_add_up_to_the_uncut_layer(seed):
    cfg = smoke()
    p = MOE.init_moe(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(100 + seed), (3, 7, cfg.d_model))
    ref, _ = dense_reference(cfg, p, x.reshape(-1, cfg.d_model))
    total = 0.0
    for first in range(0, E, SHARE):
        cs = dataclasses.replace(cfg, experts_held=(first, SHARE))
        y, _ = MOE.moe_apply(cs, share(p, first, SHARE), x)
        total = total + y.reshape(-1, cfg.d_model)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    whole, _ = MOE.moe_apply(cfg, p, x)
    np.testing.assert_allclose(np.asarray(whole.reshape(-1, cfg.d_model)),
                               np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("first", [0, 4])
def test_no_token_dropped_when_one_expert_gets_every_token(first):
    """Positive tokens and a router column of large positive weights put
    expert ``first`` in every token's top-k: 64 tokens on one expert, where
    the capacity buffer (1.25 k T / E + 1 = 21 slots) drops most of them."""
    cfg = smoke()
    p = MOE.init_moe(jax.random.PRNGKey(7), cfg)
    p["router"] = p["router"].at[:, first].set(5.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (64, cfg.d_model)))
    ref, idx = dense_reference(cfg, p, x)
    assert bool((idx == first).any(-1).all())
    cs = dataclasses.replace(cfg, experts_held=(first, SHARE))
    ps = share(p, first, SHARE)
    ref_share, _ = dense_reference(cs, dict(p, **{
        k: jnp.where((jnp.arange(E) >= first) & (jnp.arange(E) < first + SHARE),
                     1.0, 0.0)[:, None, None] * p[k]
        for k in ("w_gate", "w_up", "w_down")}), x)
    for c, pp, want in ((cfg, p, ref), (cs, ps, ref_share)):
        y, _, counts = MOE.moe_apply(c, pp, x[None], with_counts=True)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert int(counts[0]) == 64 * K
    # the capacity buffer the local path used to run (and the shard_map
    # paths still run) drops tokens here
    capacity = int(cfg.capacity_factor * K * 64 // E) + 1
    gates, eidx, _ = MOE._route(cfg, p["router"], x)
    buf, slot, keep, order, _ = MOE._dispatch(cfg, x, eidx, gates, capacity)
    capped = MOE._combine(cfg, MOE._expert_ffn(cfg, p, buf), slot, keep,
                          order, gates, 64, cfg.d_model)
    assert not bool(keep.all())
    assert float(jnp.abs(capped - ref).max()) > 1e-2


def test_routing_counts_match_a_count_on_the_host():
    cfg = smoke(experts_held=(4, SHARE))
    p = MOE.init_moe(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.d_model))
    _, _, counts = MOE.moe_apply(cfg, p, x, with_counts=True)
    _, idx, _ = MOE._route(cfg, p["router"], x.reshape(-1, cfg.d_model))
    idx = np.asarray(idx)
    held = (idx >= 4) & (idx < 8)
    assert counts.tolist() == [idx.size, int(held.sum()),
                               len(set(idx[held].tolist()))]


def engine_case(first=4, seed=0):
    cfg = smoke(experts_held=(first, SHARE))
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, arrival_s=0.0,
                    tokens=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new=m)
            for i, (n, m) in enumerate([(5, 4), (8, 6), (8, 3), (11, 5)])]
    return cfg, params, reqs


def test_engine_serves_the_share_as_a_full_forward_does():
    """Prefill, then decode through the paged cache: at every served step,
    ``lm.forward`` over the whole sequence at once puts the served token's
    logit within rounding of its best (float32; the two sum the same terms
    in another order, so 1e-4 of the logits' scale covers the rounding and
    no other token)."""
    cfg, params, reqs = engine_case()
    pool = PagedKVPool(64, 16 * cfg.n_layers * cfg.n_kv_heads)
    eng = ContinuousBatchingEngine(cfg, params, 24, max_batch=3,
                                   cache_dtype=jnp.float32, pool=pool)
    rep = eng.serve(reqs)
    for r in reqs:
        served = rep.tokens[r.rid]
        assert len(served) == r.max_new
        seq = np.concatenate([r.tokens, served[:-1]])[None]
        logits, _ = lm.forward(cfg, params, {"tokens": jnp.asarray(seq)})
        rows = np.asarray(logits[0, r.prompt_len - 1:])
        # greedy tokens: each is the full forward's best up to rounding
        best = rows.max(-1)
        got = rows[np.arange(len(served)), served]
        assert np.all(best - got <= 1e-4 * np.abs(rows).max()), (best - got)


def test_engine_moe_bank_counts_its_routing():
    cfg, params, reqs = engine_case(seed=1)
    pool = PagedKVPool(64, 16 * cfg.n_layers * cfg.n_kv_heads)
    eng = ContinuousBatchingEngine(cfg, params, 24, max_batch=4,
                                   cache_dtype=jnp.float32, pool=pool)
    telemetry.reset("moe")
    rep = eng.serve(reqs)
    got = telemetry.bank("moe").as_dict()
    # the host's count of the same routing: each layer's router over the
    # normed residual stream of a full forward, at every position a program
    # ran (prompt positions in prefill, served positions in decode steps)
    first, count = cfg.held_experts
    want = dict.fromkeys(MOE_COUNTERS, 0)
    decode_rows = {}
    for r in reqs:
        served = rep.tokens[r.rid]
        seq = np.concatenate([r.tokens, served[:-1]])
        for layer, idx in enumerate(_layer_routing(cfg, params, seq)):
            held = (idx >= first) & (idx < first + count)
            want["assignments"] += idx.size
            want["assignments_held"] += int(held.sum())
            # decode position j of request r ran in the step that decoded
            # token j - prompt_len + 1 of it
            for j in range(r.prompt_len, len(seq)):
                step = decode_rows.setdefault((layer, j - r.prompt_len), set())
                step.update(idx[j][held[j]].tolist())
    # all four requests arrive at once and decode in lockstep: decode step
    # t holds every request still decoding at its t-th token
    want["decode_experts_touched"] = sum(len(s) for s in decode_rows.values())
    assert got == want


def _layer_routing(cfg, params, seq):
    """Each layer's top-k expert ids over a full forward of ``seq``."""
    from repro.layers.norms import rms_norm
    from repro.layers import attention as A
    x = params["embed"]["embed"][jnp.asarray(seq)][None]
    pos = jnp.arange(len(seq))[None]
    out = []
    blocks = params["blocks"][0]
    for layer in range(cfg.n_periods):
        p = jax.tree.map(lambda a: a[layer], blocks)
        h = rms_norm(x, p["norm_mix"]["scale"], cfg.norm_eps)
        a, _ = A.attn_apply(cfg, p["attn"], h, pos, causal=True)
        x = x + a
        h = rms_norm(x, p["norm_ffn"]["scale"], cfg.norm_eps)
        _, idx, _ = MOE._route(cfg, p["ffn"]["router"], h[0])
        out.append(np.asarray(idx))
        y, _ = MOE.moe_apply(cfg, p["ffn"], h)
        x = x + y
    return out


def test_dense_programs_return_no_counts():
    cfg = dataclasses.replace(configs.smoke_config("qwen3_1p7b"),
                              dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    cache = lm.init_cache(cfg, 1, 8, jnp.float32)
    out = lm.prefill(cfg, params, {"tokens": jnp.ones((1, 4), jnp.int32)}, cache)
    assert len(out) == 2
    assert not ContinuousBatchingEngine(cfg, params, 8)._moe
