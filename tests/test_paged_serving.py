"""Paged-KV pool + continuous batching: parity, preemption, in-plane-ness.

The contracts under test (ISSUE 6 acceptance):
* the continuous-batching engine on a fixed request set produces
  bit-identical per-request tokens to ``ServingEngine.generate``;
* an evict-to-host -> re-admit page roundtrip is value-preserving,
  including the Compress wire codec;
* every page movement appears in a ``capture()`` trace — zero out-of-plane
  KV transfers;
* continuous batching sustains strictly higher tokens/s than the static
  gang at two offered loads on two fabrics.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.descriptor import page_descriptor, page_layout
from repro.models import lm
from repro.runtime import DistributedScheduler, Topology
from repro.runtime.trace import capture
from repro.serving import (ContinuousBatchingEngine, PagedKVPool,
                           ServingEngine, StaticBatchEngine, depaginate,
                           paginate, poisson_stream, trace_stream,
                           uniform_stream)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(configs.smoke_config("qwen3_1p7b"),
                              dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reference_tokens(cfg, params, reqs, max_len, n_steps):
    toks = jnp.asarray(np.stack([r.tokens for r in reqs]), jnp.int32)
    eng = ServingEngine(cfg, params, max_len=max_len,
                        cache_dtype=jnp.float32)
    return np.asarray(eng.generate({"tokens": toks}, n_steps))


# ---------------------------------------------------------------------------
# page-pool mechanics
# ---------------------------------------------------------------------------
def test_page_layout_picks_tiled_layout_when_divisible():
    assert page_layout(32, 16, "float32").name == "MNM8N8"
    assert page_layout(32, 128, "float32").name == "MNM8N128"
    assert page_layout(31, 7, "float32").name == "MN"      # nothing divides


def test_paginate_depaginate_roundtrip():
    rng = np.random.default_rng(0)
    mat = jnp.asarray(rng.standard_normal((37, 16)), jnp.float32)
    pages = paginate(mat, 32)
    assert len(pages) == 2 and all(p.shape == (32, 16) for p in pages)
    np.testing.assert_array_equal(np.asarray(depaginate(pages, 37)),
                                  np.asarray(mat))
    # the zero-pad really is zero (beyond-valid rows must match init_cache)
    assert not np.asarray(pages[-1])[5:].any()


def test_evict_restore_roundtrip_value_preserving_with_compress():
    """Page -> host (Compress wire) -> page is bit-exact, and the pool's
    slot bookkeeping survives the trip."""
    pool = PagedKVPool(4, 32, compress_block=8)
    sched = DistributedScheduler(Topology.host_device(2), name="t")
    pool.bind(sched)
    rng = np.random.default_rng(1)
    mat = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    # make some 8-row blocks all-zero so Compress actually skips blocks
    mat = mat.at[8:16].set(0.0)
    pid = pool.alloc(16, "float32")
    pool.store(pid, mat)
    sched.flush(); pool.commit()
    slot0 = pool.page(pid).slot
    pool.evict(pid)
    sched.flush(); pool.commit()
    assert pool.page(pid).location == "host"
    assert pool.free_pages == 4
    pool.restore(pid)
    sched.flush(); pool.commit()
    assert pool.page(pid).location == "dev"
    assert pool.page(pid).slot == slot0
    back = pool.load(pid)
    sched.flush()
    np.testing.assert_array_equal(np.asarray(back.result()), np.asarray(mat))


def test_pool_defrag_compacts_and_preserves_values():
    pool = PagedKVPool(4, 32)
    sched = DistributedScheduler(Topology.host_device(1), name="t")
    pool.bind(sched)
    rng = np.random.default_rng(2)
    mats, pids = [], []
    for i in range(3):
        m = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
        pid = pool.alloc(8, "float32")
        pool.store(pid, m)
        mats.append(m); pids.append(pid)
    sched.flush(); pool.commit()
    pool.free(pids[0])                       # hole at slot 0
    assert pool.fragmentation() == 1
    assert pool.defrag() == 1
    sched.flush(); pool.commit()
    assert pool.fragmentation() == 0
    assert {pool.page(p).slot for p in pids[1:]} == {0, 1}
    for pid, m in zip(pids[1:], mats[1:]):
        f = pool.load(pid)
        sched.flush()
        np.testing.assert_array_equal(np.asarray(f.result()), np.asarray(m))


# ---------------------------------------------------------------------------
# decode parity with the fixed-batch engine
# ---------------------------------------------------------------------------
def test_continuous_matches_fixed_batch_bitwise(model):
    """Fixed request set, simultaneous arrival: bit-identical per-request
    tokens to ``ServingEngine.generate`` (same compiled programs)."""
    cfg, params = model
    reqs = uniform_stream(cfg, 2, 0.0, prompt_len=4, max_new=3)
    ref = _reference_tokens(cfg, params, reqs, 24, 3)
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=4,
                                   cache_dtype=jnp.float32)
    rep = eng.serve(reqs)
    assert rep.n_requests == 2
    for r in reqs:
        np.testing.assert_array_equal(rep.tokens[r.rid], ref[r.rid])


def test_continuous_parity_survives_preemption(model):
    """A pool too small for the batch forces evict-to-host -> re-admit mid
    generation; tokens must still match the fixed-batch reference exactly
    (the roundtrip is value-preserving end to end)."""
    cfg, params = model
    reqs = uniform_stream(cfg, 3, 0.0, prompt_len=8, max_new=4)
    ref = _reference_tokens(cfg, params, reqs, 24, 4)
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=3,
                                   cache_dtype=jnp.float32,
                                   pool=PagedKVPool(7, 32))
    rep = eng.serve(reqs)
    assert rep.preemptions > 0, "pool of 7 pages must force preemption"
    assert rep.pool_stats["evictions"] > 0
    assert rep.pool_stats["restores"] == rep.pool_stats["evictions"]
    for r in reqs:
        np.testing.assert_array_equal(rep.tokens[r.rid], ref[r.rid])


def test_serve_leaves_no_device_buffer_in_reference_cycles(model):
    """Each step's scheduler, its tasks and their futures form reference
    cycles; the engine drops the tasks' page buffers after every step, so
    a collection after ``serve()`` finds none (with preemption too)."""
    import gc
    cfg, params = model
    reqs = uniform_stream(cfg, 3, 0.0, prompt_len=8, max_new=4)
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=3,
                                   cache_dtype=jnp.float32,
                                   pool=PagedKVPool(7, 32))
    eng.serve(reqs)                                    # compiles
    gc.collect()
    gc.disable()
    try:
        eng.serve(reqs)
        gc.set_debug(gc.DEBUG_SAVEALL)
        gc.collect()
        leaked = [o for o in gc.garbage if isinstance(o, jax.Array)]
    finally:
        gc.set_debug(0)
        gc.garbage.clear()
        gc.enable()
    assert eng.preemptions > 0 and leaked == []


def test_admission_counts_pages_of_same_step_admissions(model):
    """Each prompt alone fits the pool but three together do not: admission
    must charge the pages of requests it already admitted this step (their
    pages are allocated only after prefill), or prefill runs the pool out."""
    cfg, params = model
    reqs = uniform_stream(cfg, 3, 0.0, prompt_len=8, max_new=4)
    ref = _reference_tokens(cfg, params, reqs, 24, 4)
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=3,
                                   cache_dtype=jnp.float32,
                                   pool=PagedKVPool(5, 32))
    rep = eng.serve(reqs)
    assert rep.n_requests == 3
    assert rep.pool_stats["peak_used"] <= 5
    for r in reqs:
        np.testing.assert_array_equal(rep.tokens[r.rid], ref[r.rid])


def test_ragged_batch_tokens_independent_of_composition(model):
    """Staggered arrivals make a ragged (vector-position) batch; each
    request's tokens must equal the ones it gets served alone (batch
    composition is invisible to the sampled tokens)."""
    cfg, params = model
    stream = trace_stream(cfg, [(0.0, 4, 4), (10e-6, 8, 3), (30e-6, 4, 5)],
                          seed=3)
    rep = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=4,
                                   cache_dtype=jnp.float32).serve(stream)
    assert rep.n_requests == 3
    for r in stream:
        solo = ContinuousBatchingEngine(
            cfg, params, max_len=24, max_batch=1,
            cache_dtype=jnp.float32).serve([r])
        np.testing.assert_array_equal(solo.tokens[r.rid], rep.tokens[r.rid])


def test_vector_pos_decode_matches_scalar(model):
    """The ragged-batch decode path (per-request position vector) is
    bit-identical to the scalar path when all positions agree."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab)
    cache = lm.init_cache(cfg, 2, 24, dtype=jnp.float32)
    logits, cache = lm.prefill(cfg, params, {"tokens": toks}, cache)
    nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    l_s, c_s = lm.decode_step(cfg, params, nxt, cache)
    cache_v = dict(cache, pos=jnp.full((2,), cache["pos"], jnp.int32))
    l_v, c_v = lm.decode_step(cfg, params, nxt, cache_v)
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_v))
    assert c_v["pos"].shape == (2,)
    np.testing.assert_array_equal(np.asarray(c_v["pos"]),
                                  np.full((2,), int(c_s["pos"])))


# ---------------------------------------------------------------------------
# in-plane-ness: zero out-of-plane KV transfers
# ---------------------------------------------------------------------------
def test_every_page_movement_is_captured(model):
    """The pool's movement counter equals the count of ``page:``-labelled
    scheduler events in the capture — no KV byte moves outside the plane."""
    cfg, params = model
    reqs = uniform_stream(cfg, 3, 5e-6, prompt_len=4, max_new=3)
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=2,
                                   cache_dtype=jnp.float32,
                                   pool=PagedKVPool(8, 32))
    with capture(name="serve") as tr:
        rep = eng.serve(reqs)
    page_events = tr.labelled("page:")
    assert len(page_events) == rep.pool_stats["movements"]
    assert rep.pool_stats["movements"] > 0
    # all page traffic is scheduler-routed (link-pinned), none ad hoc
    assert all(e.link is not None for e in page_events)
    # per-op ledger agrees with the pool's own counters
    by_op = {}
    for e in page_events:
        op = e.label.split(":")[2]
        by_op[op] = by_op.get(op, 0) + 1
    # prefill stores are labelled "store", decode-step stores "decode"
    assert (by_op.get("store", 0) + by_op.get("decode", 0)
            == rep.pool_stats["stores"])
    assert by_op.get("load", 0) == rep.pool_stats["loads"]
    assert by_op.get("evict", 0) == rep.pool_stats["evictions"]
    assert by_op.get("restore", 0) == rep.pool_stats["restores"]


# ---------------------------------------------------------------------------
# continuous beats static under load (two loads x two fabrics)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fabric", ["host_device1", "host_device2"])
def test_continuous_beats_static_under_load(model, fabric):
    cfg, params = model
    topo = (Topology.host_device(1) if fabric == "host_device1"
            else Topology.host_device(2))
    for rate in (5e4, 1.5e5):
        stream = poisson_stream(cfg, 10, rate, prompt_lens=(4, 8),
                                max_new=(2, 6), seed=1)
        rc = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=4,
                                      cache_dtype=jnp.float32,
                                      topology=topo).serve(list(stream))
        rs = StaticBatchEngine(cfg, params, max_len=24, max_batch=4,
                               cache_dtype=jnp.float32,
                               topology=topo).serve(list(stream))
        assert rc.n_requests == rs.n_requests == 10
        assert rc.total_tokens == rs.total_tokens   # same useful work
        assert rc.tokens_per_s > rs.tokens_per_s, (
            f"{fabric} rps{rate}: continuous {rc.tokens_per_s:.0f} <= "
            f"static {rs.tokens_per_s:.0f}")


# ---------------------------------------------------------------------------
# satellite: explicit serving topology
# ---------------------------------------------------------------------------
def test_serving_engine_topology_is_explicit(model):
    cfg, params = model
    eng = ServingEngine(cfg, params, max_len=16, cache_dtype=jnp.float32)
    assert eng.topology is not None
    assert eng.topology.link_names == Topology.host_device(2).link_names
    ring = Topology.ring(4)
    eng2 = ServingEngine(cfg, params, max_len=16, cache_dtype=jnp.float32,
                         topology=ring)
    assert eng2.topology is ring
    assert eng2._new_scheduler().topology is ring
