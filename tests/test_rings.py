"""Ring-buffer descriptor submission: guard-bit pointers, doorbell pricing,
credit-based backpressure, per-tenant fairness, and the completion queue
(DESIGN.md §12).

Acceptance properties (ISSUE 8):
  (a) the ring scheduler stays bit-identical to serial ``xdma.transfer``
      dispatch at every depth, including depth-2 rings under blocking
      backpressure and forced serving preemption (no deadlock, ever);
  (b) per-tenant rings under 10x adversarial overload keep the starved
      tenant within 25% of its fair bandwidth share while a single shared
      ring demonstrably does not;
  (c) the incremental makespan from completion-queue timestamps is
      bit-equal to the full event-driven replay once the rings drain;
  (d) ``XDMAFuture.result()`` honors its contract: it drains only until its
      own task is done, leaving later independent tasks pending.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as C
from repro.core import xdma
from repro.runtime import (DistributedScheduler, Topology, capture, simulate,
                           telemetry)
from repro.runtime.ring import (DEFAULT_RING_DEPTH, Completion,
                                DescriptorRing, WouldBlock)


def rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       dtype)


# -- the ring itself ----------------------------------------------------------
def test_ring_guard_bit_pointers_full_empty_and_wraparound():
    r = DescriptorRing("link0", 3)
    assert r.is_empty and not r.is_full and r.credits == 3 and len(r) == 0
    # drive the cursors several times around the 2*depth space: the guard
    # bit must keep distinguishing full from empty across every wrap
    tid = 0
    for _ in range(5):                    # 5 laps x 3 slots > 2 * depth
        for _ in range(3):
            r.post(tid)
            tid += 1
        assert r.is_full and r.credits == 0 and not r.is_empty
        with pytest.raises(WouldBlock):
            r.post(tid)
        popped = [r.pop() for _ in range(3)]
        assert popped == [tid - 3, tid - 2, tid - 1]   # FIFO across the wrap
        assert r.is_empty and r.credits == 3
    with pytest.raises(IndexError):
        r.pop()
    # partial fill: occupancy/credits stay consistent mid-lap
    r.post(99)
    assert r.head() == 99 and r.occupancy == 1 and r.credits == 2
    with pytest.raises(ValueError):
        DescriptorRing("bad", 0)


def test_scheduler_validates_backpressure_policy():
    with pytest.raises(ValueError):
        DistributedScheduler(Topology.parallel(1), backpressure="spin")


# -- satellite: result() partial drain ----------------------------------------
def test_future_result_drains_only_its_own_task():
    sched = DistributedScheduler(Topology.parallel(1))
    x = rand((64, 128))
    desc = C.describe("MN", "MNM8N128")
    f1 = sched.submit(x, desc, link="link0")
    f2 = sched.submit(x, desc, link="link0")     # later, independent task
    got = f1.result()
    assert f1.done() and not f2.done()           # the documented contract
    assert sched.pending == 1
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(xdma.transfer(x, desc)))
    sched.flush()
    assert f2.done() and sched.pending == 0


# -- backpressure: blocking policy ---------------------------------------------
def test_depth2_blocking_ring_is_bit_identical_and_never_deadlocks():
    topo = Topology.parallel(2)
    sched = DistributedScheduler(topo, ring_depth=2)
    x = rand((256, 512))
    d_store = C.describe("MN", "MNM8N128", C.RMSNormPlugin())
    d_load = C.describe("MNM8N128", "MN", C.Transpose())
    # 4 chained roundtrips per link: 16 posts through depth-2 rings — every
    # third post blocks until a completion frees a credit
    futs = []
    for link in ("link0", "link1"):
        for _ in range(4):
            f1 = sched.submit(x, d_store, link=link)
            f2 = sched.submit(f1, d_load, link=link)
            futs.append(f2)
    sched.flush()
    ref = xdma.transfer(xdma.transfer(x, d_store), d_load)
    for f in futs:
        np.testing.assert_array_equal(np.asarray(f.result()), np.asarray(ref))
    assert sched.pending == 0
    assert len(sched.completions) == 16


def test_blocking_submit_counts_ring_full_events():
    telemetry.reset("rings")
    sched = DistributedScheduler(Topology.parallel(1), ring_depth=2)
    x = rand((64, 128))
    desc = C.describe("MN", "MN")
    for _ in range(5):
        sched.submit(x, desc, link="link0")
    bank = telemetry.bank("rings")
    assert bank.get("full:link0") == 3           # posts 3, 4, 5 found it full
    assert bank.get("doorbells:link0") == 5
    assert bank.get("credits_hw:link0") == 2     # never exceeds the depth
    sched.flush()


# -- backpressure: error policy --------------------------------------------------
def test_error_policy_raises_wouldblock_then_drain_and_repost():
    sched = DistributedScheduler(Topology.parallel(1), ring_depth=2,
                                 backpressure="error")
    x = rand((64, 128))
    desc = C.describe("MN", "MNM8N128")
    f1 = sched.submit(x, desc, link="link0")
    f2 = sched.submit(x, desc, link="link0")
    with pytest.raises(WouldBlock) as ei:
        sched.submit(x, desc, link="link0")
    assert ei.value.resource == "link0" and ei.value.depth == 2
    assert sched.pending == 2                    # the rejected post left no task
    sched.step()                                 # one completion -> one credit
    f3 = sched.submit(x, desc, link="link0")     # repost lands
    sched.flush()
    ref = xdma.transfer(x, desc)
    for f in (f1, f2, f3):
        np.testing.assert_array_equal(np.asarray(f.result()), np.asarray(ref))


# -- doorbell pricing -----------------------------------------------------------
def test_doorbell_csr_writes_priced_separately_from_transfer():
    x = rand((256, 512))
    desc = C.describe("MN", "MNM8N128")

    def makespan_with(csr_cost):
        topo = Topology("t")
        topo.add_link("A", "B", name="link0", csr_write_cost=csr_cost)
        sched = DistributedScheduler(topo)
        for _ in range(4):
            sched.submit(x, desc, link="link0")
        sched.flush()
        return sched.report().makespan

    free = makespan_with(0.0)
    priced = makespan_with(20e-9)
    # config posting is additive and per-descriptor: exactly 4 CSR writes
    assert priced == pytest.approx(free + 4 * 20e-9, abs=1e-15)
    # and it is separate: trace replays price pure data movement (csr=0)
    with capture() as tr:
        sched = DistributedScheduler(Topology.parallel(1))
        for _ in range(4):
            sched.submit(x, desc, link="link0")
        sched.flush()
    assert all(t.csr_writes == 1 for t in sched.sim_tasks())
    rep = tr.replay(Topology.parallel(1))
    assert rep.makespan == pytest.approx(free, rel=1e-12)


# -- per-tenant fairness ----------------------------------------------------------
def _light_share(per_tenant):
    topo = Topology.parallel(1)
    sched = DistributedScheduler(topo)
    x = jnp.zeros((512, 512), jnp.float32)
    desc = C.describe("MN", "MN")
    heavy = "heavy" if per_tenant else ""
    light = "light" if per_tenant else ""
    futs = []
    for _ in range(40):                          # the adversary posts 10x
        sched.submit(x, desc, link="link0", tenant=heavy)
    for _ in range(4):
        futs.append(sched.submit(x, desc, link="link0", tenant=light))
    sched.flush()
    rep = sched.report()
    light_end = max(rep.span_of(f.task_id).end for f in futs)
    light_bytes = sum(sched._tasks[f.task_id].nbytes for f in futs)
    return light_bytes / (light_end * topo.link("link0").bandwidth)


def test_per_tenant_rings_bound_starvation_under_10x_overload():
    fair = 0.5                                   # two tenants, one link
    tenant = _light_share(per_tenant=True)
    shared = _light_share(per_tenant=False)
    assert tenant >= 0.75 * fair                 # within 25% of fair share
    assert shared < 0.75 * fair                  # the shared ring starves
    assert tenant / shared > 3.0


def test_tenant_dispatch_counters_track_shares():
    telemetry.reset("rings")
    sched = DistributedScheduler(Topology.parallel(1))
    x = rand((64, 128))
    desc = C.describe("MN", "MN")
    for _ in range(6):
        sched.submit(x, desc, link="link0", tenant="a")
    for _ in range(2):
        sched.submit(x, desc, link="link0", tenant="b")
    sched.flush()
    bank = telemetry.bank("rings")
    assert bank.get("tenant_dispatch:a") == 6
    assert bank.get("tenant_dispatch:b") == 2
    # arbitration interleaved them: b's last dispatch beat a's 6th
    order = [sched._tasks[tid].tenant for tid in sched._dispatched["link0"]]
    assert order == ["a", "b", "a", "b", "a", "a", "a", "a"]


def test_single_tenant_dispatch_order_is_submission_order():
    sched = DistributedScheduler(Topology.parallel(2))
    x = rand((64, 128))
    desc = C.describe("MN", "MNM8N128")
    futs = [sched.submit(x, desc) for _ in range(6)]   # round-robin routed
    sched.flush()
    assert [t.id for t in sched.sim_tasks()] == [f.task_id for f in futs]


# -- incremental makespan ----------------------------------------------------------
def test_incremental_makespan_bit_equal_to_replay():
    topo = Topology.host_device(2)
    sched = DistributedScheduler(topo)
    x = rand((256, 512))
    store = C.describe("MN", "MNM8N128", C.RMSNormPlugin())
    load = C.describe("MNM8N128", "MN", C.Transpose())
    futs = []
    for link in ("h2d0", "h2d1"):
        f1 = sched.submit(x, store, link=link)
        f2 = sched.submit(f1, load, link=link.replace("h2d", "d2h"))
        futs.append(f2)
    cf = sched.submit_compute(lambda a, b: a + b, futs[0], futs[1],
                              cost_s=3e-6)
    sched.submit(cf, store, link="h2d0", deps=(cf,))
    sched.flush()
    assert sched.makespan() == sched.report().makespan   # bit-equal
    # and the completion queue carries the same spans the replay computes
    rep = sched.report()
    for c in sched.completions:
        span = rep.span_of(c.task_id)
        assert (span.start, span.end) == (c.start_s, c.end_s)


def test_makespan_falls_back_to_replay_while_pending():
    sched = DistributedScheduler(Topology.parallel(1))
    x = rand((64, 128))
    desc = C.describe("MN", "MN")
    f1 = sched.submit(x, desc, link="link0")
    sched.submit(f1, desc, link="link0")
    f1.result()                                   # partial drain: 1 pending
    assert sched.pending == 1
    # mid-flight the incremental sum is a prefix, so makespan() must take
    # the full-replay path (which also prices the still-queued tail)
    assert sched.makespan() == sched.report().makespan
    sched.flush()
    assert sched.makespan() == sched.report().makespan


# -- trace integration -------------------------------------------------------------
def test_trace_events_carry_ring_occupancy():
    with capture() as tr:
        sched = DistributedScheduler(Topology.parallel(1), ring_depth=4)
        x = rand((64, 128))
        desc = C.describe("MN", "MN")
        sched.submit(x, desc, link="link0")
        sched.submit(x, desc, link="link0")
        sched.submit(x, desc, link="link0")
        sched.flush()
    occ = [e.ring_occupancy for e in tr.xdma_events()]
    assert occ == [1, 2, 3]                       # fill level per doorbell
    # non-scheduler events keep None
    with capture() as tr2:
        xdma.transfer(rand((64, 128)), C.describe("MN", "MN"))
    assert [e.ring_occupancy for e in tr2.xdma_events()] == [None]


# -- XDMAQueue through the rings ---------------------------------------------------
def test_queue_submit_to_matches_run():
    q = C.XDMAQueue([C.describe("MN", "MNM8N128", C.RMSNormPlugin()),
                     C.describe("MNM8N128", "MN", C.Transpose())],
                    name="kv_roundtrip")
    x = rand((256, 512))
    sched = DistributedScheduler(Topology.parallel(2))
    fut = q.submit_to(sched, x)                   # round-robin routes task 0,
    sched.flush()                                 # chain pinned to its link
    np.testing.assert_array_equal(np.asarray(fut.result()),
                                  np.asarray(q.run(x)))
    resources = {t.resource for t in sched.sim_tasks()}
    assert len(resources) == 1                    # the whole chain, one link
    with pytest.raises(ValueError):
        C.XDMAQueue(name="empty").submit_to(sched, x)


def test_queue_submit_to_depth2_backpressure_parity():
    q = C.XDMAQueue([C.describe("MN", "MNM8N128")] + [
        C.describe("MNM8N128", "MNM8N128") for _ in range(4)],
        name="deep_chain")
    x = rand((64, 128))
    sched = DistributedScheduler(Topology.parallel(1), ring_depth=2)
    fut = q.submit_to(sched, x, link="link0")     # 5 posts, depth 2: blocks
    np.testing.assert_array_equal(np.asarray(fut.result()),
                                  np.asarray(q.run(x)))


# -- serving under ring pressure -----------------------------------------------------
def test_depth2_rings_survive_forced_preemption_with_token_parity():
    import dataclasses

    import jax

    from repro import configs
    from repro.models import lm
    from repro.serving import (ContinuousBatchingEngine, PagedKVPool,
                               uniform_stream)

    cfg = dataclasses.replace(configs.smoke_config("qwen3_1p7b"),
                              dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = uniform_stream(cfg, 3, 0.0, prompt_len=8, max_new=4)

    def serve(ring_depth, backpressure):
        return ContinuousBatchingEngine(
            cfg, params, max_len=24, max_batch=3, cache_dtype=jnp.float32,
            pool=PagedKVPool(7, 32),              # tight: forces preemption
            ring_depth=ring_depth, backpressure=backpressure).serve(reqs)

    ref = serve(None, "block")                    # default-depth reference
    for policy in ("block", "error"):             # paged._submit handles both
        got = serve(2, policy)
        assert got.preemptions > 0                # the pressure was real
        for r in reqs:
            np.testing.assert_array_equal(got.tokens[r.rid],
                                          ref.tokens[r.rid])


# -- grouped flush: logical rounds, grouped programs ------------------------------
def _page():
    """One at-rest (16, 128) bfloat16 page."""
    from repro.core.descriptor import page_descriptor
    return xdma.transfer(rand((16, 128), 3, jnp.bfloat16),
                         page_descriptor(16, 128, "bfloat16"))


def _page_loads(sched, xs):
    """200 loads of one page descriptor over the two d2h links."""
    from repro.core.descriptor import page_descriptor
    desc = page_descriptor(16, 128, "bfloat16", direction="load")
    return [sched.submit(xs[0], desc, link=("d2h0", "d2h1")[i % 2])
            for i in range(200)]


_MIXED = (C.describe("MN", "MNM8N128"), C.describe("MN", "MN"),
          C.describe("MN", "MNM8N128", C.RMSNormPlugin()),
          C.describe("MN", "MN", C.Scale(2.0)))


def _mixed(sched, xs):
    """Mixed descriptors and shapes, spread over every link."""
    links = ("h2d0", "d2h0", "h2d1", "d2h1")
    return [sched.submit(xs[(i // 4) % 2], _MIXED[i % 4],
                         link=links[(i * 7) % 4]) for i in range(40)]


def _cross_link_deps(sched, xs):
    """Chains whose data and ordering-only dependencies cross links."""
    store = C.describe("MN", "MNM8N128")
    load = C.describe("MNM8N128", "MN", C.Transpose())
    futs = []
    for i in range(6):
        f1 = sched.submit(xs[0], store, link=("h2d0", "h2d1")[i % 2])
        f2 = sched.submit(f1, load, link=("d2h1", "d2h0")[i % 2])
        f3 = sched.submit(xs[0], store, link="h2d1", deps=(f2,))
        futs += [f1, f2, f3, sched.submit(xs[0], store, link="h2d0")]
    return futs


def _compute_mid(sched, xs):
    """A compute task in the middle of a drain, fed by deferred tasks and
    feeding later ones."""
    desc = C.describe("MN", "MN", C.Scale(2.0))
    first = [sched.submit(xs[0], desc, link=("h2d0", "h2d1")[i % 2])
             for i in range(8)]
    cf = sched.submit_compute(lambda a, b: a + b, first[0], first[5],
                              cost_s=3e-6)
    after = [sched.submit(cf, desc, link="h2d0")]
    after += [sched.submit(xs[0], desc, link="d2h0", deps=(cf,))
              for _ in range(5)]
    return first + [cf] + after


def _tenants(sched, xs):
    """Several tenants on one link, round-robin arbitrated."""
    desc = C.describe("MN", "MNM8N128")
    futs = [sched.submit(xs[0], desc, link="h2d0", tenant="bulk")
            for _ in range(12)]
    futs += [sched.submit(xs[1], desc, link="h2d0", tenant=tn)
             for tn in ("a", "b") for _ in range(3)]
    futs += [sched.submit(xs[0], desc, link="h2d1", tenant="a")
             for _ in range(4)]
    return futs


def _drain(schedule, drain, under_jit):
    """Run ``schedule`` on a fresh scheduler drained by ``drain``; returns
    the values, completions, makespans, rounds and counter deltas."""
    import jax

    telemetry.reset("rings")                 # it keeps high-water marks
    links, rings = telemetry.bank("links"), telemetry.bank("rings")
    before = links.as_dict()
    box = []

    def body(*xs):
        sched = DistributedScheduler(Topology.host_device(2))
        futs = schedule(sched, xs)
        drain(sched)
        box.append((sched, futs))
        return [f.result() for f in futs]

    xs = (rand((64, 128), 1), rand((128, 256), 2).astype(jnp.bfloat16))
    if schedule is _page_loads:
        xs = (_page(),)
    values = jax.jit(body)(*xs) if under_jit else body(*xs)
    sched, futs = box[0]
    return {
        "values": [np.asarray(v) for v in values],
        "completions": [(c.task_id, c.resource, c.tenant, c.round, c.start_s,
                         c.end_s) for c in sched.completions],
        "makespan": (sched.makespan(), sched.report().makespan),
        "rounds": [sched._tasks[f.task_id].round for f in futs],
        "links": {k: v - before.get(k, 0) for k, v in links.as_dict().items()
                  if v != before.get(k, 0)},
        "rings": rings.as_dict(),
    }


def _steps(sched):
    while sched.step():
        pass


@pytest.mark.parametrize("schedule,under_jit", [
    (_page_loads, False), (_mixed, False), (_cross_link_deps, False),
    (_compute_mid, False), (_tenants, False), (_mixed, True)],
    ids=["one_page_descriptor", "mixed", "cross_link_deps", "compute_mid",
         "tenants", "tracers_under_jit"])
def test_grouped_flush_matches_a_drain_by_steps(schedule, under_jit):
    """``flush()`` plans the rounds repeated ``step()`` runs and executes
    them as grouped programs: values bit-identical, and the same
    completions (task, resource, tenant, round, span), makespan, replay and
    ``links``/``rings`` counters."""
    got = _drain(schedule, DistributedScheduler.flush, under_jit)
    want = _drain(schedule, _steps, under_jit)
    assert len(got["values"]) == len(want["values"])
    for g, w in zip(got["values"], want["values"]):
        np.testing.assert_array_equal(g, w)
    for key in ("completions", "makespan", "rounds", "links", "rings"):
        assert got[key] == want[key], key
    assert got["makespan"][0] == got["makespan"][1]
    assert got["links"]                             # the drain did count


def test_grouped_flush_bounds_programs_and_compiles():
    """A flush of 200 equal page loads launches 64+64+64+8, four programs;
    flushes of every size from 1 to 256 compile at most seven round
    programs (widths 1..64) for that descriptor and geometry."""
    from repro.core.descriptor import page_descriptor
    from repro.runtime import scheduler as S

    xdma.clear_cache()
    desc = page_descriptor(16, 128, "bfloat16", direction="load")
    x = _page()
    programs = telemetry.bank("sched")

    def flush(n):
        sched = DistributedScheduler(Topology.host_device(2))
        for i in range(n):
            sched.submit(x, desc, link=("d2h0", "d2h1")[i % 2])
        before = programs.get("programs")
        sched.flush()
        return programs.get("programs") - before

    assert flush(200) == 4
    for n in range(1, 257):
        assert flush(n) == len(S._chunk_widths(n)) <= 4 + 6
    mine = [fn for key, fn in S._ROUND_CACHE.items() if desc in key]
    assert len(mine) <= 7
    assert all(fn._cache_size() == 1 for fn in mine)   # one geometry each
    assert S._chunk_widths(200) == [64, 64, 64, 8]
