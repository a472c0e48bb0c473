"""The distributed XDMA runtime: link topology, per-link async scheduling,
and the deterministic utilization simulator (DESIGN.md §6).

Acceptance properties (ISSUE 2):
  (a) per-link FIFO ordering is preserved while tasks on disjoint links
      complete concurrently in the simulated timeline;
  (b) scheduler results are bit-identical to running the same descriptors
      through ``xdma.transfer`` serially;
  (c) on a >=2-link topology with independent transfers the simulated
      makespan is strictly below the serial in-order schedule and per-link
      utilization beats the single-link ``XDMAQueue`` baseline.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multidevice
from repro import core as C
from repro.core import xdma
from repro.runtime import (DistributedScheduler, SimTask, Topology,
                           queue_sim_tasks, serialize, simulate)


def rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), dtype)


# -- topology ----------------------------------------------------------------
def test_topology_presets_and_lookup():
    ring = Topology.ring(4)
    assert len(ring.links) == 4 and ring.nodes == ("dev0", "dev1", "dev2", "dev3")
    assert Topology.ring(4, bidirectional=True).links_between("dev1", "dev0")
    mesh = Topology.tpu_mesh((2, 2))
    assert len(mesh.nodes) == 4 and len(mesh.links) == 8   # 2 torus links/dev
    hd = Topology.host_device(2)
    assert hd.link_names == ("h2d0", "d2h0", "h2d1", "d2h1")
    par = Topology.parallel(3, prefix="lane")
    assert par.link("lane2").src == "memA"
    with pytest.raises(KeyError):
        par.link("lane9")
    with pytest.raises(ValueError):
        par.add_link("memA", "memB", name="lane0")          # duplicate name
    with pytest.raises(ValueError):
        Topology.ring(1)


def test_tpu_mesh_accepts_a_device_grid():
    class _MeshLike:                     # jax.sharding.Mesh duck type
        devices = np.empty((2, 4), dtype=object)

    topo = Topology.tpu_mesh(_MeshLike())
    assert len(topo.nodes) == 8
    # every device has one +1 torus link per axis of size > 1
    assert len(topo.links_from("dev(0,0)")) == 2
    assert topo.links_between("dev(0,3)", "dev(0,0)")       # wraps


def test_link_cost_model_rounds_to_beats():
    link = Topology.parallel(1).link("link0")
    assert link.transfer_time(0) == link.latency
    one_beat = link.transfer_time(1)
    assert one_beat == link.transfer_time(link.width)       # ceil to a beat
    assert link.transfer_time(link.width + 1) > one_beat


# -- simulator: (a) per-link FIFO order, cross-link concurrency --------------
def test_per_link_fifo_with_disjoint_link_concurrency():
    topo = Topology.parallel(2)
    kb64 = 64 * 1024
    tasks = [SimTask(id=0, resource="link0", nbytes=kb64),
             SimTask(id=1, resource="link0", nbytes=kb64),
             SimTask(id=2, resource="link1", nbytes=kb64)]
    rep = simulate(tasks, topo)
    s0, s1, s2 = (rep.span_of(i) for i in range(3))
    assert s1.start == s0.end                   # same-link FIFO: strict order
    assert s1.stall > 0                         # head-of-line wait is counted
    assert s2.start == 0.0                      # disjoint link: starts at once
    assert s2.start < s0.end                    # ... i.e. overlaps task 0
    # deterministic: replay twice, identical timeline
    rep2 = simulate(tasks, topo)
    assert rep.spans == rep2.spans and rep.makespan == rep2.makespan


def test_simulator_dependencies_cross_links():
    topo = Topology.parallel(2)
    tasks = [SimTask(id=0, resource="link0", nbytes=1 << 20),
             SimTask(id=1, resource="link1", nbytes=1 << 20, deps=(0,))]
    rep = simulate(tasks, topo)
    assert rep.span_of(1).start == rep.span_of(0).end
    assert rep.span_of(1).stall == 0.0          # waited on data, not the link


def test_simulator_rejects_bad_schedules():
    topo = Topology.parallel(1)
    with pytest.raises(ValueError):             # unknown dependency
        simulate([SimTask(id=0, resource="link0", deps=(7,))], topo)
    with pytest.raises(ValueError):             # duplicate ids
        simulate([SimTask(id=0, resource="link0"),
                  SimTask(id=0, resource="link0")], topo)
    with pytest.raises(ValueError):             # FIFO deadlock: head waits on
        simulate([SimTask(id=0, resource="link0", deps=(1,)),   # a task stuck
                  SimTask(id=1, resource="link0")], topo)       # behind it


def test_queue_sim_tasks_follow_shape_contracts():
    from repro.serving.transfer import kv_roundtrip_queue
    q = kv_roundtrip_queue(jnp.float32)
    tasks = queue_sim_tasks(q, (64, 128), jnp.float32, "link0")
    assert [t.deps for t in tasks] == [(), (0,)]
    assert all(t.nbytes == 2 * 64 * 128 * 4 for t in tasks)


# -- scheduler: (b) bit-identical to serial transfer -------------------------
def test_scheduler_bit_identical_to_serial_transfer():
    topo = Topology.parallel(2)
    sched = DistributedScheduler(topo)
    x = rand((256, 512))
    d_store = C.describe("MN", "MNM8N128", C.RMSNormPlugin())
    d_load = C.describe("MNM8N128", "MN", C.Transpose())
    d_scale = C.describe("MN", "MN", C.Scale(3.0))
    d_cast = C.describe("MN", "MN", C.Cast(jnp.bfloat16))

    f1 = sched.submit(x, d_store, link="link0")
    f2 = sched.submit(f1, d_load, link="link0")
    f3 = sched.submit(x, d_scale, link="link1")
    f4 = sched.submit(f3, d_cast, link="link1", deps=(f2,))
    sched.flush()

    s1 = xdma.transfer(x, d_store)
    s2 = xdma.transfer(s1, d_load)
    s3 = xdma.transfer(x, d_scale)
    s4 = xdma.transfer(s3, d_cast)
    for fut, ref in [(f1, s1), (f2, s2), (f3, s3), (f4, s4)]:
        np.testing.assert_array_equal(np.asarray(fut.result()), np.asarray(ref))


def test_scheduler_round_batching_reuses_cfg_cache():
    xdma.clear_cache()
    topo = Topology.parallel(2)
    sched = DistributedScheduler(topo)
    x = rand((64, 128))
    desc = C.describe("MN", "MNM8N128")
    f1 = sched.submit(x, desc, link="link0")
    f2 = sched.submit(x, desc, link="link1")
    sched.flush()
    # both tasks dispatched in ONE round through ONE cached lowering
    assert sched._tasks[f1.task_id].round == sched._tasks[f2.task_id].round == 0
    assert xdma.cache_stats().misses == 1
    np.testing.assert_array_equal(np.asarray(f1.result()), np.asarray(f2.result()))


def test_scheduler_round_batches_compiled_fused_programs():
    # plugin-carrying descriptors lower through the plugin compiler (one
    # Pallas kernel each); they must round-batch like any other local task
    # and stay bit-identical to serial transfer
    xdma.clear_cache()
    sched = DistributedScheduler(Topology.parallel(2))
    x = rand((128, 256))
    d0 = C.describe("MN", "MNM8N128", C.RMSNormPlugin(), C.Scale(2.0))
    d1 = C.describe("MN", "MN", C.GatherScatter(indices=np.arange(127, -1, -1)))
    f0 = sched.submit(x, d0, link="link0")
    f1 = sched.submit(x, d1, link="link1")
    sched.flush()
    assert sched._tasks[f0.task_id].round == sched._tasks[f1.task_id].round == 0
    np.testing.assert_array_equal(np.asarray(f0.result()),
                                  np.asarray(xdma.transfer(x, d0)))
    np.testing.assert_array_equal(np.asarray(f1.result()),
                                  np.asarray(xdma.transfer(x, d1)))


# -- sim-vs-real parity: the simulator replays the schedule the scheduler
#    actually dispatched (catches drift between scheduler.py and simulator.py)
def _submit_parity_batch(sched):
    x = rand((256, 512))
    d_store = C.describe("MN", "MNM8N128", C.RMSNormPlugin())
    d_load = C.describe("MNM8N128", "MN", C.Transpose())
    d_scale = C.describe("MN", "MN", C.Scale(3.0))
    futs = []
    for i in range(3):                      # 3 chains, round-robin routed
        f1 = sched.submit(x, d_store)
        f2 = sched.submit(f1, d_load)
        futs += [f1, f2]
    futs.append(sched.submit(x, d_scale, deps=(futs[1],)))
    sched.flush()
    return futs


def _scheduler_dispatch_order(sched, resource):
    """Task ids actually dispatched on ``resource``, in dispatch order."""
    ts = [t for t in sched._tasks.values()
          if t.resource == resource and t.done]
    assert all(t.round >= 0 for t in ts)
    return [t.id for t in sorted(ts, key=lambda t: t.round)]


@pytest.mark.parametrize("n_links", [1, 2])
def test_sim_replay_matches_scheduler_dispatch_order(n_links):
    topo = Topology.parallel(n_links)
    sched = DistributedScheduler(topo)
    _submit_parity_batch(sched)
    rep = simulate(sched.sim_tasks(), topo)
    for link in topo.link_names:
        sim_order = [s.task_id for s in rep.spans if s.resource == link]
        assert sim_order == _scheduler_dispatch_order(sched, link), link
        # and both equal the per-link FIFO submission order (paper §II-B)
        fifo = [tid for tid in sorted(sched._tasks)
                if sched._tasks[tid].resource == link]
        assert sim_order == fifo, link


@pytest.mark.parametrize("n_links", [1, 2])
def test_serialize_preserves_scheduler_submission_order(n_links):
    topo = Topology.parallel(n_links)
    sched = DistributedScheduler(topo)
    _submit_parity_batch(sched)
    serial = serialize(sched.sim_tasks(), "link0", topo)
    rep = simulate(serial, topo)
    order = [s.task_id for s in rep.spans if s.resource == "link0"]
    want = [tid for tid in sorted(sched._tasks)
            if sched._tasks[tid].resource in topo]
    assert order == want
    if n_links == 1:
        # one link: the in-order baseline IS the scheduler's own dispatch
        assert order == _scheduler_dispatch_order(sched, "link0")


def test_serialize_keeps_zero_cost_compute_off_the_link():
    """Without a topology, ``serialize`` must classify by *traffic* (does the
    task move bytes?), not by cost: a barrier-style compute task with
    ``cost_s=0, nbytes=0`` stays on its engine instead of being serialized
    into link traffic (regression: the old predicate ``cost_s > 0`` rerouted
    it and the replay then rejected the engine-less schedule)."""
    tasks = [SimTask(id=0, resource="link1", nbytes=1 << 20),
             SimTask(id=1, resource="engine0", nbytes=0, cost_s=0.0,
                     deps=(0,)),
             SimTask(id=2, resource="link1", nbytes=1 << 10, deps=(1,))]
    serial = serialize(tasks, "link0")           # no topology on purpose
    assert [t.resource for t in serial] == ["link0", "engine0", "link0"]
    rep = simulate(serial, Topology.parallel(1))
    assert rep.span_of(1).start == rep.span_of(0).end


def test_stall_rounds_counter_reconciles_with_sim_contention():
    """`stall_rounds:<link>` pins the scheduler's blocked-round accounting:
    one increment per round a link's ring head waits on cross-link data.
    T2 (link1) deps T0 (link0) -> link1 blocks for exactly one round; the
    replay agrees — T2's wait was data (zero span stall), while the tasks
    queued behind a busy link (T1, T3) carry all the contention stall."""
    from repro.runtime import telemetry
    telemetry.reset("links")
    sched = DistributedScheduler(Topology.parallel(2))
    x = rand((256, 512))
    desc = C.describe("MN", "MNM8N128")
    f0 = sched.submit(x, desc, link="link0")
    sched.submit(x, desc, link="link0")
    sched.submit(x, desc, link="link1", deps=(f0,))
    sched.submit(x, desc, link="link1")
    sched.flush()
    bank = telemetry.bank("links")
    assert bank.get("stall_rounds:link1") == 1
    assert bank.get("stall_rounds:link0", 0) == 0
    rep = sched.report()
    assert rep.span_of(2).stall == 0.0           # waited on data, not link1
    assert rep.span_of(3).stall > 0.0            # queued behind T2's slot
    assert rep.contention_stall == rep.span_of(1).stall + rep.span_of(3).stall


def test_scheduler_routing_and_validation():
    sched = DistributedScheduler(Topology.parallel(2))
    x = rand((8, 128))
    desc = C.describe("MN", "MN")
    # default routing round-robins the fabric
    f1, f2, f3 = (sched.submit(x, desc) for _ in range(3))
    assert [sched._tasks[f.task_id].resource for f in (f1, f2, f3)] == \
        ["link0", "link1", "link0"]
    with pytest.raises(KeyError):
        sched.submit(x, desc, link="nope")
    with pytest.raises(TypeError):
        sched.submit(x, "not-a-descriptor")
    with pytest.raises(ValueError):
        sched.submit_compute(lambda v: v, x, resource="link0")  # link name
    fut = sched.submit_compute(lambda a, b: a + b, f1, f2, cost_s=1e-6)
    np.testing.assert_array_equal(np.asarray(fut.result()),
                                  np.asarray(x) + np.asarray(x))
    assert sched.pending == 0


# -- (c) distributed beats the in-order single-link schedule -----------------
def test_distributed_makespan_and_utilization_beat_serial():
    topo = Topology.parallel(2)
    sched = DistributedScheduler(topo)
    x = rand((512, 512))
    desc = C.describe("MN", "MNM8N128")
    futs = [sched.submit(x, desc) for _ in range(6)]    # independent transfers
    sched.flush()
    dist = sched.report()

    # serial baseline: the same tasks through one in-order FIFO — what a
    # single XDMAQueue dispatches
    serial = simulate(serialize(sched.sim_tasks(), "link0"), topo)
    assert dist.makespan < serial.makespan
    assert dist.mean_link_utilization > serial.mean_link_utilization
    assert serial.link_utilization["link1"] == 0.0

    # the XDMAQueue contract-derived baseline agrees with the serialized one
    q = C.XDMAQueue([desc] * 6)
    q_tasks = queue_sim_tasks(q, (512, 512), jnp.float32, "link0")
    q_rep = simulate(q_tasks, topo)
    assert dist.mean_link_utilization > q_rep.mean_link_utilization
    for f in futs:
        np.testing.assert_array_equal(np.asarray(f.result()),
                                      np.asarray(xdma.transfer(x, desc)))


# -- rewired call sites ------------------------------------------------------
def test_kv_roundtrips_overlapped_parity_and_pipelining():
    from repro.serving import transfer as T
    kvs = [rand((2, 64, 4, 32), seed=s) for s in range(3)]
    outs, sched = T.kv_roundtrips_overlapped(kvs)
    for kv, out in zip(kvs, outs):
        ref = T.kv_load_transposed(T.kv_prefill_store(kv))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    rep = sched.report()
    spans = {t.label + f"#{t.id}": rep.span_of(t.id) for t in sched.sim_tasks()}
    stores = sorted((s for n, s in spans.items() if n.startswith("kv_store")),
                    key=lambda s: s.start)
    loads = sorted((s for n, s in spans.items() if n.startswith("kv_load")),
                   key=lambda s: s.start)
    # shard 1's store overlaps shard 0's load: separate links pipeline
    assert stores[1].start < loads[0].end
    assert rep.makespan < simulate(serialize(sched.sim_tasks(), "h2d0"),
                                   sched.topology).makespan


def test_prefetch_staged_matches_stage_batch():
    from repro.data.pipeline import SyntheticLM, prefetch_staged, stage_batch
    ds = SyntheticLM(vocab=64, seq_len=8, global_batch=4, family="vlm",
                     d_model=16)
    batches = [ds.batch_at(i) for i in range(4)]
    staged = list(prefetch_staged(iter(batches), jnp.bfloat16, depth=2))
    assert len(staged) == len(batches)
    for got, b in zip(staged, batches):
        ref = stage_batch(b, jnp.bfloat16)
        assert set(got) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]))


def test_moe_scheduled_dispatch_matches_local():
    out = run_multidevice("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.layers import moe as MOE
from repro.sharding import Axes
from repro.runtime import DistributedScheduler, Topology
cfg = dataclasses.replace(configs.smoke_config('qwen3_moe_30b_a3b'),
                          dtype=jnp.float32, capacity_factor=8.0)
p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
y_local, _ = MOE.moe_apply(cfg, p, x)
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg2 = cfg.with_axes(Axes(batch=('data',), model='model', model_size=4, batch_size=2))
sched = DistributedScheduler(Topology.parallel(2, prefix='a2a'), name='moe')
with mesh:
    y_sched, _ = jax.jit(lambda xx: MOE.moe_apply(cfg2, p, xx, mesh=mesh,
                                                  scheduler=sched))(x)
rel = float(jnp.abs(y_sched - y_local).max() / (jnp.abs(y_local).max() + 1e-9))
assert rel < 5e-4, rel
rep = sched.report()
# both chunks' dispatches run concurrently on their own links while FFN
# (a compute engine) sits between dispatch and return per chunk
d0, d1 = rep.span_of(0), rep.span_of(3)
assert d0.resource != d1.resource and d1.start < d0.end
ffn = [s for s in rep.spans if s.resource == 'expert_ffn']
assert len(ffn) == 2 and all(s.duration > 0 for s in ffn)
ret = [s for s in rep.spans if s.label.startswith('a2a_return')]
assert all(r.start >= f.end for r, f in zip(sorted(ret, key=lambda s: s.start), ffn))
# tight capacity: token dropping must match the unscheduled path exactly
# (the chunked path pads the buffer, never the capacity)
cfg4 = dataclasses.replace(cfg2, capacity_factor=1.0)
sched2 = DistributedScheduler(Topology.parallel(2, prefix='a2a'), name='moe2')
with mesh:
    y_tight, _ = jax.jit(lambda xx: MOE.moe_apply(cfg4, p, xx, mesh=mesh))(x)
    y_tight_s, _ = jax.jit(lambda xx: MOE.moe_apply(cfg4, p, xx, mesh=mesh,
                                                    scheduler=sched2))(x)
np.testing.assert_allclose(np.asarray(y_tight_s), np.asarray(y_tight),
                           rtol=1e-5, atol=1e-6)
print('OK')
""")
    assert "OK" in out
