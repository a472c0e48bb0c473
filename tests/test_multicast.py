"""The multicast movement plane (DESIGN.md §14): tree synthesis, forked
scheduling, shared-hop pricing, and the rewired broadcast consumers.

Acceptance properties (ISSUE 10):
  * the synthesized tree carries each payload over every tree edge exactly
    once — per-link wire bytes are 1x the payload, never the N-unicast Nx;
  * capture -> replay agrees with the scheduler on per-link bytes on all
    three fabric presets, and replaying on a *different* fabric
    re-synthesizes the tree from the recorded spec;
  * the simulated multicast makespan strictly beats N unicasts whenever the
    tree shares >= 1 hop, and equals them exactly (ratio 1.0, never worse)
    when it shares none;
  * the multicast-backed ring all-gather stays bitwise-equal to
    ``lax.all_gather``, including with the serving plane under forced
    preemption in the same process.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multidevice
from repro import core as C
from repro.core import Endpoint, autotune
from repro.core.descriptor import XDMADescriptor
from repro.runtime import (DistributedScheduler, Topology, capture,
                           multicast_sim_tasks, simulate, telemetry,
                           unicast_sim_tasks)


def rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       dtype)


def _mcast_desc(dsts):
    return C.describe(Endpoint.local(C.MN), Endpoint.multicast(tuple(dsts)))


# -- tree synthesis ----------------------------------------------------------
def test_ring_tree_is_a_chain_with_nested_serves():
    topo = Topology.ring(4)
    tree = topo.multicast_tree("dev0", ["dev1", "dev2", "dev3"])
    assert [(h.src, h.dst) for h in tree.hops] == [
        ("dev0", "dev1"), ("dev1", "dev2"), ("dev2", "dev3")]
    # the first hop serves everyone downstream, the last only its leaf
    assert [len(h.serves) for h in tree.hops] == [3, 2, 1]
    # unicasts would re-walk the prefix: 1 + 2 + 3 hops vs the tree's 3
    assert tree.unicast_hop_count == 6 and tree.saved_hops == 3
    assert tree.bytes_saved(100) == 300
    assert tree.delivery("dev2") == 1


def test_mesh_tree_forks_and_star_saves_nothing():
    mesh = Topology.tpu_mesh((2, 2))
    tree = mesh.multicast_tree("dev(0,0)",
                               ["dev(0,1)", "dev(1,0)", "dev(1,1)"])
    assert len(tree.hops) == 3 and tree.fork_count >= 1
    assert tree.saved_hops >= 1
    star = Topology.host_device(devices=4)
    stree = star.multicast_tree("host", ["dev0", "dev1", "dev2", "dev3"])
    # every destination is its own spoke: no edge is shared, nothing saved
    assert len(stree.hops) == 4 and stree.saved_hops == 0
    assert stree.fork_count == 1 and all(len(h.serves) == 1
                                         for h in stree.hops)


def test_chain_policy_and_validation_errors():
    mesh = Topology.tpu_mesh((2, 2))
    chain = mesh.multicast_tree("dev(0,0)", ["dev(0,1)", "dev(1,1)"],
                                policy="chain")
    assert chain.kind == "chain"
    # the chain threads dst i through dst i-1 (ring-chain fallback shape)
    assert chain.delivery("dev(1,1)") == len(chain.hops) - 1
    with pytest.raises(ValueError):
        mesh.multicast_tree("dev(0,0)", [])
    with pytest.raises(ValueError):
        mesh.multicast_tree("dev(0,0)", ["dev(0,0)"])
    with pytest.raises(ValueError):
        mesh.multicast_tree("dev(0,0)", ["nowhere"])
    with pytest.raises(ValueError):
        mesh.multicast_tree("dev(0,0)", ["dev(0,1)"], policy="bogus")


# -- simulator pricing -------------------------------------------------------
NBYTES = 1 << 20


def test_multicast_strictly_beats_unicasts_exactly_when_hops_shared():
    cases = [
        (Topology.ring(4), "dev0", ["dev1", "dev2", "dev3"]),
        (Topology.tpu_mesh((2, 2)), "dev(0,0)",
         ["dev(0,1)", "dev(1,0)", "dev(1,1)"]),
        (Topology.host_device(devices=4), "host",
         ["dev0", "dev1", "dev2", "dev3"]),
    ]
    for topo, src, dsts in cases:
        m_tasks, tree = multicast_sim_tasks(topo, src, dsts, NBYTES)
        u_tasks = unicast_sim_tasks(topo, src, dsts, NBYTES)
        ratio = (simulate(u_tasks, topo).makespan
                 / simulate(m_tasks, topo).makespan)
        if tree.saved_hops >= 1:
            assert ratio > 1.0, (topo.name, ratio)
        else:
            assert ratio == pytest.approx(1.0, abs=1e-15), (topo.name, ratio)


def test_ring_and_mesh_ratios_are_the_designed_values():
    ring = Topology.ring(4)
    m, tree = multicast_sim_tasks(ring, "dev0", ["dev1", "dev2", "dev3"],
                                  NBYTES)
    u = unicast_sim_tasks(ring, "dev0", ["dev1", "dev2", "dev3"], NBYTES)
    # chain pipeline: 3 hop-times vs the serial 1+2+2 unicast re-walks
    assert (simulate(u, ring).makespan / simulate(m, ring).makespan
            == pytest.approx(5 / 3, rel=1e-12))
    mesh = Topology.tpu_mesh((2, 2))
    m, _ = multicast_sim_tasks(mesh, "dev(0,0)",
                               ["dev(0,1)", "dev(1,0)", "dev(1,1)"], NBYTES)
    u = unicast_sim_tasks(mesh, "dev(0,0)",
                          ["dev(0,1)", "dev(1,0)", "dev(1,1)"], NBYTES)
    assert (simulate(u, mesh).makespan / simulate(m, mesh).makespan
            == pytest.approx(3 / 2, rel=1e-12))


def test_wire_bytes_once_per_tree_edge_not_per_destination():
    ring = Topology.ring(4)
    m_tasks, tree = multicast_sim_tasks(ring, "dev0",
                                        ["dev1", "dev2", "dev3"], NBYTES)
    links = [t.resource for t in m_tasks]
    assert sorted(links) == sorted(set(links))       # each edge exactly once
    assert all(t.nbytes == NBYTES for t in m_tasks)
    # the unicast schedule re-carries the payload: dev0's egress link 3x
    u_tasks = unicast_sim_tasks(ring, "dev0", ["dev1", "dev2", "dev3"],
                                NBYTES)
    first = ring.links_between("dev0", "dev1")[0].name
    per_link = {}
    for t in u_tasks:
        per_link[t.resource] = per_link.get(t.resource, 0) + t.nbytes
    assert per_link[first] == 3 * NBYTES


# -- the scheduler fork ------------------------------------------------------
def test_submit_multicast_forks_delivers_bit_identical_payloads():
    telemetry.reset("multicast")
    x = rand((64, 256))
    sched = DistributedScheduler(Topology.ring(4))
    fut = sched.submit_multicast(x, _mcast_desc(["dev1", "dev2", "dev3"]),
                                 src="dev0", label="bcast")
    sched.flush()
    assert fut.done() and fut.dsts == ("dev1", "dev2", "dev3")
    for got in fut.result():
        np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
    # one ring post (one doorbell CSR write) per tree hop, no more
    assert len(fut.tree.hops) == 3
    hop_tasks = [sched._tasks[f.task_id] for f in
                 (fut.future(d) for d in fut.dsts)]
    assert all(t.csr_writes == 1 for t in hop_tasks)
    stats = telemetry.bank("multicast").as_dict()
    assert stats["trees"] == 1 and stats["hops"] == 3
    assert stats["saved_hop_bytes"] == fut.tree.bytes_saved(x.nbytes)


def test_submit_multicast_guards_and_plain_submit_refuses_it():
    x = rand((32, 128))
    sched = DistributedScheduler(Topology.ring(4))
    with pytest.raises(ValueError):
        sched.submit(x, _mcast_desc(["dev1"]), link="dev0->dev1")
    with pytest.raises(TypeError):
        sched.submit_multicast(x, "not a descriptor", src="dev0")
    with pytest.raises(ValueError):
        sched.submit_multicast(x, C.describe("MN", "MN"), src="dev0")
    plug = C.describe(Endpoint.local(C.MN),
                      Endpoint.multicast(("dev1",)), C.Scale(2.0))
    with pytest.raises(ValueError):
        sched.submit_multicast(x, plug, src="dev0")


def test_per_destination_auto_layout_resolves_against_delivery_link():
    x = rand((256, 512))
    sched = DistributedScheduler(Topology.ring(4))
    desc = C.describe(Endpoint.local(C.MN),
                      Endpoint.multicast((("dev1", "MNM8N128"),
                                          ("dev2", "auto"))))
    fut = sched.submit_multicast(x, desc, src="dev0")
    sched.flush()
    by_dst = fut.dst_descriptors()
    assert by_dst["dev1"].dst_layout.name == "MNM8N128"
    assert not by_dst["dev2"].dst_layout.is_auto     # resolved, not deferred
    # physical deliveries relayout back to the logical payload bit-exactly
    tiled = fut.result_at("dev1")
    back = C.xdma.transfer(tiled, C.describe("MNM8N128", "MN"))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


# -- capture -> replay -------------------------------------------------------
def _fabric_presets():
    return [
        (Topology.ring(4), "dev0", ["dev1", "dev2", "dev3"]),
        (Topology.tpu_mesh((2, 2)), "dev(0,0)",
         ["dev(0,1)", "dev(1,0)", "dev(1,1)"]),
        (Topology.host_device(devices=4), "host", ["dev1", "dev2", "dev3"]),
    ]


def _per_link_bytes(tasks):
    out = {}
    for t in tasks:
        out[t.resource] = out.get(t.resource, 0) + int(t.nbytes or 0)
    return out


@pytest.mark.parametrize("idx", [0, 1, 2])
def test_capture_replay_byte_parity_on_every_fabric_preset(idx):
    topo, src, dsts = _fabric_presets()[idx]
    x = rand((64, 256))
    with capture(name="mcast") as tr:
        sched = DistributedScheduler(topo)
        fut = sched.submit_multicast(x, _mcast_desc(dsts), src=src)
        sched.flush()
    assert fut.done()
    got = _per_link_bytes(tr.sim_tasks(topo))
    want = _per_link_bytes(sched.sim_tasks())
    assert got == want
    # every tree edge priced once: per-link bytes are 1x the task payload
    payload = 2 * x.nbytes                       # in + out pass, like submit
    assert all(v == payload for v in want.values())
    assert len(want) == len(fut.tree.hops)


def test_replay_on_a_different_fabric_resynthesizes_the_tree():
    x = rand((64, 256))
    with capture(name="mcast") as tr:
        sched = DistributedScheduler(Topology.ring(4))
        sched.submit_multicast(x, _mcast_desc(["dev1", "dev2", "dev3"]),
                               src="dev0")
        sched.flush()
    star = Topology.host_device(devices=4)       # none of the ring links
    rep = tr.replay(star)
    busy = {res for res, b in rep.link_busy.items() if b > 0}
    # the re-synthesized tree routes dev0 -> host -> {dev1, dev2, dev3}
    assert busy == {"d2h0", "h2d1", "h2d2", "h2d3"}
    assert rep.makespan > 0


def test_trace_tags_and_chrometrace_fork_annotations():
    from repro.runtime import chrometrace
    x = rand((64, 256))
    star = Topology.host_device(devices=4)
    with capture(name="mcast") as tr:
        sched = DistributedScheduler(star)
        sched.submit_multicast(x, _mcast_desc(["dev1", "dev2", "dev3"]),
                               src="host")
        sched.flush()
    tagged = [e for e in tr.events if e.multicast_group is not None]
    assert len(tagged) == 3
    assert {e.multicast_hop for e in tagged} == {
        ("host", "dev1"), ("host", "dev2"), ("host", "dev3")}
    assert any(e.multicast_spec is not None for e in tagged)
    events = chrometrace.sim_report_events(tr.replay(star), trace=tr)
    forks = [e for e in events
             if e.get("args", {}).get("multicast_group") is not None]
    assert forks and all("hop" in e["args"] and "serves" in e["args"]
                         for e in forks)
    chrometrace.validate_events(events)


# -- satellites --------------------------------------------------------------
def test_fabric_fingerprint_includes_csr_write_cost():
    topo = Topology("t")
    topo.add_link("A", "B", name="l0", csr_write_cost=20e-9)
    fp = autotune.fabric_fingerprint(topo.link("l0"))
    assert len(fp) == 5 and fp[-1] == 20e-9
    topo2 = Topology("t")
    topo2.add_link("A", "B", name="l0", csr_write_cost=40e-9)
    assert fp != autotune.fabric_fingerprint(topo2.link("l0"))


def test_snapshot_surfaces_multicast_stats():
    telemetry.reset("multicast")
    x = rand((32, 128))
    with telemetry.session(name="mcast"):
        sched = DistributedScheduler(Topology.ring(3))
        sched.submit_multicast(x, _mcast_desc(["dev1", "dev2"]), src="dev0")
        sched.flush()
        snap = telemetry.snapshot()
    stats = snap["surfaces"]["multicast_stats"]
    assert stats["trees"] >= 1 and stats["hops"] >= 2


# -- the rewired consumers ---------------------------------------------------
def test_dp_param_broadcast_delivers_every_replica_bitwise():
    from repro.train.step import dp_param_broadcast
    params = {"w": rand((32, 64)), "emb": rand((2, 8, 128), seed=1),
              "step": jnp.zeros((), jnp.int32)}
    with capture(name="bcast") as tr:
        sched = DistributedScheduler(Topology.ring(4))
        reps = dp_param_broadcast(params, scheduler=sched)
    assert len(reps) == 3
    for rep in reps:
        np.testing.assert_array_equal(np.asarray(rep["w"]),
                                      np.asarray(params["w"]))
        np.testing.assert_array_equal(np.asarray(rep["emb"]),
                                      np.asarray(params["emb"]))
        assert rep["step"] is params["step"]     # counters stay off-plane
    assert tr.by_endpoint().get("multicast", 0) >= 6   # 2 leaves x 3 hops


def test_serving_weight_broadcast_and_prefix_fanout():
    from repro.serving import prefix_cache_fanout, replica_weight_broadcast
    params = {"w": rand((64, 128))}
    sched = DistributedScheduler(Topology.host_device(devices=3))
    out = replica_weight_broadcast(params, scheduler=sched)
    assert set(out) == {"dev0", "dev1", "dev2"}
    for p in out.values():
        np.testing.assert_array_equal(np.asarray(p["w"]),
                                      np.asarray(params["w"]))
    pages = rand((4, 16, 128), seed=2)
    fut = prefix_cache_fanout(pages, scheduler=sched, dsts=["dev1", "dev2"])
    assert all(not d.dst_layout.is_auto
               for d in fut.dst_descriptors().values())
    np.testing.assert_array_equal(np.asarray(fut.result_at("dev2")),
                                  np.asarray(pages.reshape(-1, 128)))


def test_engine_distribute_weights_builds_ring_and_returns_replicas():
    import dataclasses

    import jax

    from repro import configs
    from repro.models import lm
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(configs.smoke_config("qwen3_1p7b"),
                              dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_len=16, cache_dtype=jnp.float32)
    out, sched = eng.distribute_weights(2)
    assert set(out) == {"dev1", "dev2"} and eng.last_scheduler is sched
    ref = jax.tree_util.tree_leaves(params)
    for rep in out.values():
        for a, b in zip(jax.tree_util.tree_leaves(rep), ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_multicast_all_gather_bitwise_under_forced_preemption():
    out = run_multidevice(r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax import lax
from repro import configs
from repro.layers import moe as MOE
from repro.models import lm
from repro.serving import ContinuousBatchingEngine, PagedKVPool, uniform_stream
from repro.sharding import P

# put the serving plane under real page pressure first
cfg = dataclasses.replace(configs.smoke_config('qwen3_1p7b'),
                          dtype=jnp.float32)
params = lm.init_params(jax.random.PRNGKey(0), cfg)
reqs = uniform_stream(cfg, 3, 0.0, prompt_len=8, max_new=4)
rep = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=3,
                               cache_dtype=jnp.float32,
                               pool=PagedKVPool(7, 32)).serve(reqs)
assert rep.preemptions > 0, 'pool of 7 pages must force preemption'

# ...and the multicast-backed ring all-gather must still be bitwise
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
def body(v):
    return (MOE._ring_all_gather(v, 'model', 4),
            lax.all_gather(v, 'model', axis=1, tiled=True))
v = jax.random.normal(jax.random.PRNGKey(2), (8, 4, 16), jnp.float32)
with mesh:
    ring, ref = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, 'model', None),
        out_specs=P(None, 'model', None), check_vma=False))(v)
np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))
print('MCAST_AG_OK', rep.preemptions)
""", n_devices=8)
    assert "MCAST_AG_OK" in out
