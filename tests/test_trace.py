"""The movement plane (DESIGN.md §9): capture ledger, replay cost model,
and the applications routed through it.

Acceptance properties (ISSUE 5):
  * capture -> replay is deterministic;
  * a trace captured from a scheduler agrees with ``scheduler.report()`` on
    per-link bytes;
  * a captured serving-decode trace's simulated makespan strictly improves
    with >= 2 links;
  * every data movement issued by ``ServingEngine.generate``, the explicit
    DP ``train_step``, ``CheckpointManager.save/restore``, and ``moe_apply``
    appears in a ``capture()`` trace, with zero out-of-plane collectives
    (every collective primitive call originates in ``repro.core.remote``,
    the plane's lowering backend) and zero out-of-plane staging.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multidevice
from repro import core as C
from repro.core import xdma
from repro.runtime import (DistributedScheduler, Topology, TransferTrace,
                           capture)
from repro.runtime import trace as TR


def rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), dtype)


# -- ledger basics -----------------------------------------------------------
def test_capture_is_scoped_and_zero_cost_when_off():
    x = rand((64, 256))
    desc = C.describe("MN", "MNM8N128")
    assert TR.current() is None
    with capture(name="t") as tr:
        assert TR.current() is tr
        xdma.transfer(x, desc)
    assert TR.current() is None
    n = len(tr.events)
    xdma.transfer(x, desc)                    # outside the scope: not recorded
    assert len(tr.events) == n == 1
    ev = tr.events[0]
    assert ev.endpoint == "local" and ev.desc is desc
    assert ev.nbytes == 2 * 64 * 256 * 4
    # the tile row is the contiguous burst; a software loop issues full rows
    assert ev.burst_bytes == 128 * 4 and ev.row_bytes == 256 * 4
    assert ev.pipeline_depth == 9


def test_capture_records_dataflow_deps_and_queue_chains():
    x = rand((128, 256))
    store = C.describe("MN", "MNM8N128", C.RMSNormPlugin())
    load = C.describe("MNM8N128", "MN", C.Transpose())
    with capture() as tr:
        y = xdma.transfer(x, store)
        xdma.transfer(y, load)                        # consumes y -> dep edge
        q = C.XDMAQueue([store, load], name="rt")
        q.run(x)                                      # fused queue: 2 events
    assert [e.deps for e in tr.events] == [(), (0,), (), (2,)]
    assert [e.source for e in tr.events] == ["transfer", "transfer",
                                             "queue", "queue"]
    # queue events carry the contract-propagated geometry
    assert tr.events[2].logical_shape == (128, 256)
    assert tr.events[3].logical_shape == (128, 256)


def test_capture_replay_determinism():
    def workload(tr_name):
        with capture(name=tr_name) as tr:
            sched = DistributedScheduler(Topology.host_device(2))
            x = rand((256, 512))
            # d_buf=5: keep this round's descriptor identities distinct from
            # other tests' (the scheduler round cache is global + structural)
            store = C.describe("MN", "MNM8N128", d_buf=5)
            load = C.describe("MNM8N128", "MN", C.Transpose(), d_buf=5)
            for lane in range(3):
                f = sched.submit(x, store, label=f"s{lane}")
                sched.submit(f, load, label=f"l{lane}")
            sched.flush()
        return tr

    t1, t2 = workload("a"), workload("b")
    assert len(t1.events) == len(t2.events)
    for a, b in zip(t1.events, t2.events):
        assert (a.endpoint, a.link, a.deps, a.nbytes, a.burst_bytes,
                a.row_bytes, a.pipeline_depth) == \
               (b.endpoint, b.link, b.deps, b.nbytes, b.burst_bytes,
                b.row_bytes, b.pipeline_depth)
    for topo in (Topology.host_device(2), Topology.ring(4)):
        r1, r2 = t1.replay(topo), t2.replay(topo)
        assert r1.makespan == r2.makespan and r1.spans == r2.spans
        # and replaying the same trace twice is bit-stable too
        again = t1.replay(topo)
        assert again.spans == r1.spans


def test_lazy_flush_does_not_leak_into_other_traces():
    """A scheduler submitted under capture A but drained under capture B must
    finalize and register provenance with A (the trace owning its events) —
    B's dependency graph must not reference A's event ids."""
    with capture(name="a") as ta:
        sched = DistributedScheduler(Topology.parallel(2))
        x = rand((64, 128))
        f = sched.submit(x, C.describe("MN", "MN"))
    with capture(name="b") as tb:
        sched.flush()                    # lazily drained under another trace
        xdma.transfer(f.result(), C.describe("MN", "MN"))
    assert len(ta.events) == 1
    assert ta.events[0].nbytes == 2 * 64 * 128 * 4      # finalized into A
    assert len(tb.events) == 1 and tb.events[0].deps == ()
    tb.replay(Topology.parallel(1))                     # stays well-formed


def test_trace_vs_scheduler_report_per_link_byte_parity():
    with capture() as tr:
        sched = DistributedScheduler(Topology.parallel(3))
        x = rand((256, 512))
        descs = [C.describe("MN", "MNM8N128"),
                 C.describe("MN", "MN", C.Scale(2.0)),
                 C.describe("MN", "MN", C.Cast(jnp.bfloat16))]
        for i in range(6):
            sched.submit(x, descs[i % 3])
        sched.flush()
    want = {}
    for t in sched.sim_tasks():
        if t.resource in sched.topology:
            want[t.resource] = want.get(t.resource, 0) + t.nbytes
    assert tr.per_link_bytes() == want
    assert tr.total_bytes == sum(want.values())
    # the report prices exactly those bytes
    assert sched.report().total_bytes == sum(want.values())


def test_sw_agu_costing_strictly_slower_than_frontend():
    with capture() as tr:
        x = rand((512, 512))
        xdma.transfer(x, C.describe("MN", "MNM8N128"))
        xdma.transfer(x, C.describe("MN", "MN", C.Transpose()))
    topo = Topology.parallel(2)
    hw, sw = tr.replay(topo), tr.replay(topo, sw_agu=True)
    assert sw.makespan > hw.makespan
    tasks = tr.sim_tasks(topo, sw_agu=True)
    assert all(t.issue_overhead_s is not None and t.pipeline_depth == 1
               for t in tasks)


# -- serving through the plane ----------------------------------------------
def _serving_trace(n_steps=2):
    from repro import configs
    from repro.models import lm
    from repro.serving.engine import ServingEngine

    cfg = dataclasses.replace(configs.smoke_config("qwen3_1p7b"),
                              dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_len=24, cache_dtype=jnp.float32)
    prompt = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                           cfg.vocab)}
    with capture(name="serving") as tr:
        out = eng.generate(dict(prompt), n_steps)
    return tr, eng, out


def test_serving_decode_trace_improves_with_more_links():
    tr, eng, _ = _serving_trace()
    assert len(tr.xdma_events()) > 0
    # per-step KV roundtrips are present and scheduler-routed
    labels = [e.label for e in tr.events]
    assert any(l.startswith("kv:prefill") for l in labels)
    assert any(l.startswith("kv:decode") for l in labels)
    one = tr.replay(Topology.host_device(1))
    two = tr.replay(Topology.host_device(2))
    assert two.makespan < one.makespan           # strictly better with 2 pairs
    # and the engine's own scheduler carries the same schedule
    assert eng.last_scheduler is not None
    assert eng.last_scheduler.report().total_bytes == tr.total_bytes


def test_serving_generate_bit_identical_with_and_without_capture():
    _, _, out1 = _serving_trace()
    _, _, out2 = _serving_trace()
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# -- checkpointing through the plane ----------------------------------------
def test_checkpoint_staging_recorded_and_exact(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    tree = {"w": rand((32, 64)), "b": jnp.zeros((64,), jnp.float32),
            "step": jnp.asarray(3, jnp.int32)}
    m = CheckpointManager(str(tmp_path), keep=2)
    with capture(name="ckpt") as tr:
        m.save(1, tree)
        back = m.restore(1, jax.eval_shape(lambda: tree))
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))
    # one d2h event on save + one h2d event on restore for the matrix shard;
    # the vector/scalar leaves are control state, not plane traffic
    assert len(tr.xdma_events()) == 2
    assert all(e.endpoint == "local" for e in tr.xdma_events())


def test_checkpoint_cast_and_compress_capable_staging(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    w = rand((32, 64)).at[:16].set(0.0)
    tree = {"w": w}
    m = CheckpointManager(str(tmp_path), keep=2, stage_dtype=jnp.bfloat16,
                          wire_compress_blocks=8)
    with capture() as tr:
        m.save(1, tree)
    ev = tr.xdma_events()[0]
    assert any(p.name == "compress_blocksparse" for p in ev.desc.pre)
    # half the row blocks are zero: the compressed wire is cheaper than dense
    assert ev.wire_nbytes is not None and ev.wire_nbytes < 32 * 64 * 2
    back = m.restore(1, jax.eval_shape(lambda: tree))
    assert back["w"].dtype == jnp.float32        # cast back to template dtype
    np.testing.assert_allclose(np.asarray(back["w"]),
                               np.asarray(w.astype(jnp.bfloat16), np.float32))


# -- data pipeline through the plane ----------------------------------------
def test_pipeline_staging_lands_in_ambient_capture():
    from repro.data.pipeline import SyntheticLM, prefetch_staged, stage_batch

    ds = SyntheticLM(vocab=64, seq_len=8, global_batch=4, family="vlm",
                     d_model=16)
    batches = [ds.batch_at(i) for i in range(3)]
    with capture(name="staging") as tr:
        staged = list(prefetch_staged(iter(batches), jnp.bfloat16, depth=2))
    assert len(staged) == 3
    evs = tr.xdma_events()
    assert len(evs) == 3                       # one embeds staging per batch
    assert all(e.source == "scheduler" and e.link.startswith("h2d")
               for e in evs)
    with capture() as tq:
        stage_batch(batches[0], jnp.bfloat16)
    assert [e.source for e in tq.xdma_events()] == ["queue"]


# -- the full in-plane contract (collectives + staging) ----------------------
IN_PLANE_PROLOGUE = r"""
import traceback
from jax import lax as _lax
_calls = []
def _spy(name, orig):
    def wrapped(*a, **k):
        stack = "".join(traceback.format_stack())
        _calls.append((name, "core/remote.py" in stack))
        return orig(*a, **k)
    return wrapped
for _n in ("psum", "all_gather", "all_to_all", "ppermute"):
    setattr(_lax, _n, _spy(_n, getattr(_lax, _n)))

def assert_all_in_plane():
    out = [n for n, ok in _calls if not ok]
    assert _calls, "expected collective traffic"
    assert not out, f"out-of-plane collectives: {out}"
"""


def test_moe_apply_zero_out_of_plane_collectives_and_bit_parity():
    out = run_multidevice(IN_PLANE_PROLOGUE + r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax import lax
from repro import configs
from repro.layers import moe as MOE
from repro.sharding import Axes, P
from repro.runtime import capture

cfg = dataclasses.replace(configs.smoke_config('qwen3_moe_30b_a3b'),
                          dtype=jnp.float32, capacity_factor=8.0)
p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg2 = cfg.with_axes(Axes(batch=('data',), model='model', model_size=4,
                          batch_size=2))

# EP path: seq-split + a2a + ring all-gather, captured
with capture(name='moe') as tr:
    with mesh:
        y_ep, aux = jax.jit(lambda xx: MOE.moe_apply(cfg2, p, xx, mesh=mesh))(x)
kinds = tr.by_endpoint()
assert kinds.get('all_to_all', 0) >= 2, kinds      # dispatch + return
assert kinds.get('multicast', 0) >= 3, kinds       # ring all-gather hops
assert kinds.get('reduce', 0) >= 1, kinds          # aux pmean
assert_all_in_plane()

# bit parity vs the pre-plane direct-collective spelling of the EP body
y_local, _ = MOE.moe_apply(cfg, p, x)
rel = float(jnp.abs(y_ep - y_local).max() / (jnp.abs(y_local).max() + 1e-9))
assert rel < 5e-4, rel

# the ring all-gather alone is bitwise lax.all_gather
def body(v):
    g_ring = MOE._ring_all_gather(v, 'model', 4)
    g_ref = lax.all_gather(v, 'model', axis=1, tiled=True)
    return g_ring, g_ref
v = jax.random.normal(jax.random.PRNGKey(2), (8, 4, 16), jnp.float32)
with mesh:
    ring, ref = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, 'model', None),
        out_specs=P(None, 'model', None), check_vma=False))(v)
np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))

# TP path (psum through a reduce descriptor) matches replicated-expert math
cfg_tp = dataclasses.replace(cfg, n_experts=6, top_k=2, d_ff_expert=32)
p_tp = MOE.init_moe(jax.random.PRNGKey(3), cfg_tp)
cfg_tp2 = cfg_tp.with_axes(Axes(batch=('data',), model='model', model_size=4,
                                batch_size=2))
y_tp_local, _ = MOE.moe_apply(cfg_tp, p_tp, x)
with mesh:
    y_tp, _ = jax.jit(lambda xx: MOE.moe_apply(cfg_tp2, p_tp, xx,
                                               mesh=mesh))(x)
rel = float(jnp.abs(y_tp - y_tp_local).max() / (jnp.abs(y_tp_local).max() + 1e-9))
assert rel < 5e-4, rel
print('OK')
""")
    assert "OK" in out


def test_dp_train_step_through_plane_multidevice():
    out = run_multidevice(IN_PLANE_PROLOGUE + r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.configs.base import ShapeConfig
from repro.data.pipeline import SyntheticLM, stage_batch
from repro.train.step import init_state, make_train_step, make_dp_train_step
from repro.runtime import capture, Topology

cfg = dataclasses.replace(configs.smoke_config('qwen2_0p5b'), dtype=jnp.float32)
shape = ShapeConfig('t', 16, 8, 'train', microbatches=1)
ds = SyntheticLM(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=1)
state = init_state(jax.random.PRNGKey(0), cfg)
mesh = jax.make_mesh((4,), ('dp',),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)

# uncompressed explicit DP == the single-process reference step
step_ref = jax.jit(make_train_step(cfg, shape))
step_dp = make_dp_train_step(cfg, shape, mesh=mesh, axis='dp',
                             compressed=False)
batch = stage_batch(ds.batch_at(0), jnp.float32)
s_ref, m_ref = step_ref(dict(state), dict(batch))
with capture(name='train') as tr:
    s_dp, m_dp = step_dp(dict(state), dict(batch))
assert abs(float(m_ref['loss']) - float(m_dp['loss'])) < 1e-5
worst = max(float(jnp.abs(a - b).max()) for a, b in
            zip(jax.tree.leaves(s_ref['params']), jax.tree.leaves(s_dp['params'])))
assert worst < 1e-4, worst
# every gradient leaf's all-reduce is a reduce-endpoint ledger row
n_leaves = len(jax.tree.leaves(state['params']))
reduces = [e for e in tr.xdma_events() if e.endpoint == 'reduce']
assert len(reduces) == n_leaves + 1, (len(reduces), n_leaves)  # + loss mean
assert_all_in_plane()

# compressed codec: int8 wire, close-but-not-equal update
step_c = make_dp_train_step(cfg, shape, mesh=mesh, axis='dp', compressed=True)
with capture(name='trainc') as trc:
    s_c, m_c = step_c(dict(state), dict(batch))
assert abs(float(m_c['loss']) - float(m_ref['loss'])) < 1e-5  # loss uncompressed
red = [e for e in trc.xdma_events() if e.endpoint == 'reduce' and e.wire_nbytes]
assert red and all(e.wire_nbytes < e.nbytes for e in red
                   if e.logical_shape and len(e.logical_shape) >= 2)
rep = trc.replay(Topology.ring(4))
sw = trc.replay(Topology.ring(4), sw_agu=True)
assert sw.makespan > rep.makespan
print('OK')
""")
    assert "OK" in out


def test_serving_and_checkpoint_zero_out_of_plane(tmp_path):
    """Single-device serving + checkpoint paths issue no collectives at all;
    their staging is fully in-plane (every float matrix movement is a ledger
    event)."""
    from repro.checkpoint.manager import CheckpointManager

    tr, eng, _ = _serving_trace()
    # every float matrix cache leaf roundtrips through the plane each step
    cache_mats = 2  # qwen3_1p7b smoke: one ATTN period -> stacked k + v
    per_step = 2 * cache_mats                       # store + load per tensor
    assert len(tr.xdma_events()) == per_step * (1 + 2)  # prefill + 2 steps
    m = CheckpointManager(str(tmp_path))
    with capture() as tc:
        m.save(1, {"w": rand((16, 128))})
    assert len(tc.xdma_events()) == 1
