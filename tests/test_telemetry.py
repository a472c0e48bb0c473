"""The telemetry plane (DESIGN.md §11): counter banks, spans, snapshot,
Chrome-trace export.

Acceptance properties (ISSUE 7):
  * per-link byte counters == ``TransferTrace.per_link_bytes()`` ==
    the submitting scheduler's per-link byte sums, bit-exactly, across
    serving + train + MoE captures;
  * spans nest correctly, including under jit (chokepoint spans record at
    trace time, once per compilation — same discipline as ``capture()``);
  * telemetry disabled is zero-cost: ``snapshot()`` is ``{}``, the span
    hook is a shared no-op context, results are bit-identical with and
    without a session;
  * the exported Chrome trace validates and contains events for all three
    chokepoints plus the serving engine's phase spans;
  * the five legacy stats surfaces are views over the same banks the
    snapshot reports.
"""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as C
from repro.core import xdma
from repro.runtime import (DistributedScheduler, Topology, capture,
                           chrometrace, telemetry)


def rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       dtype)


def profiled(fn, trace_dir):
    """Run ``fn`` under ``jax.profiler`` (Python tracer off); returns its
    result and the ``/host:CPU`` events as (name, start_ns, end_ns, stats)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]
    return out, host


@pytest.fixture(scope="module")
def model():
    from repro import configs
    from repro.models import lm

    cfg = dataclasses.replace(configs.smoke_config("qwen3_1p7b"),
                              dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# -- counter banks -----------------------------------------------------------
def test_counter_bank_basics():
    b = telemetry.CounterBank("t")
    b.inc("a")
    b.inc("a", 2)
    b.inc("bytes:x", 100)
    b.inc("bytes:y", 7)
    b.record_max("hw", 3)
    b.record_max("hw", 1)                       # high-water keeps the max
    assert b.get("a") == 3 and b["hw"] == 3
    assert b.with_prefix("bytes:") == {"x": 100, "y": 7}
    assert list(b.as_dict()) == sorted(b.as_dict())
    assert "a" in b and "zzz" not in b
    b.clear()
    assert len(b) == 0 and b.get("a") == 0


def test_bank_registry_get_or_create_and_register():
    telemetry.reset("test_registry")
    b = telemetry.bank("test_registry")
    assert telemetry.bank("test_registry") is b
    mine = telemetry.CounterBank("test_registry")
    telemetry.register(mine)
    assert telemetry.banks()["test_registry"] is mine


# -- zero-cost-off -----------------------------------------------------------
def test_snapshot_empty_and_span_noop_without_session():
    assert telemetry.active() is None
    assert telemetry.snapshot() == {}
    # the module-level hook hands back one shared null context: nothing
    # allocated, nothing recorded
    assert telemetry.span("anything") is telemetry._NULL
    telemetry.record_value("ttft_s", 1.0)       # no-op, must not raise


def test_span_hook_sinks_profiler_and_session(tmp_path):
    """One span API, two sinks: the profiler gets a TraceAnnotation (name
    exact, args as stats), the session a SpanEvent, and with both open
    each gets its own."""
    assert telemetry.span("anything") is telemetry._NULL

    def spans():
        with telemetry.span("test.phase", "test", step=3, engine="cb"):
            pass
    _, host = profiled(spans, tmp_path / "profiler")
    got = [(n, st) for n, _, _, st in host if n == "test.phase"]
    assert got == [("test.phase", {"step": 3, "engine": "cb"})]

    with telemetry.session(name="s") as tel:
        spans()
    assert [(s.name, s.track, s.args) for s in tel.spans] == [
        ("test.phase", "test", {"step": 3, "engine": "cb"})]

    with telemetry.session(name="both") as tel:
        _, host = profiled(spans, tmp_path / "both")
    assert [s.name for s in tel.spans] == ["test.phase"]
    assert [n for n, *_ in host if n == "test.phase"] == ["test.phase"]


def test_results_bit_identical_with_and_without_session(model, tmp_path):
    x = rand((64, 128))
    desc = C.describe("MN", "MNM8N128")
    off = xdma.transfer(x, desc)
    with telemetry.session(name="on") as tel:
        on = xdma.transfer(x, desc)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))
    # and the disabled run contributed zero trace events
    assert [s.name for s in tel.spans] == ["xdma.transfer"]
    events = chrometrace.telemetry_events(telemetry.Telemetry("empty"))
    assert all(e["ph"] == "M" for e in events)   # no spans -> no X events

    # the engine serves the same tokens with a session or the profiler on
    from repro.serving import ContinuousBatchingEngine, uniform_stream

    cfg, params = model
    reqs = uniform_stream(cfg, 3, 1e-5, prompt_len=8, max_new=3, seed=0)

    def serve():
        eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=2,
                                       cache_dtype=jnp.float32,
                                       capacity_pages=48)
        return eng.serve(reqs).tokens
    off = serve()
    with telemetry.session(name="on"):
        on_session = serve()
    on_profiler, _ = profiled(serve, tmp_path)
    for got in (on_session, on_profiler):
        assert got.keys() == off.keys()
        for rid in off:
            np.testing.assert_array_equal(got[rid], off[rid])


# -- counter/ledger/report reconciliation ------------------------------------
def _per_link_from_sched(sched):
    want = {}
    for t in sched.sim_tasks():
        if t.resource in sched.topology and t.nbytes:
            want[t.resource] = want.get(t.resource, 0) + t.nbytes
    return want


def _bank_link_bytes():
    return {k: v for k, v
            in telemetry.bank("links").with_prefix("bytes:").items() if v}


def test_three_way_per_link_byte_parity_scheduler():
    telemetry.reset("links")
    with capture() as tr:
        sched = DistributedScheduler(Topology.parallel(3))
        x = rand((256, 512))
        descs = [C.describe("MN", "MNM8N128"),
                 C.describe("MN", "MN", C.Scale(2.0)),
                 C.describe("MN", "MN", C.Cast(jnp.bfloat16))]
        for i in range(6):
            sched.submit(x, descs[i % 3])
        sched.flush()
    assert _bank_link_bytes() == tr.per_link_bytes() \
        == _per_link_from_sched(sched)
    # the companion counters exist per dispatched link
    links = telemetry.bank("links")
    for res in tr.per_link_bytes():
        assert links.get(f"tasks:{res}") > 0
        assert links.get(f"wire_bytes:{res}") > 0
        assert links.get(f"bursts:{res}") > 0


def test_three_way_parity_serving_capture(model):
    from repro.serving.engine import ServingEngine

    cfg, params = model
    eng = ServingEngine(cfg, params, max_len=24, cache_dtype=jnp.float32)
    prompt = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                           cfg.vocab)}
    telemetry.reset("links")
    with capture(name="serving") as tr:
        eng.generate(prompt, 2)
    assert tr.per_link_bytes()                   # KV roundtrips present
    assert _bank_link_bytes() == tr.per_link_bytes() \
        == _per_link_from_sched(eng.last_scheduler)


def test_three_way_parity_moe_capture():
    from repro import configs
    from repro.layers import moe as MOE
    from repro.sharding import Axes

    cfg = dataclasses.replace(configs.smoke_config("qwen3_moe_30b_a3b"),
                              dtype=jnp.float32, capacity_factor=4.0)
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    cfg = cfg.with_axes(Axes(batch=(), model="model", model_size=1,
                             batch_size=1))
    sched = DistributedScheduler(Topology.parallel(2, prefix="a2a"),
                                 name="moe")
    telemetry.reset("links")
    with telemetry.session(name="moe") as tel, capture(name="moe") as tr:
        with mesh:
            jax.jit(lambda xx: MOE.moe_apply(cfg, p, xx, mesh=mesh,
                                             scheduler=sched))(x)
    assert tr.per_link_bytes()
    assert _bank_link_bytes() == tr.per_link_bytes() \
        == _per_link_from_sched(sched)
    # spans recorded under jit + shard_map stay structurally well-nested:
    # parents precede children, depth matches the parent chain
    for i, s in enumerate(tel.spans):
        assert s.parent < i
        if s.parent >= 0:
            assert s.depth == tel.spans[s.parent].depth + 1
        else:
            assert s.depth == 0
    assert any(s.name == "DistributedScheduler.submit" for s in tel.spans)


def test_three_way_parity_train_capture(model):
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import SyntheticLM, stage_batch
    from repro.train.step import init_state, make_dp_train_step

    cfg, _ = model
    shape = ShapeConfig("t", 16, 4, "train", microbatches=1)
    ds = SyntheticLM(vocab=cfg.vocab, seq_len=16, global_batch=4, seed=0)
    state = init_state(jax.random.PRNGKey(0), cfg)
    mesh = jax.make_mesh((1,), ("dp",),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    step = make_dp_train_step(cfg, shape, mesh=mesh, axis="dp",
                              compressed=True)
    telemetry.reset("links")
    with capture(name="train") as tr:
        batch = stage_batch(ds.batch_at(0), jnp.float32)
        step(state, batch)
    assert len(tr.events) > 0
    # the train path moves through queue/reduce endpoints (no pinned links):
    # ledger and counters must agree on exactly that — both empty or equal
    assert _bank_link_bytes() == tr.per_link_bytes()


# -- span nesting ------------------------------------------------------------
def test_spans_nest_by_with_stack_and_once_per_compilation():
    x = rand((32, 128))
    desc = C.describe("MN", "MNM8N128")
    fn = jax.jit(lambda v: xdma.transfer(v, desc))
    with telemetry.session(name="nest") as tel:
        with tel.span("outer", track="test"):
            fn(x)                               # traces: records the span
            fn(x)                               # cached: records nothing
    names = [s.name for s in tel.spans]
    assert names == ["outer", "xdma.transfer"]
    inner = tel.spans[1]
    assert inner.parent == 0 and inner.depth == 1
    assert tel.spans[0].parent == -1 and tel.spans[0].depth == 0
    assert inner.track == "transfer"


def test_queue_and_scheduler_chokepoints_record_spans():
    x = rand((64, 128))
    q = xdma.XDMAQueue([C.describe("MN", "MNM8N128"),
                        C.describe("MNM8N128", "MN")], name="q")
    with telemetry.session(name="chokepoints") as tel:
        q.run(x)
        sched = DistributedScheduler(Topology.parallel(2))
        sched.submit(x, C.describe("MN", "MN"))
        sched.submit_compute(lambda: None, cost_s=1e-6)
        sched.flush()
    tracks = {s.track for s in tel.spans}
    assert {"queue", "scheduler"} <= tracks
    assert {"XDMAQueue.run", "DistributedScheduler.submit",
            "DistributedScheduler.submit_compute"} \
        <= {s.name for s in tel.spans}


# -- legacy surfaces are views over the banks --------------------------------
def test_cache_stats_is_view_over_cfg_cache_bank():
    xdma.clear_cache()
    x = rand((16, 32))
    desc = C.describe("MN", "NM")
    xdma.transfer(x, desc)
    xdma.transfer(x, desc)
    stats = xdma.cache_stats()
    b = telemetry.bank("cfg_cache")
    assert (stats.misses, stats.hits) == (b.get("misses"), b.get("hits")) \
        == (1, 1)
    xdma.clear_cache()
    assert xdma.cache_stats().misses == 0 and b.get("misses") == 0


def test_agu_and_cfg_stats_are_views_over_banks():
    from repro.core import plugin_compiler as PC
    from repro.kernels import agu

    agu.clear_agu_stats()
    agu.record_fallback("test-reason")
    assert agu.agu_stats()["fallback"] == 1
    assert agu.agu_stats()["reasons"] == {"test-reason": 1}
    assert telemetry.bank("agu").get("fallback") == 1
    agu.clear_agu_stats()
    assert agu.agu_stats()["fallback"] == 0

    PC.clear_stats()
    assert PC.cfg_stats() == {"fused": 0, "fallback": 0, "reasons": {}}
    assert telemetry.bank("plugin_compiler") is telemetry.banks()["plugin_compiler"]


def test_pool_stats_is_view_over_registered_bank():
    from repro.serving import PagedKVPool

    pool = PagedKVPool(4, 32, name="tpool")
    sched = DistributedScheduler(Topology.host_device(1), name="t")
    pool.bind(sched)
    pid = pool.alloc(16, "float32")
    pool.store(pid, jnp.ones((32, 16), jnp.float32))
    sched.flush()
    pool.commit()
    assert pool.stats["stores"] == 1 and pool.stats["movements"] == 1
    assert telemetry.banks()["pool:tpool"].get("stores") == 1
    with telemetry.session(name="s"):
        snap = telemetry.snapshot()
    assert snap["surfaces"]["pool_stats"]["tpool"]["stores"] == 1


def test_percentile_is_nearest_rank():
    """The documented estimator is nearest-rank (``ceil(n*q/100)``-th order
    statistic): always an actual sample, never interpolated (regression:
    the old implementation linearly interpolated while the docstring
    promised nearest-rank)."""
    tel = telemetry.Telemetry("t")
    tel.record_value("lat", 5.0)
    assert tel.percentile("lat", 99) == 5.0      # 1-sample p99 = the sample
    assert tel.percentile("lat", 50) == 5.0
    tel.record_value("lat", 1.0)
    assert tel.percentile("lat", 99) == 5.0      # 2-sample p99 = the max,
    assert tel.percentile("lat", 50) == 1.0      # not 1 + 0.98*(5-1)
    tel.record_value("lat", 2.0)
    tel.record_value("lat", 3.0)
    # 4 samples, p50: ceil(4*0.5) = 2nd order statistic — an exact-rank hit
    assert tel.percentile("lat", 50) == 2.0
    assert tel.percentile("lat", 100) == 5.0
    assert tel.percentile("empty", 99) == 0.0


def test_rings_bank_counts_doorbells_and_snapshot_surfaces_them():
    """The ring plane's counters live in ``bank("rings")`` and ride the
    snapshot as the ``scheduler_rings`` surface (DESIGN.md §12)."""
    telemetry.reset("rings")
    sched = DistributedScheduler(Topology.parallel(1), ring_depth=2)
    x = rand((64, 128))
    desc = C.describe("MN", "MN")
    for _ in range(3):
        sched.submit(x, desc, link="link0", tenant="a")
    sched.flush()
    with telemetry.session(name="rings"):
        snap = telemetry.snapshot()
    rings = snap["surfaces"]["scheduler_rings"]
    assert rings["doorbells:link0"] == 3
    assert rings["full:link0"] == 1              # the third post blocked once
    assert rings["credits_hw:link0"] == 2        # high-water == ring depth
    assert rings["tenant_dispatch:a"] == 3


# -- snapshot + serving SLO --------------------------------------------------
ENGINE_PHASES = {"engine.admit", "engine.prefill", "engine.preempt",
                 "engine.gather", "engine.compose", "engine.decode",
                 "engine.scatter", "engine.defrag"}


def _serve_under_session(model, n_requests=3):
    from repro.serving import ContinuousBatchingEngine, uniform_stream

    cfg, params = model
    reqs = uniform_stream(cfg, n_requests, 1e-5, prompt_len=8, max_new=3,
                          seed=0)
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=2,
                                   cache_dtype=jnp.float32,
                                   capacity_pages=48)
    telemetry.reset("links")
    with telemetry.session(name="serve") as tel, \
            capture(name="serve") as tr:
        rep = eng.serve(reqs)
        snap = telemetry.snapshot()
    return eng, tel, tr, rep, snap


def test_snapshot_subsumes_surfaces_and_slo_histograms(model):
    eng, tel, tr, rep, snap = _serve_under_session(model)
    assert snap["session"] == "serve"
    # one snapshot carries all five surfaces
    for key in ("cache_stats", "agu_stats", "cfg_stats", "scheduler_links",
                "pool_stats"):
        assert key in snap["surfaces"]
    # per-link reconciliation against the ledger, through the snapshot
    got = {k[len("bytes:"):]: v
           for k, v in snap["surfaces"]["scheduler_links"].items()
           if k.startswith("bytes:") and v}
    assert got == tr.per_link_bytes()
    # SLO histograms: one TTFT sample per finished request, TBT in between
    assert snap["histograms"]["ttft_s"]["count"] == rep.n_requests
    assert snap["histograms"]["tbt_s"]["count"] \
        == rep.total_tokens - rep.n_requests
    assert rep.ttft_p99_s >= rep.ttft_p50_s >= 0.0
    assert rep.tbt_p99_s >= rep.tbt_p50_s >= 0.0
    # engine phase spans on the host clock, nested in their steps
    phases = {s.name for s in tel.spans_on("engine")}
    assert {"engine.prefill", "engine.gather", "engine.decode",
            "engine.scatter"} <= phases
    steps = [i for i, s in enumerate(tel.spans) if s.name == "engine.step"]
    assert len(steps) == eng.steps
    assert all(tel.spans[s.parent].name == "engine.step"
               for s in tel.spans if s.name in ENGINE_PHASES)


def test_engine_phases_on_the_profilers_clock(model, tmp_path):
    """Under the profiler every step is one ``engine.step`` with its phases
    nested inside, every ``sched.flush`` lies inside a phase, and the
    engine's programs carry their names (``jit_<name>``)."""
    from repro.serving import ContinuousBatchingEngine, uniform_stream

    cfg, params = model
    reqs = uniform_stream(cfg, 3, 1e-5, prompt_len=8, max_new=3, seed=0)
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=2,
                                   cache_dtype=jnp.float32, capacity_pages=48)
    eng.serve(reqs)                              # compile outside the trace
    _, host = profiled(lambda: eng.serve(reqs), tmp_path)
    steps = [(s, e) for n, s, e, _ in host if n == "engine.step"]
    phases = [(n, s, e) for n, s, e, _ in host if n in ENGINE_PHASES]
    flushes = [(s, e) for n, s, e, _ in host if n == "sched.flush"]
    assert len(steps) == eng.steps > 0
    assert {"engine.admit", "engine.prefill", "engine.gather",
            "engine.compose", "engine.decode",
            "engine.scatter"} <= {n for n, _, _ in phases}
    steps.sort()
    assert all(e <= s for (_, e), (s, _) in zip(steps, steps[1:]))
    inside = lambda s, e, spans: sum(a <= s and e <= b for a, b in spans)
    assert all(inside(s, e, steps) == 1 for _, s, e in phases)
    assert flushes and all(inside(s, e, [(a, b) for _, a, b in phases]) == 1
                           for s, e in flushes)
    # a program jitted from ``f`` runs as PjitFunction(f), module jit_f
    programs = {n[len("PjitFunction("):-1] for n, *_ in host
                if n.startswith("PjitFunction(")}
    assert {"engine_prefill", "engine_decode"} <= programs
    assert "_unknown" not in programs


def test_sched_bank_counts_batched_tasks():
    """``batched_tasks`` counts the XDMA tasks that ran inside a fused
    program, ``programs`` the XDMA programs launched; all XDMA tasks
    dispatched are the ``links`` bank's ``tasks:<resource>``.

    A flush defers every local concrete task and runs each group of equal
    descriptors as power-of-two chunks of ``jit_sched_round``: the three
    equal descriptors of round 0 run as chunks of 2 and 1 (closed when the
    compute task is picked), and the lone round-1 task as a chunk of 1, so
    all four tasks ride fused programs, three of them."""
    telemetry.reset("sched")
    links = telemetry.bank("links")
    tasks = lambda: sum(links.with_prefix("tasks:").values())
    before = tasks()
    sched = DistributedScheduler(Topology.parallel(3))
    x = rand((64, 128))
    for link in ("link0", "link1", "link2"):     # one round, fused
        sched.submit(x, C.describe("MN", "MNM8N128"), link=link)
    sched.submit(x, C.describe("MN", "MN"), link="link0")   # a round alone
    sched.submit_compute(lambda: None, cost_s=1e-6)
    sched.flush()
    sched.flush()                                # nothing left: no round
    assert telemetry.bank("sched").as_dict() == {"batched_tasks": 4,
                                                 "programs": 3}
    assert tasks() - before == 4


def test_chrome_trace_exports_chokepoints_and_engine_phases(model, tmp_path):
    import json

    eng, tel, tr, rep, snap = _serve_under_session(model)
    # add the remaining chokepoints to the same session's trace
    with telemetry.session(tel), capture(tr):
        x = rand((32, 128))
        xdma.transfer(x, C.describe("MN", "MNM8N128"))
        xdma.XDMAQueue([C.describe("MN", "MN")], name="q").run(x)
    events = (chrometrace.trace_events(tr, eng.topology)
              + chrometrace.telemetry_events(tel))
    n = chrometrace.validate_events(events)
    assert n == len(events)
    cats = {e["cat"] for e in events if e["ph"] == "X"}
    # all three movement chokepoints + engine phases are visible
    assert {"transfer", "queue", "scheduler", "engine"} <= cats
    # counter tracks for queue occupancy
    assert any(e["ph"] == "C" and e["name"].startswith("occupancy:")
               for e in events)
    path = str(tmp_path / "serving.trace.json")
    chrometrace.export(events, path)
    with open(path) as f:
        doc = json.load(f)
    assert len(doc["traceEvents"]) == len(events)


def test_validate_events_rejects_malformed():
    with pytest.raises(ValueError):
        chrometrace.validate_events([{"ph": "X", "name": "a"}])
    with pytest.raises(ValueError):
        chrometrace.validate_events([{"ph": "?", "name": "a"}])
    assert chrometrace.validate_events([]) == 0
