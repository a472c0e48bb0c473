"""The engine's page scatter: one ``engine_scatter`` program per prefill
length group and per decode step.

The reference is the per-request eager path: cut each request's B=1 row
from the batched cache, squeeze it, build its whole canonical (rows, cols)
matrix, then slice (or zero-pad) every page to store.  The program must
give the same pages bit for bit, and the engine must make the same page
operations, in the same order, under the same labels.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_multidevice

from repro import configs
from repro.models import lm
from repro.runtime import telemetry
from repro.runtime.trace import capture
from repro.serving import (ContinuousBatchingEngine, PagedKVPool,
                           StaticBatchEngine, trace_stream, uniform_stream)
from repro.serving.continuous import _ReqState, _bucket
from repro.serving.requests import Request


# ---------------------------------------------------------------------------
# the reference: the per-request eager path
# ---------------------------------------------------------------------------
def _to_canonical(meta, leaf_nb):
    """Per-request leaf (batch axis removed) -> its whole canonical matrix,
    token axis outermost for sequence leaves."""
    if meta.kind == "seq":
        leaf_nb = jnp.moveaxis(leaf_nb, meta.seq_axis_nb(), 0)
    return leaf_nb.reshape(meta.rows, meta.cols)


def _eager_page(meta, R, cache, row, j):
    """Page ``j`` of batch row ``row``, as the eager path cut it."""
    leaf = jax.tree_util.tree_leaves(cache)[meta.index]
    b1 = jax.lax.dynamic_slice_in_dim(leaf, row, 1, axis=meta.batch_axis)
    mat = _to_canonical(meta, jnp.squeeze(b1, axis=meta.batch_axis))
    if (j + 1) * R <= meta.rows:
        return jax.lax.dynamic_slice_in_dim(mat, j * R, R)
    return jnp.pad(mat[j * R:], ((0, (j + 1) * R - meta.rows), (0, 0)))


class EagerScatterEngine(ContinuousBatchingEngine):
    """The engine with the per-request eager scatter: for each request in
    turn, each paged leaf, each page from the first dirty one, allocate if
    new and store the eagerly cut page."""

    def _scatter(self, group, cache, *, deps=(), written=None,
                 label="store"):
        R = self.pool.page_rows
        dtype_name = str(jnp.dtype(self.cache_dtype))
        for i, st in enumerate(group):
            for m in self.metas:
                if m.kind in ("pos", "const"):
                    continue
                plist = st.pages.setdefault(m.index, [])
                want = self._pages_at(m, st.pos)
                first = 0
                if m.kind == "seq" and written is not None:
                    first = (min(written[i], self.max_len - 1) * m.rpt) // R
                for j in range(first, want):
                    if j >= len(plist):
                        plist.append(self.pool.alloc(m.cols, dtype_name))
                    self.pool.store(plist[j], _eager_page(m, R, cache, i, j),
                                    deps=deps, label=label)


# ---------------------------------------------------------------------------
# bit equality of the pages
# ---------------------------------------------------------------------------
def _cfg(arch, **kw):
    return dataclasses.replace(configs.smoke_config(arch), **kw)


# (config, page_rows, max_len, cache dtype, prompt lengths of the batch)
_CASES = {
    # rows per token 4 divides 32 rows; the last page of a 22-token leaf
    # (88 rows) is padded
    "seq-divides": (lambda: _cfg("qwen3_1p7b"), 32, 22, jnp.float32,
                    (5, 22, 9)),
    # the bench geometry: a page of 4 whole tokens, a bf16 cache
    "seq-bench-bf16": (lambda: _cfg("qwen3_1p7b"), 16, 24, jnp.bfloat16,
                       (7, 16)),
    # 3 periods x 2 KV heads = 6 rows a token: pages straddle tokens
    "seq-straddles": (lambda: _cfg("qwen3_1p7b", n_periods=3), 32, 24,
                      jnp.float32, (11, 24, 3, 16, 5)),
    # one row: token 5 (rows 30-35) straddles the first two pages
    "seq-one-row": (lambda: _cfg("qwen3_1p7b", n_periods=3), 32, 24,
                    jnp.float32, (5,)),
    # straddling, max_len 21: the window moves back at the sequence's end
    "seq-straddles-end": (lambda: _cfg("qwen3_1p7b", n_periods=3), 40, 21,
                          jnp.float32, (21, 20, 13)),
    # K stored transposed: the sequence axis is the leaf's last
    "seq-xdma-layout": (lambda: _cfg("qwen3_1p7b", n_periods=3,
                                     xdma_cache=True), 32, 24, jnp.float32,
                        (9, 24, 1)),
    # a hybrid: attention pages plus whole SSM states (6 and 256 rows)
    "state-mamba": (lambda: _cfg("jamba_1p5_large_398b"), 32, 24,
                    jnp.float32, (4, 17, 24)),
    # rolling windows shorter than max_len page whole, one with batch axis 0
    "state-window": (lambda: _cfg("gemma3_27b"), 32, 24, jnp.float32,
                     (3, 24)),
}


def _random_cache(cfg, B, max_len, dtype, seed):
    """A batched cache of the engine's shapes, every paged value random."""
    shapes = jax.eval_shape(lambda: lm.init_cache(cfg, B, max_len, dtype))
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = [jax.random.normal(k, l.shape, jnp.float32).astype(l.dtype)
           if jnp.issubdtype(l.dtype, jnp.floating)
           else jnp.zeros(l.shape, l.dtype)
           for k, l in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tree, out)


def _assert_pages_bitwise(eng, cache, plan):
    R = eng.pool.page_rows
    got = eng._scatter_pages(cache, plan)
    assert len(got) == len(plan)
    for (m, i, j, _), page in zip(plan, got):
        want = _eager_page(m, R, cache, i, j)
        assert page.shape == want.shape == (R, m.cols)
        assert page.dtype == want.dtype
        assert np.asarray(page).tobytes() == np.asarray(want).tobytes(), (
            m.index, i, j)


@pytest.mark.parametrize("phase", ["prefill", "decode"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_scatter_program_pages_equal_eager_path_bitwise(case, phase):
    make_cfg, R, max_len, dtype, plens = _CASES[case]
    cfg = make_cfg()
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    eng = ContinuousBatchingEngine(cfg, params, max_len, max_batch=8,
                                   cache_dtype=dtype,
                                   pool=PagedKVPool(512, R))
    assert any(m.kind == ("state" if case.startswith("state") else "seq")
               for m in eng._paged)
    group = [_ReqState(Request(rid=i, arrival_s=0.0,
                               tokens=np.zeros(p, np.int32), max_new=1))
             for i, p in enumerate(plens)]
    for st, p in zip(group, plens):
        st.pos = p
    cache = _random_cache(cfg, len(group), max_len, dtype, seed=len(plens))
    plan = eng._plan_scatter(group)
    if phase == "decode":
        # the step after: each row wrote its slot at pos, one dirty page
        # per sequence leaf (two where a token straddles pages)
        written = [st.pos - 1 if st.pos == max_len else st.pos
                   for st in group]
        for st in group:
            st.pos = min(st.pos + 1, max_len)
        plan = eng._plan_scatter(group, written)
        dirty = {}
        for m, i, j, _ in plan:
            if m.kind == "seq":
                dirty.setdefault((m.index, i), []).append(j)
        assert len(dirty) == len(group) * sum(
            m.kind == "seq" for m in eng._paged)
        assert all(1 <= len(js) <= 2 for js in dirty.values())
    _assert_pages_bitwise(eng, cache, plan)


def test_bucket_rounds_up_to_powers_of_two():
    assert [_bucket(n) for n in (0, 1, 2, 3, 4, 5, 8, 9, 100)] == \
        [0, 1, 2, 4, 4, 8, 8, 16, 128]


# ---------------------------------------------------------------------------
# the mechanism: programs, compiles, page operations, tokens
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    cfg = _cfg("qwen3_1p7b", dtype=jnp.float32)
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


def _count_calls(eng, name):
    calls = [0]
    inner = getattr(eng, name)

    def counted(*a):
        calls[0] += 1
        return inner(*a)
    setattr(eng, name, counted)
    return calls


def test_one_scatter_program_per_decode_step_and_prefill_group(model):
    cfg, params = model
    stream = trace_stream(cfg, [(0.0, 4, 4), (0.0, 4, 3), (0.0, 8, 5),
                                (10e-6, 8, 3), (30e-6, 4, 5)], seed=5)
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=4,
                                   cache_dtype=jnp.float32)
    prefills = _count_calls(eng, "_prefill")
    decodes = _count_calls(eng, "_decode")
    before = telemetry.bank("serving").get("scatter_programs")
    rep = eng.serve(stream)
    n = telemetry.bank("serving").get("scatter_programs") - before
    assert rep.n_requests == 5
    assert prefills[0] >= 3                 # two lengths at 0, more later
    assert n == decodes[0] + prefills[0]


def test_second_serve_compiles_nothing(model):
    cfg, params = model
    reqs = uniform_stream(cfg, 3, 5e-6, prompt_len=8, max_new=4)
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=2,
                                   cache_dtype=jnp.float32,
                                   pool=PagedKVPool(8, 32))
    first = eng.serve(reqs)
    compiles = []

    def on_event(name, secs, **_):
        if name.endswith("backend_compile_duration"):
            compiles.append(name)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        again = eng.serve(reqs)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []
    assert first.pool_stats["stores"] > 0
    for r in reqs:
        np.testing.assert_array_equal(again.tokens[r.rid], first.tokens[r.rid])


def _served(engine_cls, cfg, params, reqs, **kw):
    eng = engine_cls(cfg, params, max_len=24, cache_dtype=jnp.float32, **kw)
    with capture(name="serve") as tr:
        rep = eng.serve(reqs)
    events = [(e.label, e.link) for e in tr.labelled("page:")]
    return rep, events


@pytest.mark.parametrize("engine,arch,stream", [
    ("continuous", "qwen3_1p7b", "ragged"),
    ("continuous", "qwen3_1p7b", "preempting"),
    ("static", "qwen3_1p7b", "ragged"),
    ("continuous", "jamba_1p5_large_398b", "ragged"),
])
def test_page_operations_and_tokens_equal_eager_engine(model, engine, arch,
                                                       stream):
    """Same requests through the one-program engine and the eager one: the
    same pool counters, page ids, labels and links in the same order, and
    the same tokens."""
    if arch == "qwen3_1p7b":
        cfg, params = model
    else:
        cfg = _cfg(arch, dtype=jnp.float32)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
    if stream == "ragged":
        reqs = trace_stream(cfg, [(0.0, 4, 4), (10e-6, 8, 3), (30e-6, 5, 6)],
                            seed=3)
        kw = {"max_batch": 3, "capacity_pages": 48}
    else:
        reqs = uniform_stream(cfg, 3, 0.0, prompt_len=8, max_new=4)
        kw = {"max_batch": 3, "pool": None}
    cls = StaticBatchEngine if engine == "static" else ContinuousBatchingEngine

    class Eager(EagerScatterEngine, cls):
        pass
    runs = []
    for c in (cls, Eager):
        if stream == "preempting":
            kw["pool"] = PagedKVPool(7, 32)
        runs.append(_served(c, cfg, params, reqs, **kw))
    (rep, events), (ref, ref_events) = runs
    if stream == "preempting":
        assert rep.preemptions > 0
    assert rep.pool_stats == ref.pool_stats
    assert events == ref_events and events
    assert rep.steps == ref.steps
    for r in reqs:
        np.testing.assert_array_equal(rep.tokens[r.rid], ref.tokens[r.rid])


def test_scatter_program_on_a_sharded_cache():
    """Under a mesh the program takes the sharded leaves (batch rows over
    four devices, KV heads over two) and cuts the same pages."""
    out = run_multidevice('''
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import lm
from repro.serving import ContinuousBatchingEngine, PagedKVPool
from repro.serving.continuous import _ReqState
from repro.serving.requests import Request
cfg = dataclasses.replace(configs.smoke_config("qwen3_1p7b"), n_periods=3)
params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
eng = ContinuousBatchingEngine(cfg, params, 24, max_batch=4,
                               cache_dtype=jnp.float32, pool=PagedKVPool(256, 32))
mesh = jax.make_mesh((4, 2), ("b", "h"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cache = lm.init_cache(cfg, 4, 24, jnp.float32)
keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
cache = jax.tree.map(lambda a: jax.random.normal(next(keys), a.shape)
                     if a.ndim == 5 else a, cache)
spec = NamedSharding(mesh, P(None, "b", None, "h", None))
sharded = jax.tree.map(lambda a: jax.device_put(a, spec) if a.ndim == 5 else a,
                       cache)
group = [_ReqState(Request(rid=i, arrival_s=0.0, tokens=np.zeros(p, np.int32),
                           max_new=1)) for i, p in enumerate((5, 24, 11, 17))]
for st, p in zip(group, (5, 24, 11, 17)):
    st.pos = p
plan = eng._plan_scatter(group)
got = eng._scatter_pages(sharded, plan)
want = eng._scatter_pages(cache, plan)
assert len(jax.tree.leaves(sharded)[0].sharding.device_set) == 8
assert all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
           for a, b in zip(got, want))
print("PAGES", len(got))
''')
    assert "PAGES" in out
