"""The engine's batch composition: one ``engine_compose`` program per decode
step.

The reference is the per-request eager path: for each request and paged
leaf, concatenate the request's pages, zero-pad (or cut) them to the leaf's
canonical rows, reshape and move the axes back into the leaf, add the batch
axis; then concatenate the batch per leaf.  The program must give the same
batched cache bit for bit, and the engine must make the same page
operations, in the same order, under the same labels, and serve the same
tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_multidevice
from test_engine_scatter import _CASES, _cfg

from repro.models import lm
from repro.runtime import telemetry
from repro.runtime.trace import capture
from repro.serving import (ContinuousBatchingEngine, PagedKVPool,
                           StaticBatchEngine, trace_stream, uniform_stream)
from repro.serving.continuous import _ReqState, _bucket, _from_canonical
from repro.serving.requests import Request


# ---------------------------------------------------------------------------
# the reference: the per-request eager path
# ---------------------------------------------------------------------------
def _eager_compose_leaf(eng, m, page_vals):
    """Pages -> one per-request cache leaf (batch axis restored), the
    unvalidated tail zero-filled exactly as ``init_cache`` leaves it."""
    R = eng.pool.page_rows
    have = len(page_vals) * R
    if page_vals:
        mat = jnp.concatenate(page_vals, axis=0)
        if have < m.rows:
            mat = jnp.pad(mat, ((0, m.rows - have), (0, 0)))
        else:
            mat = mat[:m.rows]
    else:
        mat = jnp.zeros((m.rows, m.cols), eng.cache_dtype)
    t_leaf = jax.tree_util.tree_leaves(eng._template)[m.index]
    nb_shape = t_leaf.shape[:m.batch_axis] + t_leaf.shape[m.batch_axis + 1:]
    return jnp.expand_dims(_from_canonical(m, mat, nb_shape), m.batch_axis)


def _eager_compose_cache(eng, active, gathered):
    """Per-request pages -> one batched decode cache, leaf by leaf."""
    t_leaves, treedef = jax.tree_util.tree_flatten(eng._template)
    out = list(t_leaves)
    for m in eng.metas:
        if m.kind == "pos":
            poss = [min(st.pos, eng.max_len) for st in active]
            out[m.index] = (jnp.asarray(poss[0], jnp.int32)
                            if len(set(poss)) == 1
                            else jnp.asarray(poss, jnp.int32))
        elif m.kind == "const":
            out[m.index] = jnp.zeros(t_leaves[m.index].shape,
                                     t_leaves[m.index].dtype)
        else:
            parts = [_eager_compose_leaf(
                eng, m, [f.result() for f in gathered[i][m.index]])
                for i in range(len(active))]
            out[m.index] = jnp.concatenate(parts, axis=m.batch_axis)
    return jax.tree_util.tree_unflatten(treedef, out)


class EagerComposeEngine(ContinuousBatchingEngine):
    """The engine with the per-request eager composition."""

    def _compose_cache(self, active, gathered):
        return _eager_compose_cache(self, active, gathered)


class _Done:
    """A page future that has landed."""

    def __init__(self, value):
        self.value = value

    def result(self):
        return self.value


# ---------------------------------------------------------------------------
# bit equality of the batched cache
# ---------------------------------------------------------------------------
def _random_gathered(eng, group, dtype, seed):
    """Each request's pages of every paged leaf, every value random."""
    key = jax.random.PRNGKey(seed)
    gathered = []
    for st in group:
        g = {}
        for m in eng._paged:
            n = eng._pages_at(m, st.pos)
            key, sub = jax.random.split(key)
            pages = jax.random.normal(sub, (n, eng.pool.page_rows, m.cols),
                                      jnp.float32).astype(dtype)
            g[m.index] = [_Done(p) for p in pages]
        gathered.append(g)
    return gathered


def _assert_cache_bitwise(got, want):
    got_l, got_t = jax.tree_util.tree_flatten(got)
    want_l, want_t = jax.tree_util.tree_flatten(want)
    assert got_t == want_t
    for i, (a, b) in enumerate(zip(got_l, want_l)):
        assert a.shape == b.shape and a.dtype == b.dtype, i
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), i


def _group(plens, max_len):
    group = [_ReqState(Request(rid=i, arrival_s=0.0,
                               tokens=np.zeros(1, np.int32), max_new=1))
             for i in range(len(plens))]
    for st, p in zip(group, plens):
        st.pos = min(p, max_len)
    return group


@pytest.mark.parametrize("batch", ["one", "ragged-full"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_compose_program_cache_equals_eager_path_bitwise(case, batch):
    make_cfg, R, max_len, dtype, plens = _CASES[case]
    cfg = make_cfg()
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    if batch == "one":
        plens = plens[:1]
    else:
        # the case's rows plus one request at max_len: every page of a full
        # leaf, next to rows with few
        plens = tuple(plens) + (max_len,)
    eng = ContinuousBatchingEngine(cfg, params, max_len, max_batch=len(plens),
                                   cache_dtype=dtype,
                                   pool=PagedKVPool(512, R))
    assert any(m.kind == ("state" if case.startswith("state") else "seq")
               for m in eng._paged)
    group = _group(plens, max_len)
    gathered = _random_gathered(eng, group, dtype, seed=len(plens))
    got = eng._compose_cache(group, gathered)
    _assert_cache_bitwise(got, _eager_compose_cache(eng, group, gathered))


def test_full_batches_need_bucket_padding():
    """The ragged full batches above pad some leaf's page tuple."""
    padded = 0
    for case, (make_cfg, R, max_len, dtype, plens) in _CASES.items():
        cfg = make_cfg()
        params = jax.eval_shape(
            lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
        eng = ContinuousBatchingEngine(cfg, params, max_len,
                                       cache_dtype=dtype,
                                       pool=PagedKVPool(512, R))
        for m in eng._paged:
            n = sum(eng._pages_at(m, min(p, max_len))
                    for p in tuple(plens) + (max_len,))
            padded += _bucket(n, bits=3) > n
    assert padded >= 3


def test_compose_bucket_pads_at_most_a_quarter():
    assert [_bucket(n, bits=3) for n in (0, 1, 8, 9, 13, 17, 100, 513)] == \
        [0, 1, 8, 10, 14, 20, 112, 640]
    for n in range(1, 4096):
        assert n <= _bucket(n, bits=3) <= n + n // 4


# ---------------------------------------------------------------------------
# the mechanism: programs, compiles, page operations, tokens
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    import dataclasses
    cfg = dataclasses.replace(_cfg("qwen3_1p7b"), dtype=jnp.float32)
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


def _ragged(cfg):
    return trace_stream(cfg, [(0.0, 4, 4), (0.0, 4, 3), (0.0, 8, 5),
                              (10e-6, 8, 3), (30e-6, 4, 5)], seed=5)


def test_one_compose_program_per_decode_step(model):
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=4,
                                   cache_dtype=jnp.float32)
    decodes = [0]
    inner = eng._decode

    def counted(*a):
        decodes[0] += 1
        return inner(*a)
    eng._decode = counted
    before = telemetry.bank("serving").get("compose_programs")
    rep = eng.serve(_ragged(cfg))
    n = telemetry.bank("serving").get("compose_programs") - before
    assert rep.n_requests == 5
    assert decodes[0] > 0 and n == decodes[0]


def test_compose_compiles_within_batches_times_buckets(model):
    """A serve compiles at most one compose program per (batch size, page
    buckets) it meets, and a second serve none."""
    cfg, params = model
    eng = ContinuousBatchingEngine(cfg, params, max_len=24, max_batch=4,
                                   cache_dtype=jnp.float32,
                                   pool=PagedKVPool(64, 16))
    seen = set()
    inner = eng._compose_args

    def noted(gathered):
        args = inner(gathered)
        seen.add((len(gathered), tuple(len(p) for p in args[0])))
        return args
    eng._compose_args = noted
    eng.serve(_ragged(cfg))
    compiled = eng._compose_program._cache_size()
    batches = {b for b, _ in seen}
    buckets = {n for _, n in seen}
    assert len(seen) > 1
    assert compiled <= len(batches) * len(buckets)
    assert compiled == len(seen)
    eng.serve(_ragged(cfg))
    assert eng._compose_program._cache_size() == compiled


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
def test_page_operations_and_tokens_equal_eager_compose_engine(
        model, engine, arch, stream):
    """Same requests through the one-program engine and the eager one: the
    same pool counters, page ids, labels and links in the same order, and
    the same tokens."""
    if arch == "qwen3_1p7b":
        cfg, params = model
    else:
        import dataclasses
        cfg = dataclasses.replace(_cfg(arch), dtype=jnp.float32)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
    if stream == "ragged":
        reqs = trace_stream(cfg, [(0.0, 4, 4), (10e-6, 8, 3), (30e-6, 5, 6)],
                            seed=3)
        kw = {"max_batch": 3, "capacity_pages": 48}
    else:
        reqs = uniform_stream(cfg, 3, 0.0, prompt_len=8, max_new=4)
        kw = {"max_batch": 3, "pool": None}
    cls = StaticBatchEngine if engine == "static" else ContinuousBatchingEngine

    class Eager(EagerComposeEngine, cls):
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


def test_compose_program_on_a_sharded_cache():
    """Pages cut from a cache sharded over a mesh (batch rows over four
    devices, KV heads over two) compose into the same cache as unsharded
    pages do, and that cache holds the sharded cache's pages."""
    out = run_multidevice('''
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import lm
from repro.serving import ContinuousBatchingEngine, PagedKVPool
from repro.serving.continuous import _ReqState
from repro.serving.requests import Request

class Done:
    def __init__(self, v):
        self.v = v
    def result(self):
        return self.v

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
plens = (5, 24, 11, 17)
group = [_ReqState(Request(rid=i, arrival_s=0.0, tokens=np.zeros(p, np.int32),
                           max_new=1)) for i, p in enumerate(plens)]
for st, p in zip(group, plens):
    st.pos = p
plan = eng._plan_scatter(group)

def gathered(pages):
    g = [{m.index: [] for m in eng._paged} for _ in group]
    for (m, i, j, _), page in zip(plan, pages):
        g[i][m.index].append(Done(page))
    return g
got = eng._compose_cache(group, gathered(eng._scatter_pages(sharded, plan)))
want = eng._compose_cache(group, gathered(eng._scatter_pages(cache, plan)))
assert len(jax.tree.leaves(sharded)[0].sharding.device_set) == 8
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
# the valid prefix of every row is the sharded cache's
for m in eng._paged:
    leaf = np.asarray(jax.tree.leaves(got)[m.index])
    src = np.asarray(jax.tree.leaves(sharded)[m.index])
    for i, p in enumerate(plens):
        a = np.take(np.take(leaf, i, m.batch_axis), range(p), m.seq_axis_nb())
        b = np.take(np.take(src, i, m.batch_axis), range(p), m.seq_axis_nb())
        assert a.tobytes() == b.tobytes()
print("LEAVES", len(jax.tree.leaves(got)))
''')
    assert "LEAVES" in out
