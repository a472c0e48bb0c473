"""The unified xdma.transfer() surface: descriptor-only dispatch for all four
movement kinds, CFG-cache (trace-once) semantics, queue ordering, endpoint
back-compat, and parity with the pre-refactor entry points."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multidevice
from repro import core as C
from repro.core import xdma
from repro.core.descriptor import Endpoint, XDMADescriptor


def rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), dtype)


# -- local movements: parity with the pre-refactor functions -----------------
@pytest.mark.parametrize("src,dst,plugins", [
    ("MN", "MNM8N128", ()),
    ("MN", "MNM16N128", (C.RMSNormPlugin(),)),
    ("MNM8N128", "MN", (C.Transpose(),)),
    ("MNM16N128", "MNM16N128", (C.Transpose(),)),
])
def test_transfer_local_fused_parity(src, dst, plugins):
    dtype = jnp.bfloat16 if "16" in src + dst else jnp.float32
    x = rand((256, 512), dtype=dtype)
    if src != "MN":
        x = C.by_name(src).from_logical(x)
    desc = C.describe(src, dst, *plugins)
    np.testing.assert_array_equal(np.asarray(xdma.transfer(x, desc)),
                                  np.asarray(C.xdma_copy(x, desc)))


def test_transfer_local_pallas_parity():
    x = rand((256, 512))
    d_pallas = C.describe("MN", "MNM8N128", backend="pallas", d_buf=5)
    d_fused = C.describe("MN", "MNM8N128")
    np.testing.assert_array_equal(np.asarray(xdma.transfer(x, d_pallas)),
                                  np.asarray(C.xdma_copy(x, d_fused)))


def test_transfer_quantized_payload():
    x = rand((64, 256))
    desc = C.describe("MN", "MNM32N128", C.Quantize())
    out = xdma.transfer(x, desc)
    ref = C.xdma_copy(x, desc)
    assert out.values.dtype == jnp.int8 and out.values.shape == ref.values.shape
    # jit-fused vs eager amax differs by float-rounding ulps; compare payloads
    np.testing.assert_allclose(np.asarray(out.scales), np.asarray(ref.scales),
                               rtol=1e-6)
    deq = C.Dequantize(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(deq(C.QTensor(C.by_name("MNM32N128").to_logical(out.values),
                                 out.scales))),
        np.asarray(deq(C.QTensor(C.by_name("MNM32N128").to_logical(ref.values),
                                 ref.scales))),
        rtol=1e-5, atol=1e-6)


# -- descriptor semantics ----------------------------------------------------
def test_legacy_descriptor_spelling_maps_to_endpoints():
    d = XDMADescriptor(src_layout=C.MN, dst_layout=C.MNM8N128,
                       plugins=(C.Transpose(),))
    assert d.src == Endpoint.local(C.MN)
    assert d.dst.layout == C.MNM8N128
    assert d.pre == d.plugins and d.post == ()
    assert d.movement == "local" and not d.is_remote
    # plugins attribute is always the full pre+post cascade
    d2 = C.describe("MN", "MN", pre=(C.Scale(2.0),), post=(C.BiasAdd(1.0),))
    assert [p.name for p in d2.plugins] == ["scale", "bias_add"]


def test_describe_rejects_double_plugin_spelling():
    with pytest.raises(ValueError):
        C.describe("MN", "MN", C.Scale(2.0), pre=(C.Scale(2.0),))
    with pytest.raises(ValueError):     # mixed legacy+endpoint spelling
        XDMADescriptor(plugins=(C.Scale(2.0),), post=(C.BiasAdd(1.0),))


def test_remote_endpoint_classification_and_validation():
    peer = Endpoint.peer("x", [(0, 1), (1, 0)])
    assert C.describe(C.MN, peer).movement == "peer"
    a2a = Endpoint.all_to_all("x", split_axis=0, concat_axis=1)
    assert C.describe(C.MN, a2a).movement == "all_to_all"
    red = Endpoint.reduce("x", axis_size=8)
    assert C.describe(C.MN, red).movement == "reduce"
    with pytest.raises(ValueError):
        Endpoint(kind="peer", axis="x")            # no perm
    with pytest.raises(ValueError):
        Endpoint(kind="all_to_all")                # no axis
    with pytest.raises(ValueError):
        XDMADescriptor(src=peer, dst=a2a)          # two remote ends
    with pytest.raises(ValueError):
        C.describe(C.MN, peer, backend="pallas")   # pallas is local-only


def test_shape_dtype_propagate_through_both_hosts():
    d = C.describe("MN", "MN", pre=(C.Transpose(),), post=(C.Cast(jnp.bfloat16),))
    assert d.out_logical_shape((4, 8)) == (8, 4)
    assert d.out_dtype(jnp.float32) == jnp.bfloat16
    assert d.dst_pattern((4, 8)).bounds == (8, 4)


def test_channels_exposed_through_describe():
    d = C.describe("MN", "MNM8N128", channels=4, d_buf=5)
    assert d.channels == 4 and "N_C=4" in d.summary()
    lanes = d.src_patterns((256, 512))
    assert len(lanes) == 4
    assert sum(p.num_elements for p in lanes) == 256 * 512
    assert lanes[0].bounds == (64, 512)
    assert [p.base for p in lanes] == [c * 64 * 512 for c in range(4)]
    with pytest.raises(ValueError):
        d.validate((255, 512))          # rows not divisible by N_C
    with pytest.raises(ValueError):
        C.describe("MN", "MN", channels=0).validate((8, 8))
    with pytest.raises(ValueError):     # lane rows must align to src tiles
        C.describe("MNM8N128", "MN", channels=4).src_patterns((16, 128))


@pytest.mark.parametrize("src", ["MN", "MNM8N128"])
def test_channel_lanes_partition_the_address_space(src):
    """The N_C lane generators together cover exactly the full pattern."""
    d = C.describe(src, "MN", channels=4)
    full = set(d.src_pattern((32, 128)).addresses().tolist())
    lane_addrs = [p.addresses().tolist() for p in d.src_patterns((32, 128))]
    union = set()
    for a in lane_addrs:
        assert union.isdisjoint(a)      # lanes never alias
        union |= set(a)
    assert union == full


# -- the CFG cache: "config phase happens once" ------------------------------
class _TraceCounter(C.Plugin):
    name = "trace_counter"

    def __init__(self):
        self.traces = []

    def __call__(self, x):
        self.traces.append(x.shape)
        return x


def test_cfg_cache_hit_counting_and_trace_once():
    counter = _TraceCounter()
    desc = C.describe("MN", "MNM8N128", counter)
    xdma.clear_cache()
    x = rand((64, 128))
    for _ in range(5):
        xdma.transfer(x, desc)
    stats = xdma.cache_stats()
    assert stats.misses == 1 and stats.hits == 4
    assert len(counter.traces) == 1            # CFG phase happened once
    # a new shape retraces (new executable) but reuses the cached lowering
    xdma.transfer(rand((128, 128)), desc)
    assert len(counter.traces) == 2
    assert xdma.cache_stats().misses == 1


def test_distinct_descriptors_get_distinct_cfg_entries():
    xdma.clear_cache()
    x = rand((64, 128))
    xdma.transfer(x, C.describe("MN", "MNM8N128"))
    xdma.transfer(x, C.describe("MN", "MNM16N128", C.Cast(jnp.bfloat16)))
    assert xdma.cache_stats().misses == 2


def test_structurally_equal_descriptors_share_one_cfg_entry():
    """Plugins hash structurally (frozen dataclasses), so two independently
    built but identical descriptors run one CFG phase, not two."""
    xdma.clear_cache()
    x = rand((64, 128))
    xdma.transfer(x, C.describe("MN", "MNM8N128", C.Scale(2.0)))
    xdma.transfer(x, C.describe("MN", "MNM8N128", C.Scale(2.0)))
    stats = xdma.cache_stats()
    assert stats.misses == 1 and stats.hits == 1
    # a different parameterization is a different CFG
    xdma.transfer(x, C.describe("MN", "MNM8N128", C.Scale(3.0)))
    assert xdma.cache_stats().misses == 2


def test_cfg_cache_lru_eviction_is_bounded_and_counted():
    d1 = C.describe("MN", "MNM8N128")
    d2 = C.describe("MN", "MN", C.Scale(2.0))
    d3 = C.describe("MN", "MN", C.BiasAdd(1.0))
    x = rand((64, 128))
    old_capacity = xdma.cache_capacity()
    xdma.clear_cache()
    try:
        xdma.set_cache_capacity(2)
        xdma.transfer(x, d1)
        xdma.transfer(x, d2)
        xdma.transfer(x, d1)                    # refresh d1: d2 becomes LRU
        xdma.transfer(x, d3)                    # evicts d2
        stats = xdma.cache_stats()
        assert stats.size == 2 and stats.evictions == 1
        xdma.transfer(x, d1)                    # survived (was refreshed)
        assert xdma.cache_stats().hits == 2
        xdma.transfer(x, d2)                    # was evicted: a fresh miss
        assert xdma.cache_stats().misses == 4
        assert xdma.cache_stats().evictions == 2    # ... evicting d3 in turn
        # shrinking the capacity evicts immediately
        xdma.set_cache_capacity(1)
        assert xdma.cache_stats().size == 1
        with pytest.raises(ValueError):
            xdma.set_cache_capacity(0)
    finally:
        xdma.set_cache_capacity(old_capacity)
        xdma.clear_cache()


# -- XDMAQueue: the Controller's in-order task dispatch ----------------------
def test_queue_ordering_semantics():
    x = rand((8, 128))
    q = C.XDMAQueue([C.describe("MN", "MN", C.Scale(2.0)),
                     C.describe("MN", "MN", C.BiasAdd(1.0))])
    q_rev = C.XDMAQueue([C.describe("MN", "MN", C.BiasAdd(1.0)),
                         C.describe("MN", "MN", C.Scale(2.0))])
    np.testing.assert_allclose(np.asarray(q.run(x)), np.asarray(x) * 2 + 1,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(q_rev.run(x)), (np.asarray(x) + 1) * 2,
                               rtol=1e-6)


def test_queue_fused_run_matches_per_task_dispatch():
    x = rand((256, 512))
    descs = [C.describe("MN", "MNM8N128", C.RMSNormPlugin()),
             C.describe("MNM8N128", "MN", C.Transpose())]
    q = C.XDMAQueue(descs)
    fused = q.run(x)
    step = x
    for i in range(len(q)):
        step = q.run_task(step, i)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(step))
    assert fused.shape == (512, 256)


def test_queue_submit_order_and_contracts():
    q = C.XDMAQueue(name="t")
    assert q.run(rand((4, 8))) is not None      # empty queue = identity
    i0 = q.submit(C.describe("MN", "MN", C.Transpose()))
    i1 = q.submit(C.describe("MN", "MN", C.Cast(jnp.bfloat16)))
    assert (i0, i1) == (0, 1) and len(q) == 2 and q.is_local
    assert q.out_logical_shape((4, 8)) == (8, 4)
    assert q.out_dtype(jnp.float32) == jnp.bfloat16
    with pytest.raises(TypeError):
        q.submit("not-a-descriptor")


def test_queue_empty_run_is_the_identity():
    q = C.XDMAQueue(name="empty")
    x = rand((4, 8))
    assert q.run(x) is x                        # no task, no copy, no trace
    assert q.out_logical_shape((4, 8)) == (4, 8)
    assert q.out_dtype(jnp.bfloat16) == jnp.bfloat16


def test_queue_run_task_with_interleaved_compute_matches_fused_run():
    """Dispatching task-at-a-time with compute between tasks (the MoE
    dispatch -> FFN -> return shape) is bit-identical to the fused chain."""
    from jax import lax
    x = rand((256, 512))
    q = C.XDMAQueue([C.describe("MN", "MNM8N128", C.RMSNormPlugin()),
                     C.describe("MNM8N128", "MN", C.Transpose()),
                     C.describe("MN", "MN", C.Scale(0.5))])
    step = x
    for i in range(len(q)):
        step = q.run_task(step, i)
        # value-preserving interleaved "compute" that XLA cannot fuse away
        step = lax.optimization_barrier(step)
        jax.block_until_ready(step)
    np.testing.assert_array_equal(np.asarray(step), np.asarray(q.run(x)))


def test_queue_mixed_local_remote_falls_back_to_unfused_chain():
    peer = Endpoint.peer("x", tuple((i, (i + 1) % 8) for i in range(8)))
    q = C.XDMAQueue([C.describe("MN", "MN", C.Scale(2.0)),
                     C.describe(C.MN, peer)], name="mixed")
    assert not q.is_local                       # remote task: no fused jit
    out = run_multidevice(_REMOTE_PRELUDE + """
x = jnp.asarray(np.random.default_rng(5).standard_normal((8, 16, 128)), jnp.float32)
perm = tuple((i, (i+1) % 8) for i in range(8))
q = C.XDMAQueue([C.describe('MN', 'MN', C.Scale(2.0)),
                 C.describe(C.MN, Endpoint.peer('x', perm))], name='mixed')
assert not q.is_local
run = jax.shard_map(lambda xs: q.run(xs), mesh=mesh, in_specs=PS('x'),
                    out_specs=PS('x'), check_vma=False)(x)
def chain(xs):
    v = xs
    for i in range(len(q)):
        v = q.run_task(v, i)
    return v
stepped = jax.shard_map(chain, mesh=mesh, in_specs=PS('x'),
                        out_specs=PS('x'), check_vma=False)(x)
np.testing.assert_array_equal(np.asarray(run), np.asarray(stepped))
np.testing.assert_allclose(np.asarray(run),
                           np.asarray(jnp.roll(2.0 * x, 1, axis=0)),
                           rtol=1e-6)
print('OK')
""")
    assert "OK" in out


# -- serving + data call sites ride the new surface --------------------------
def test_kv_roundtrip_queue_matches_store_then_load():
    from repro.serving import transfer as T
    kv = rand((2, 64, 4, 32))
    mat = kv.reshape(2, 64, 128)
    q = T.kv_roundtrip_queue(jnp.float32)
    out = q.run(mat)
    ref = T.kv_load_transposed(T.kv_prefill_store(kv))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_stage_batch_casts_floats_only():
    from repro.data.pipeline import stage_batch
    batch = {"tokens": np.arange(12, dtype=np.int32).reshape(3, 4),
             "embeds": np.ones((3, 4, 8), np.float32)}
    out = stage_batch(batch, jnp.bfloat16)
    assert out["tokens"].dtype == jnp.int32
    assert out["embeds"].dtype == jnp.bfloat16


# -- remote movements: parity under shard_map (subprocess mesh) --------------
_REMOTE_PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as PS
from repro import core as C
from repro.core import xdma
from repro.core.descriptor import Endpoint
mesh = jax.make_mesh((8,), ('x',),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)
"""


def test_transfer_peer_parity_with_xdma_ppermute():
    out = run_multidevice(_REMOTE_PRELUDE + """
x = jnp.asarray(np.random.default_rng(2).standard_normal((8, 16, 128)), jnp.float32)
perm = tuple((i, (i+1) % 8) for i in range(8))
desc = C.describe(Endpoint.local(C.MN), Endpoint.peer('x', perm),
                  pre=(C.Quantize(),), post=(C.Dequantize(jnp.float32),))
new = jax.shard_map(lambda xs: xdma.transfer(xs, desc), mesh=mesh,
                    in_specs=PS('x'), out_specs=PS('x'), check_vma=False)(x)
old = jax.shard_map(lambda xs: C.xdma_ppermute(xs, 'x', list(perm),
                                                  pre=[C.Quantize()],
                                                  post=[C.Dequantize(jnp.float32)]),
                       mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                       check_vma=False)(x)
np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
np.testing.assert_allclose(np.asarray(new), np.asarray(jnp.roll(x, 1, axis=0)),
                           rtol=0.02, atol=0.02)
print('OK')
""")
    assert "OK" in out


def test_transfer_all_to_all_parity():
    out = run_multidevice(_REMOTE_PRELUDE + """
x = jnp.asarray(np.random.default_rng(3).standard_normal((8, 8, 4, 16)), jnp.float32)
desc = C.describe(Endpoint.local(C.MN), Endpoint.all_to_all('x', 0, 1))
new = jax.shard_map(lambda xs: xdma.transfer(xs[0], desc)[None],
                       mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                       check_vma=False)(x)
old = jax.shard_map(lambda xs: C.xdma_all_to_all(xs[0], 'x',
                                                    split_axis=0, concat_axis=1)[None],
                       mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                       check_vma=False)(x)
np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
print('OK')
""")
    assert "OK" in out


def test_transfer_reduce_parity_with_compressed_psum():
    out = run_multidevice(_REMOTE_PRELUDE + """
g = jnp.asarray(np.random.default_rng(1).standard_normal((8, 1000)), jnp.float32)
desc = C.describe(Endpoint.local(C.MN), Endpoint.reduce('x', axis_size=8),
                  pre=(C.Quantize(),), post=(C.Dequantize(jnp.float32),))
new = jax.shard_map(lambda gs: xdma.transfer(gs[0], desc)[None],
                       mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                       check_vma=False)(g)
old = jax.shard_map(lambda gs: C.compressed_psum(gs[0], 'x', 8)[None],
                       mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                       check_vma=False)(g)
np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
rel = float(jnp.abs(new[0] - g.sum(0)).max() / jnp.abs(g.sum(0)).max())
assert rel < 0.02, rel
# extra host plugins compose around the wire codec (Scale on pre host)
desc2 = C.describe(Endpoint.local(C.MN), Endpoint.reduce('x', axis_size=8),
                   pre=(C.Scale(2.0), C.Quantize()),
                   post=(C.Dequantize(jnp.float32),))
scaled = jax.shard_map(lambda gs: xdma.transfer(gs[0], desc2)[None],
                          mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                          check_vma=False)(g)
assert scaled.dtype == jnp.float32
rel2 = float(jnp.abs(scaled[0] - 2.0 * g.sum(0)).max() / jnp.abs(2.0 * g.sum(0)).max())
assert rel2 < 0.02, rel2
# uncompressed reduce: plain psum with host plugins
desc3 = C.describe(Endpoint.local(C.MN), Endpoint.reduce('x', axis_size=8),
                   post=(C.BiasAdd(1.0),))
plain = jax.shard_map(lambda gs: xdma.transfer(gs[0], desc3)[None],
                         mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                         check_vma=False)(g)
np.testing.assert_allclose(np.asarray(plain[0]), np.asarray(g.sum(0) + 1.0),
                           rtol=1e-5, atol=1e-5)
# a Dequantize with no matching pre Quantize is not a wire codec: it stays on
# the post host and fails loudly instead of being silently dropped
desc4 = C.describe(Endpoint.local(C.MN), Endpoint.reduce('x', axis_size=8),
                   post=(C.Dequantize(jnp.bfloat16),))
try:
    jax.shard_map(lambda gs: xdma.transfer(gs[0], desc4)[None],
                     mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                     check_vma=False)(g)
except Exception:
    pass
else:
    raise AssertionError('orphan Dequantize was silently dropped')
print('OK')
""")
    assert "OK" in out


def test_moe_ep_queue_dispatch_matches_local():
    """The migrated MoE path (XDMAQueue of endpoint descriptors) still matches
    the local (no-collective) math, with and without int8 wire plugins."""
    out = run_multidevice("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.layers import moe as MOE
from repro.sharding import Axes
cfg = dataclasses.replace(configs.smoke_config('qwen3_moe_30b_a3b'),
                          dtype=jnp.float32, capacity_factor=8.0)
p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
y_local, aux_local = MOE.moe_apply(cfg, p, x)
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg2 = cfg.with_axes(Axes(batch=('data',), model='model', model_size=4, batch_size=2))
with mesh:
    y_dist, aux_dist = jax.jit(lambda xx: MOE.moe_apply(cfg2, p, xx, mesh=mesh))(x)
rel = float(jnp.abs(y_dist - y_local).max() / (jnp.abs(y_local).max() + 1e-9))
assert rel < 5e-4, rel
cfg3 = dataclasses.replace(cfg2, moe_wire_int8=True)
with mesh:
    y_q, _ = jax.jit(lambda xx: MOE.moe_apply(cfg3, p, xx, mesh=mesh))(x)
rel_q = float(jnp.abs(y_q - y_local).max() / (jnp.abs(y_local).max() + 1e-9))
assert rel_q < 0.05, rel_q
print('OK')
""")
    assert "OK" in out
