"""XDMA remote engine on an 8-device CPU mesh (subprocess: main process must
keep seeing exactly 1 device)."""
import jax
import pytest

from conftest import run_multidevice


def test_main_process_single_device():
    assert len(jax.devices()) == 1


def test_compressed_psum_and_feedback():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as PS
from repro import core as C
mesh = jax.make_mesh((8,), ('x',),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)
g = jnp.asarray(np.random.default_rng(1).standard_normal((8, 1000)), jnp.float32)
f = jax.shard_map(lambda gs: C.compressed_psum(gs[0], 'x', 8)[None],
                     mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                     check_vma=False)
out = f(g)
exact = g.sum(0)
rel = float(jnp.abs(out[0]-exact).max()/jnp.abs(exact).max())
assert rel < 0.02, rel
# error feedback converges toward unbiased over steps
err = jnp.zeros((125, 8))
def body(gs, es):
    r, e = C.compressed_psum_with_feedback(gs[0].reshape(125,8), es[0], 'x', 8)
    return r[None], e[None]
f2 = jax.shard_map(body, mesh=mesh, in_specs=(PS('x'), PS('x')),
                   out_specs=(PS('x'), PS('x')), check_vma=False)
red, new_err = f2(g.reshape(8, 125, 8), jnp.zeros((8, 125, 8)))
assert float(jnp.abs(new_err).max()) < float(jnp.abs(g).max())
print('OK')
""")
    assert "OK" in out


def test_xdma_ppermute_with_plugins():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as PS
from repro import core as C
mesh = jax.make_mesh((8,), ('x',),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)
x = jnp.asarray(np.random.default_rng(2).standard_normal((8, 16, 128)), jnp.float32)
perm = [(i, (i+1)%8) for i in range(8)]
f = jax.shard_map(lambda xs: C.xdma_ppermute(xs, 'x', perm,
                                                pre=[C.Quantize()],
                                                post=[C.Dequantize(jnp.float32)]),
                     mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                     check_vma=False)
y = f(x)
ref = jnp.roll(x, 1, axis=0)
rel = float(jnp.abs(y-ref).max()/jnp.abs(ref).max())
assert rel < 0.01, rel
print('OK')
""")
    assert "OK" in out


def test_moe_ep_matches_local():
    """shard_map EP MoE == local MoE on the same inputs (no drops)."""
    out = run_multidevice("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.layers import moe as MOE
from repro.sharding import Axes
cfg = dataclasses.replace(configs.smoke_config('qwen3-moe-30b-a3b'),
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
print('OK')
""")
    assert "OK" in out


def test_moe_tp_path_matches_local():
    """E=8 experts on 16... here E=4 on model=3 (non-divisible) -> TP path."""
    out = run_multidevice("""
import dataclasses, jax, jax.numpy as jnp
from repro import configs
from repro.layers import moe as MOE
from repro.sharding import Axes
cfg = dataclasses.replace(configs.smoke_config('mixtral-8x7b'),
                          dtype=jnp.float32, capacity_factor=8.0)
p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), jnp.float32)
y_local, _ = MOE.moe_apply(cfg, p, x)
mesh = jax.make_mesh((2, 3), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
assert not MOE.ep_enabled(cfg, 3)
cfg2 = cfg.with_axes(Axes(batch=('data',), model='model', model_size=3, batch_size=2))
with mesh:
    y_dist, _ = jax.jit(lambda xx: MOE.moe_apply(cfg2, p, xx, mesh=mesh))(x)
rel = float(jnp.abs(y_dist - y_local).max() / (jnp.abs(y_local).max() + 1e-9))
assert rel < 5e-4, rel
print('OK')
""", n_devices=6)
    assert "OK" in out


def test_cross_stage_kv_transfer():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as PS
from repro.serving.transfer import cross_stage_transfer
mesh = jax.make_mesh((8,), ('x',),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)
kv = jnp.asarray(np.random.default_rng(3).standard_normal((8, 2, 32, 4, 16)), jnp.float32)
perm = [(0, 4), (1, 5), (2, 6), (3, 7)]   # prefill ranks 0-3 -> decode ranks 4-7
f = jax.shard_map(lambda s: cross_stage_transfer(s[0], 'x', perm)[None],
                     mesh=mesh, in_specs=PS('x'), out_specs=PS('x'),
                     check_vma=False)
y = f(kv)
np.testing.assert_array_equal(np.asarray(y[4:]), np.asarray(kv[:4]))
print('OK')
""")
    assert "OK" in out
