"""train_step: microbatched (gradient-accumulation) loss/grad/update.

The global batch is split into ``shape.microbatches`` slices scanned
sequentially — activation memory scales with the microbatch, gradients
accumulate in f32.  Under jit/GSPMD the DP all-reduce is implicit in the
sharding; the *explicit* DP path — :func:`make_dp_train_step` — runs per-
device grads under shard_map and syncs them through the XDMA movement plane:
every leaf's all-reduce is a ``reduce``-endpoint descriptor (int8
Quantize/Dequantize wire codec when ``compressed=True``, lowering to
:func:`repro.core.remote.compressed_psum`), submitted through a
:class:`~repro.runtime.DistributedScheduler` when one is given, so a
``capture()`` trace records the complete DP gradient traffic of a step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import MN, Endpoint, describe
from repro.core import api as xdma
from repro.core.descriptor import reduce_descriptor
from repro.models import lm
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.sharding import constrain, P


class TrainState(dict):
    """{"params", "opt", "step"} — a plain pytree dict."""


def init_state(key, cfg: ModelConfig) -> Dict[str, Any]:
    params = lm.init_params(key, cfg)
    return {"params": params, "opt": adamw_init(params),
            "step": jnp.zeros((), jnp.int32)}


def loss_fn(cfg: ModelConfig, params, batch, *, mesh=None,
            aux_weight: float = 0.01, z_weight: float = 1e-4):
    logits, aux = lm.forward(cfg, params, batch, mesh=mesh)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - ll).mean()
    zloss = (logz ** 2).mean()
    total = nll + aux_weight * aux + z_weight * zloss
    return total, {"nll": nll, "aux": aux, "zloss": zloss}


# -- the explicit DP path: gradient sync as movement-plane tasks -------------
def dp_grad_sync(grads, axis: str, axis_size: int, *, compressed: bool = True,
                 scheduler=None):
    """All-reduce-mean a gradient pytree through the movement plane: one
    :func:`repro.core.descriptor.reduce_descriptor` task per leaf (int8 wire
    codec when ``compressed`` — lowered to ``compressed_psum``).

    Call inside ``shard_map`` (the reduce descriptors lower to collectives
    over ``axis``).  With a scheduler, every leaf is submitted as its own
    task — round-robin over the fabric's links, trace-transparent under jit —
    so a ``capture()`` ledger records one ``reduce`` event per leaf;
    without one, each leaf goes through ``xdma.transfer`` directly.
    """
    desc = reduce_descriptor(axis, axis_size, compressed=compressed)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if scheduler is None:
        outs = [xdma.transfer(g, desc) for g in leaves]
    else:
        futs = [scheduler.submit(g, desc, label=f"dp_grad[{i}]")
                for i, g in enumerate(leaves)]
        scheduler.flush()
        outs = [f.result() for f in futs]
    outs = [g / axis_size for g in outs]
    return jax.tree_util.tree_unflatten(treedef, outs)


@functools.lru_cache(maxsize=None)
def _bcast_desc(dsts: tuple) -> Any:
    return describe(Endpoint.local(MN), Endpoint.multicast(dsts))


def dp_param_broadcast(params, *, scheduler, src: Optional[str] = None,
                       replicas=None, label: str = "dp_bcast"):
    """Broadcast a parameter pytree from the primary data-parallel replica
    to every peer through the movement plane: one *multicast* descriptor
    per matrix leaf, tree-routed over the scheduler's fabric
    (:meth:`~repro.runtime.DistributedScheduler.submit_multicast`), so a
    hop shared by several replicas carries each weight once instead of
    once per replica — the N-unicast DP broadcast collapsed into one tree.

    ``src`` defaults to the fabric's first node and ``replicas`` to every
    other node.  Non-matrix leaves (scalars, step counters) replicate
    outside the plane.  Returns the per-replica parameter pytrees in
    ``replicas`` order, each leaf bit-identical to the source.
    """
    topo = scheduler.topology
    nodes = list(topo.nodes)
    if src is None:
        src = nodes[0]
    if replicas is None:
        replicas = [n for n in nodes if n != src]
    replicas = list(replicas)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    futs = {}
    for i, leaf in enumerate(leaves):
        if getattr(leaf, "ndim", 0) < 2:
            continue                      # counters ride outside the plane
        mat = leaf if leaf.ndim == 2 else leaf.reshape(-1, leaf.shape[-1])
        futs[i] = scheduler.submit_multicast(
            mat, _bcast_desc(tuple(replicas)), src=src,
            label=f"{label}[{i}]")
    scheduler.flush()
    out = []
    for node in replicas:
        rleaves = list(leaves)
        for i, f in futs.items():
            rleaves[i] = f.result_at(node).reshape(leaves[i].shape)
        out.append(jax.tree_util.tree_unflatten(treedef, rleaves))
    return out


def make_dp_train_step(cfg: ModelConfig, shape: ShapeConfig,
                       opt_cfg: Optional[AdamWConfig] = None, *, mesh,
                       axis: str = "dp", compressed: bool = True,
                       scheduler=None):
    """The explicit data-parallel trainer: per-device microbatched grads
    under ``shard_map``, gradient sync through :func:`dp_grad_sync` (the
    movement plane), optimizer update on the replicated mean grads.

    Unlike :func:`make_train_step` (whose DP reduction is implicit in GSPMD
    sharding), every byte this step moves between devices is an XDMA task —
    the paper's train-step workload, capturable and replayable.
    """
    opt_cfg = opt_cfg or AdamWConfig()
    n = int(mesh.shape[axis])
    n_micro = max(1, shape.microbatches)

    def local_grads(params, batch):
        """Microbatch-accumulated grads/loss on this device's batch shard."""
        def one(p, mb):
            return jax.value_and_grad(
                lambda q: loss_fn(cfg, q, mb)[0])(p)

        if n_micro == 1:
            loss, grads = one(params, batch)
            return loss, jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        def split(x):
            if x.ndim == 0:
                return x
            B = x.shape[0]
            assert B % n_micro == 0, (B, n_micro)
            return x.reshape((n_micro, B // n_micro) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(acc, mb):
            acc_g, acc_l = acc
            loss, grads = one(params, mb)
            acc_g = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n_micro, acc_g, grads)
            return (acc_g, acc_l + loss / n_micro), None

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss), _ = lax.scan(body, (zero, jnp.zeros((), jnp.float32)),
                                    micro)
        return loss, grads

    def body(params, batch):
        loss, grads = local_grads(params, batch)
        grads = dp_grad_sync(grads, axis, n, compressed=compressed,
                             scheduler=scheduler)
        # the loss mean rides the plane too (uncompressed scalar reduce)
        loss = xdma.transfer(loss, reduce_descriptor(axis, n)) / n
        return loss, grads

    # jit around the shard_map (eager shard_map cannot evaluate closed
    # calls); the capture chokepoints record at trace time either way
    sharded = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=(P(), P()), check_vma=False))

    def train_step(state, batch):
        loss, grads = sharded(state["params"], batch)
        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, state["params"], grads, state["opt"])
        state = {"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}
        return state, dict(loss=loss, **opt_metrics)

    return train_step


def make_train_step(cfg: ModelConfig, shape: ShapeConfig,
                    opt_cfg: Optional[AdamWConfig] = None, *, mesh=None):
    """Returns train_step(state, batch) -> (state, metrics)."""
    opt_cfg = opt_cfg or AdamWConfig()
    n_micro = max(1, shape.microbatches)

    def constrain_like_params(grads, params):
        """Keep accumulated grads on the FSDP/TP param sharding so each
        microbatch's backward emits a reduce-scatter, not an all-reduce."""
        if mesh is None or not cfg.axes.batch:
            return grads
        from repro.launch.mesh import infer_param_specs
        specs = infer_param_specs(params, cfg.axes, fsdp=True)
        return jax.tree.map(constrain, grads, specs)

    def split_micro(batch):
        def sp(x):
            if x.ndim == 0:
                return x
            b_axis = 1 if x.ndim >= 3 and x.shape[0] == 3 else 0   # (3,B,S) mrope
            B = x.shape[b_axis]
            assert B % n_micro == 0, (B, n_micro)
            mb = B // n_micro
            if b_axis == 0:
                return x.reshape((n_micro, mb) + x.shape[1:])
            return jnp.moveaxis(
                x.reshape(x.shape[0], n_micro, mb, *x.shape[2:]), 1, 0)
        return jax.tree.map(sp, batch)

    def train_step(state, batch):
        params = state["params"]
        micro = split_micro(batch)

        def micro_step(acc, mb):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, mb, mesh=mesh), has_aux=True)(params)
            acc_g, acc_l = acc
            acc_g = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n_micro, acc_g, grads)
            acc_g = constrain_like_params(acc_g, params)
            return (acc_g, acc_l + loss / n_micro), metrics

        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        zero_g = constrain_like_params(zero_g, params)
        if n_micro == 1:
            mb = jax.tree.map(lambda x: x[0] if x.ndim else x, micro)
            (grads, loss), metrics = micro_step((zero_g, 0.0), mb)
        else:
            (grads, loss), metrics = lax.scan(
                micro_step, (zero_g, jnp.zeros((), jnp.float32)), micro)
            metrics = jax.tree.map(lambda m: m.mean() if m.ndim else m, metrics)

        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, params, grads, state["opt"])
        state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return state, metrics

    return train_step
