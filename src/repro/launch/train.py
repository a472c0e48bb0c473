"""Training launcher: fault-tolerant loop with checkpoint/restart, async
saves, straggler watchdog, and elastic resume.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --steps 50 --batch 8 --seq 64 --smoke --ckpt-dir /tmp/ckpt

On a real fleet this binary runs per host (jax.distributed.initialize); here
it exercises the identical code path on however many local devices exist.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ShapeConfig
from repro.data.pipeline import SyntheticLM
from repro.launch import mesh as MM
from repro.launch.cache import enable_compile_cache
from repro.optim.adamw import AdamWConfig
from repro.train.step import init_state, make_train_step

log = logging.getLogger("repro.train")


class StragglerWatchdog:
    """Flags steps slower than ``factor`` x the running median.  On a real
    fleet this triggers re-slicing / hot-spare swap; here it logs and counts
    (the decision signal is the deliverable)."""

    def __init__(self, factor: float = 3.0, warmup: int = 5):
        self.factor = factor
        self.warmup = warmup
        self.times = []
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        self.times.append(dt)
        if len(self.times) <= self.warmup:
            return False
        med = float(np.median(self.times[:-1]))
        if dt > self.factor * med:
            self.flagged += 1
            log.warning("straggler step: %.3fs vs median %.3fs", dt, med)
            return True
        return False


def train(arch: str, *, steps: int, batch: int, seq: int, smoke: bool,
          ckpt_dir: Optional[str], ckpt_every: int = 20, microbatches: int = 1,
          lr: float = 3e-4, resume: bool = True, seed: int = 0):
    cfg = configs.smoke_config(arch) if smoke else configs.get_config(arch)
    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1:
        import math
        model = 1
        for m in (4, 2, 1):
            if n_dev % m == 0:
                model = m
                break
        mesh = jax.make_mesh((n_dev // model, model), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape_tmp = ShapeConfig("cli", seq, batch, "train", microbatches)
        cfg = cfg.with_axes(MM.axes_for(mesh, shape_tmp))
        cfg = dataclasses.replace(cfg, fsdp=True)

    shape = ShapeConfig("cli", seq, batch, "train", microbatches)
    opt_cfg = AdamWConfig(lr=lr, total_steps=max(steps, 10))
    ds = SyntheticLM(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                     seed=seed, family=cfg.family, d_model=cfg.d_model,
                     encoder_seq=cfg.encoder_seq)

    state = init_state(jax.random.PRNGKey(seed), cfg)
    start_step = 0
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if mgr and resume and mgr.latest_step() is not None:
        start_step = mgr.latest_step()
        state = jax.tree.map(jnp.asarray,
                             mgr.restore(start_step, jax.eval_shape(lambda: state)))
        log.info("resumed from step %d", start_step)

    step_fn = make_train_step(cfg, shape, opt_cfg, mesh=mesh)
    if mesh is not None:
        state_specs = MM.infer_state_specs(jax.eval_shape(lambda: state), cfg.axes)
        ns = MM.fit_specs(mesh, state_specs, jax.eval_shape(lambda: state))
        ns = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), ns,
                          is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        state = jax.device_put(state, ns)
        jit_step = jax.jit(step_fn, donate_argnums=(0,), in_shardings=(ns, None),
                           out_shardings=(ns, None))
    else:
        jit_step = jax.jit(step_fn, donate_argnums=(0,))

    dog = StragglerWatchdog()
    history = []
    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        for i in range(start_step, steps):
            t0 = time.time()
            batch_np = ds.batch_at(i)
            dev_batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            state, metrics = jit_step(state, dev_batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            dog.observe(dt)
            history.append(loss)
            if i % 5 == 0 or i == steps - 1:
                log.info("step %d loss %.4f lr %.2e gnorm %.3f (%.2fs)",
                         i, loss, float(metrics["lr"]),
                         float(metrics["grad_norm"]), dt)
            if mgr and (i + 1) % ckpt_every == 0:
                mgr.save(i + 1, state, blocking=False)
    if mgr:
        mgr.save(steps, state, blocking=True)
    return state, history


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main(argv=None):
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    _, history = train(args.arch, steps=args.steps, batch=args.batch,
                       seq=args.seq, smoke=args.smoke, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every,
                       microbatches=args.microbatches, lr=args.lr,
                       seed=args.seed)
    print(f"final loss: {history[-1]:.4f} (from {history[0]:.4f})")


if __name__ == "__main__":
    main()
