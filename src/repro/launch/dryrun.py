import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first init, and the production meshes below need 512 placeholder
# host devices (256 = one 16x16 pod; 512 = two pods).

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

For each cell this:
  1. builds the production mesh and axis roles,
  2. lowers the step function (train_step / prefill / serve_step) with
     ShapeDtypeStruct inputs and explicit NamedShardings,
  3. compiles it (proving the sharding is coherent and collectives lower),
  4. records memory_analysis + cost_analysis + collective bytes parsed from
     the HLO, and the three roofline terms (EXPERIMENTS.md reads this).

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--out results.jsonl]
"""
import argparse
import functools
import json
import math
import re
import sys
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs import specs as SP
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.launch import mesh as MM
from repro.models import lm
from repro.serving.engine import make_serve_step
from repro.train.step import init_state, make_train_step

# TPU v5e-class hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

_COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\b")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}


def collective_bytes(hlo: str) -> Dict[str, int]:
    """Sum operand bytes of every collective op in (per-device) HLO text."""
    out: Dict[str, int] = {}
    for line in hlo.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        # operands are the shapes appearing after the op name
        rhs = line.split(kind, 1)[1]
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(rhs):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + nbytes
    return out


def _ns(mesh, spec_tree, shape_tree):
    spec_tree = MM.fit_specs(mesh, spec_tree, shape_tree)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _bf16_params(sds_tree):
    """Serving holds weights in bf16 (training keeps the f32 master copy)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating)
            else s.dtype), sds_tree)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               xdma_cache: bool = False, moe_int8: bool = False):
    """Returns (lowered, cfg, shape, mesh, n_params)."""
    import dataclasses
    mesh = MM.make_production_mesh(multi_pod=multi_pod)
    shape = SHAPES[shape_name]
    base_cfg = configs.get_config(arch)
    axes = MM.axes_for(mesh, shape)
    cfg = base_cfg.with_axes(axes)
    if xdma_cache:
        cfg = dataclasses.replace(cfg, xdma_cache=True)
    if moe_int8:
        cfg = dataclasses.replace(cfg, moe_wire_int8=True)
    if shape.kind == "train":
        cfg = dataclasses.replace(cfg, fsdp=True)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    batch_sds = SP.batch_specs(cfg, shape)
    batch_specs = MM.batch_input_specs(batch_sds, axes)

    if shape.kind == "train":
        state_sds = jax.eval_shape(functools.partial(init_state, cfg=cfg), key)
        state_specs = MM.infer_state_specs(state_sds, axes)
        step = make_train_step(cfg, shape, mesh=mesh)
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(_ns(mesh, state_specs, state_sds),
                              _ns(mesh, batch_specs, batch_sds)),
                out_shardings=(_ns(mesh, state_specs, state_sds), None),
                donate_argnums=(0,),
            ).lower(state_sds, batch_sds)
    elif shape.kind == "prefill":
        params_sds = _bf16_params(jax.eval_shape(
            functools.partial(lm.init_params, cfg=cfg), key))
        param_specs = MM.infer_param_specs(params_sds, axes)
        cache_sds = jax.eval_shape(functools.partial(
            lm.init_cache, cfg, shape.global_batch, shape.seq_len))
        c_specs = MM.cache_specs(cfg, cache_sds, axes)
        fn = functools.partial(lm.prefill, cfg, mesh=mesh)
        with mesh:
            lowered = jax.jit(
                fn,
                in_shardings=(_ns(mesh, param_specs, params_sds),
                              _ns(mesh, batch_specs, batch_sds),
                              _ns(mesh, c_specs, cache_sds)),
                out_shardings=(None, _ns(mesh, c_specs, cache_sds)),
                donate_argnums=(2,),
            ).lower(params_sds, batch_sds, cache_sds)
    else:  # decode
        params_sds = _bf16_params(jax.eval_shape(
            functools.partial(lm.init_params, cfg=cfg), key))
        param_specs = MM.infer_param_specs(params_sds, axes)
        cache_sds = jax.eval_shape(functools.partial(
            lm.init_cache, cfg, shape.global_batch, shape.seq_len))
        c_specs = MM.cache_specs(cfg, cache_sds, axes)
        tok_sds = SP.decode_token_specs(cfg, shape)
        tok_specs = MM.batch_input_specs(tok_sds, axes)
        step = make_serve_step(cfg, mesh=mesh)

        def serve(params, cache, tokens):
            t = tokens.get("tokens", tokens.get("embeds"))
            return step(params, cache, t)

        with mesh:
            lowered = jax.jit(
                serve,
                in_shardings=(_ns(mesh, param_specs, params_sds),
                              _ns(mesh, c_specs, cache_sds),
                              _ns(mesh, tok_specs, tok_sds)),
                out_shardings=(None, _ns(mesh, c_specs, cache_sds)),
                donate_argnums=(1,),
            ).lower(params_sds, cache_sds, tok_sds)
    return lowered, cfg, shape, mesh


def attention_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Useful attention FLOPs (QK^T + PV = 4*B*S*S_kv*H*hd per layer, causal
    halves it); windowed layers cap S_kv at the window.  Dominates 2*N*D at
    32k+ context, so MFU accounting must include it."""
    B, S = shape.global_batch, shape.seq_len
    layers = list(cfg.lead) + list(cfg.period) * cfg.n_periods + list(cfg.tail)
    total = 0.0
    for spec in layers:
        if spec.kind != "attn":
            continue
        s_kv = min(S, spec.window) if spec.window else S
        if shape.kind == "decode":
            total += 4.0 * B * s_kv * cfg.n_heads * cfg.head_dim
        else:
            causal = 0.5 if spec.window is None else 1.0  # window already caps
            total += 4.0 * B * S * s_kv * cfg.n_heads * cfg.head_dim * causal
    if cfg.encoder_layers:      # encoder self-attn + decoder cross-attn
        Se = cfg.encoder_seq
        total += cfg.encoder_layers * 4.0 * B * Se * Se * cfg.n_heads * cfg.head_dim
        if shape.kind == "decode":
            total += cfg.n_layers * 4.0 * B * Se * cfg.n_heads * cfg.head_dim
        else:
            total += cfg.n_layers * 4.0 * B * S * Se * cfg.n_heads * cfg.head_dim
    return total


def model_flops(cfg: ModelConfig, shape: ShapeConfig, n_total: int,
                n_active: int) -> float:
    """6*N*D + 3*attn for training, 2*N*D + attn for prefill,
    2*N_active*B + attn for decode."""
    attn = attention_flops(cfg, shape)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len + 3.0 * attn
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len + attn
    return 2.0 * n_active * shape.global_batch + attn


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             compile_: bool = True, xdma_cache: bool = False,
             moe_int8: bool = False) -> Dict[str, Any]:
    t0 = time.time()
    lowered, cfg, shape, mesh = lower_cell(arch, shape_name, multi_pod=multi_pod,
                                           xdma_cache=xdma_cache,
                                           moe_int8=moe_int8)
    n_dev = mesh.size
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": n_dev, "lower_s": round(time.time() - t0, 1),
    }
    n_total, n_active = SP.count_params(cfg)
    rec["params_total"] = n_total
    rec["params_active"] = n_active
    if not compile_:
        return rec

    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    if os.environ.get("DRYRUN_PRINT_ANALYSIS"):
        print(mem)                      # proves it fits (per-device bytes)
        print(compiled.cost_analysis())  # FLOPs/bytes for the roofline
    rec["bytes_per_device"] = {
        "argument": getattr(mem, "argument_size_in_bytes", None),
        "output": getattr(mem, "output_size_in_bytes", None),
        "temp": getattr(mem, "temp_size_in_bytes", None),
        "peak": getattr(mem, "peak_memory_in_bytes", None),
    }
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    rec["xla_cost_flops_raw"] = float(cost.get("flops", 0.0))

    # trip-count-aware walk over the optimized HLO (see hlo_cost.py): XLA's
    # cost_analysis counts while bodies once, undercounting scanned programs.
    from repro.launch import hlo_cost
    walk = hlo_cost.analyze(compiled.as_text())
    flops_dev = walk["flops"]
    bytes_dev = walk["bytes"]
    rec["hlo_flops_per_device"] = flops_dev
    rec["hlo_bytes_per_device"] = bytes_dev
    coll = {k: int(v) for k, v in walk["collectives"].items()}
    rec["collective_bytes_per_device"] = coll
    coll_total = sum(coll.values())

    # roofline terms (seconds); HLO numbers are per-device for the SPMD module
    comp_t = flops_dev / PEAK_FLOPS
    mem_t = bytes_dev / HBM_BW
    coll_t = coll_total / ICI_BW
    rec["roofline_s"] = {"compute": comp_t, "memory": mem_t, "collective": coll_t}
    dom = max(rec["roofline_s"], key=rec["roofline_s"].get)
    rec["bottleneck"] = dom
    mf = model_flops(cfg, shape, n_total, n_active)
    rec["model_flops"] = mf
    global_flops = flops_dev * n_dev
    rec["useful_flop_ratio"] = (mf / global_flops) if global_flops else None
    # fraction of the roofline the dominant term allows (time of useful
    # compute at peak / achievable step time)
    ideal_t = mf / (n_dev * PEAK_FLOPS)
    ach_t = max(comp_t, mem_t, coll_t)
    rec["roofline_fraction"] = (ideal_t / ach_t) if ach_t else None
    return rec


def iter_cells():
    for arch_alias, mod in sorted(configs._ALIASES.items()):
        skips = configs.shape_skips(arch_alias)
        for shape_name in SHAPES:
            if shape_name in skips:
                yield arch_alias, shape_name, skips[shape_name]
            else:
                yield arch_alias, shape_name, None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--xdma-cache", action="store_true",
                    help="layout-optimal KV cache (the paper technique)")
    ap.add_argument("--moe-int8", action="store_true",
                    help="int8 wire format on the MoE dispatch (XDMA plugin)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    done = set()
    if args.out and args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    cells = []
    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for arch, shape_name, skip in iter_cells():
            for mp in meshes:
                cells.append((arch, shape_name, mp, skip))
    else:
        cells = [(args.arch, args.shape, args.multi_pod, None)]

    failures = 0
    for arch, shape_name, mp, skip in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        if skip is not None:
            emit({"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "skipped": skip})
            continue
        if (arch, shape_name, mesh_name) in done:
            continue
        try:
            rec = run_cell(arch, shape_name, multi_pod=mp,
                           xdma_cache=args.xdma_cache, moe_int8=args.moe_int8)
            variants = [v for v, on in (("xdma_cache", args.xdma_cache),
                                        ("moe_int8", args.moe_int8)) if on]
            if variants:
                rec["variant"] = "+".join(variants)
            emit(rec)
        except Exception as e:  # noqa: BLE001 - report and continue the sweep
            failures += 1
            emit({"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "error": f"{type(e).__name__}: {e}"[:500]})
    return 0  # cell errors are recorded in the jsonl, not exit status


if __name__ == "__main__":
    sys.exit(main())
