#!/usr/bin/env python3
"""Bring-up check on a TPU: the main serving path at full model width.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: data-parallel phase only

One chip.  Builds qwen3-1.7b at published width and depth (28 layers,
d_model 2048, 16/8 heads, d_ff 6144, vocab 151936) with bf16 weights drawn
from ``--seed``, and serves 8 requests through
``ContinuousBatchingEngine.serve`` over the paged-KV pool with a bf16 cache.
The pool is sized so that at least one request is preempted, so the
Compress/Decompress evict and restore kernels run natively.  Every request's
greedy tokens must equal a plain jitted ``lm.prefill`` + ``lm.decode_step``
loop run on the same chip with no pool.  Then the paper's two KV movements
(RMSNorm+tile store, transposed load) run on one full-context K matrix
(S=4096, d_kv=1024, bf16), and the page evict/restore descriptors run on a
(32, 1024) page; each is checked against the fused XLA composition, and each
compiled program must contain a Mosaic kernel (``tpu_custom_call``).

Four chips.  ``train.step.make_dp_train_step`` on qwen2-0.5b at published
width over a 4-way ``dp`` mesh, 3 steps at per-chip batch 4 x seq 512, with
the int8 gradient codec and without it; both are checked against a jitted
reference step that syncs gradients with ``lax.psum``.

Progress goes to stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase raises, so the script exits non-zero without that line —
also when JAX finds no TPU, or when the rest of the repository is missing.
Times printed here are host wall-clock seconds on the chip's machine; the
serving engine's simulated clock is not printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SERVE_ARCH = "qwen3-1.7b"
PROMPT_LENS = (128, 256)       # rids 0-3 and 4-7
N_REQUESTS = 8
MAX_NEW = 16                   # generated tokens per request (incl. prefill's)
MAX_BATCH = 4
TOKENS_PER_PAGE = 16
# four 128-token prompts hold 64 pages; six more cannot take the 8 pages
# their next decode step needs, so the engine preempts one request
CAPACITY_PAGES = 70
KV_SHAPE = (1, 4096, 8, 128)   # one full-context K: S=4096, d_kv=8x128
PAGE_SHAPE = (32, 1024)        # rows x the KV width of qwen3-1.7b

DP_ARCH = "qwen2-0.5b"
DP_STEPS = 3
DP_BATCH_PER_CHIP = 4
DP_SEQ = 512


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums XLA backend-compile seconds reported by ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.seconds += secs
            self.count += 1


def tpu_devices(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        sys.exit(f"chip_smoke: needs {n} chips; JAX found {len(devices)}")
    return devices


def check(ok, what) -> None:
    """Fail the phase (a raise, so it also holds under ``python -O``)."""
    if not ok:
        raise AssertionError(what)


def has_mosaic_kernel(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def assert_equal(name, got, want) -> None:
    import jax.numpy as jnp
    if got.shape != want.shape or got.dtype != want.dtype:
        raise AssertionError(f"{name}: {got.shape}/{got.dtype} vs "
                             f"{want.shape}/{want.dtype}")
    bad = int(jnp.sum(got != want))
    if bad:
        diff = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - want.astype(jnp.float32))))
        raise AssertionError(f"{name}: {bad} of {got.size} elements differ "
                             f"from the XLA composition (max |diff| {diff})")


def assert_same_rounding(name, got, want, exact) -> int:
    """``got`` and ``want`` are bf16 roundings of one f32 computation whose
    row reductions ran in different orders (Mosaic and XLA sum a row's lanes
    in their own trees, which moves the f32 result by an ulp).  Each element
    must equal ``want`` or be its bf16 neighbour on the other side of the
    float64 value ``exact``: both are then faithful roundings.  Returns the
    number of elements that differ."""
    import numpy as np
    g = np.asarray(got).reshape(-1)
    w = np.asarray(want).reshape(-1)
    e = np.asarray(exact, np.float64).reshape(-1)
    differ = np.flatnonzero(g.view(np.uint16) != w.view(np.uint16))
    gd, wd = g[differ].astype(np.float64), w[differ].astype(np.float64)
    steps = np.abs(g[differ].view(np.uint16).astype(np.int32)
                   - w[differ].view(np.uint16).astype(np.int32))
    between = ((np.minimum(gd, wd) <= e[differ])
               & (e[differ] <= np.maximum(gd, wd)))
    check(bool(np.all((steps == 1) & (np.sign(gd) == np.sign(wd)) & between)),
          f"{name}: {differ.size} elements differ from the XLA composition "
          "by more than the rounding of a reordered sum")
    return int(differ.size)


# -- one chip ----------------------------------------------------------------
def serve_phase(seed: int, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.models import lm
    from repro.serving import ContinuousBatchingEngine, PagedKVPool, Request

    cfg = configs.get_config(SERVE_ARCH)
    widths = (cfg.n_periods, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
              cfg.d_ff, cfg.vocab)
    check(widths == (28, 2048, 16, 8, 6144, 151936), widths)

    def init(key):
        params = lm.init_params(key, cfg)
        return jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)

    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(init)(jax.random.PRNGKey(seed)))
    leaves = jax.tree.leaves(params)
    log(f"params: {sum(l.size for l in leaves)} "
        f"({sum(l.nbytes for l in leaves)} bytes bf16), "
        f"init {time.perf_counter() - t0:.3f}s")

    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, arrival_s=0.0, max_new=MAX_NEW,
                    tokens=rng.integers(0, cfg.vocab,
                                        PROMPT_LENS[i * len(PROMPT_LENS)
                                                    // N_REQUESTS],
                                        dtype=np.int32))
            for i in range(N_REQUESTS)]
    max_len = max(PROMPT_LENS) + MAX_NEW

    # the pool pages each (layers, S, kv_heads, head_dim) cache leaf as an
    # (S * layers * kv_heads, head_dim) matrix: size pages in tokens
    k_leaf = jax.eval_shape(
        lambda: lm.init_cache(cfg, 1, max_len, jnp.bfloat16))["blocks"][0]["k"]
    rows_per_token = k_leaf.shape[0] * k_leaf.shape[3]
    pool = PagedKVPool(CAPACITY_PAGES, TOKENS_PER_PAGE * rows_per_token)
    eng = ContinuousBatchingEngine(cfg, params, max_len, max_batch=MAX_BATCH,
                                   cache_dtype=jnp.bfloat16, pool=pool)

    served = []
    for run in ("cold", "warm"):
        c0, n0 = clock.seconds, clock.count
        t0 = time.perf_counter()
        rep = eng.serve(reqs)
        # every step pulls its argmax tokens to the host, so the last token
        # on the host means the device work of the serve has finished
        wall = time.perf_counter() - t0
        log(f"serve ({run}): {rep.n_requests} requests, {rep.total_tokens} "
            f"tokens, {rep.steps} steps, {eng.preemptions} preemptions, "
            f"{wall:.3f}s wall, of which {clock.count - n0} compiles "
            f"{clock.seconds - c0:.3f}s")
        check(rep.n_requests == N_REQUESTS, rep.n_requests)
        check(rep.total_tokens == N_REQUESTS * MAX_NEW, rep.total_tokens)
        check(eng.preemptions >= 1, "the pool must force a preemption")
        check(pool.stats["evictions"] >= 1 and pool.stats["restores"] >= 1,
              f"no evict/restore: {pool.stats}")
        served.append(rep.tokens)

    # reference: the plain model loop, one request at a time, no pool
    prefill = jax.jit(lambda p, t, c: lm.prefill(cfg, p, {"tokens": t}, c))
    decode = jax.jit(lambda p, t, c: lm.decode_step(cfg, p, t, c))
    t0 = time.perf_counter()
    for r in reqs:
        cache = lm.init_cache(cfg, 1, max_len, jnp.bfloat16)
        logits, cache = prefill(params, jnp.asarray(r.tokens[None]), cache)
        check(bool(jnp.all(jnp.isfinite(logits))), f"request {r.rid}")
        want = [int(jnp.argmax(logits[0, -1]))]
        while len(want) < r.max_new:
            logits, cache = decode(params, jnp.asarray([[want[-1]]]), cache)
            want.append(int(jnp.argmax(logits[0, -1])))
        for tokens in served:
            got = tokens[r.rid].tolist()
            if got != want:
                first = next(i for i, (a, b) in enumerate(zip(got, want))
                             if a != b)
                raise AssertionError(
                    f"request {r.rid}: served tokens differ from the plain "
                    f"loop at token {first}: {got} vs {want}")
    log(f"reference loop: all {N_REQUESTS} requests token-exact "
        f"({time.perf_counter() - t0:.3f}s)")


def movement_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (MN, RMSNormPlugin, Transpose, describe,
                            layout_for_dtype, tiled_layout, xdma)
    from repro.core.descriptor import page_descriptor
    from repro.serving.transfer import kv_load_transposed, kv_prefill_store

    def fused(desc):
        return dataclasses.replace(desc, backend="fused")

    kv = jax.random.normal(jax.random.PRNGKey(seed + 1), KV_SHAPE,
                           jnp.bfloat16)
    B, S, KV, hd = KV_SHAPE
    mat = kv.reshape(B, S, KV * hd)
    tiled = kv_prefill_store(kv)
    layout = layout_for_dtype(jnp.bfloat16)
    store_ref = describe(MN, layout, RMSNormPlugin())
    x64 = np.asarray(mat, np.float64)
    exact = x64 / np.sqrt(np.mean(x64 * x64, axis=-1, keepdims=True)
                          + RMSNormPlugin().eps)
    flips = assert_same_rounding(
        "kv_prefill_store", layout.to_logical(tiled),
        layout.to_logical(xdma.transfer(mat, fused(store_ref))), exact)
    check(has_mosaic_kernel(kv_prefill_store, kv), "store: no Mosaic kernel")
    loaded = kv_load_transposed(tiled)
    tm, tn = tiled.shape[-2:]
    load_ref = describe(tiled_layout(tm, tn), MN, Transpose())
    assert_equal("kv_load_transposed", loaded,
                 xdma.transfer(tiled, fused(load_ref)))
    check(has_mosaic_kernel(kv_load_transposed, tiled),
          "load: no Mosaic kernel")
    log(f"kv movements: store {tuple(mat.shape)} -> {tuple(tiled.shape)} "
        f"matches the XLA composition ({flips} of {tiled.size} elements one "
        f"bf16 ulp apart, reordered row sum); load -> "
        f"{tuple(loaded.shape)} bit-equal")

    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        page = jax.random.normal(jax.random.PRNGKey(seed + 2), PAGE_SHAPE,
                                 dtype)
        page = page.at[:PAGE_SHAPE[0] // 4].set(0)     # blocks to skip
        store = page_descriptor(*PAGE_SHAPE, name, direction="store")
        resident = xdma.transfer(page, store)
        for direction, x in (("load", resident), ("store", page)):
            desc = page_descriptor(*PAGE_SHAPE, name, direction=direction,
                                   wire_compress_rows=8)
            run = lambda v, d=desc: xdma.transfer(v, d)
            assert_equal(f"page {direction} {name}", run(x),
                         xdma.transfer(x, fused(desc)))
            check(has_mosaic_kernel(run, x), f"page {direction}: no kernel")
    log(f"page evict/restore {PAGE_SHAPE} f32+bf16: equal to the XLA "
        "composition")


def one_chip(seed: int) -> dict:
    import jax

    from repro.launch.cache import enable_compile_cache
    from repro.runtime import telemetry

    device = tpu_devices(1)[0]
    log(f"device: {device.device_kind}, count {len(jax.devices())}")
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    serve_phase(seed, clock)
    movement_phase(seed)
    with telemetry.session(name="chip_smoke"):     # the banks always count
        counters = telemetry.snapshot()["counters"]
    log(f"compile: {clock.count} programs, {clock.seconds:.3f}s")
    log(f"peak_bytes_in_use: {device.memory_stats()['peak_bytes_in_use']}")
    for bank in ("agu", "plugin_compiler"):
        c = counters.get(bank, {})
        counts = {k: v for k, v in c.items() if not k.startswith("reason:")}
        reasons = {k: v for k, v in c.items() if k.startswith("reason:")}
        log(f"{bank}: fallback {c.get('fallback', 0)} {counts} {reasons}")
    return {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices())}


# -- four chips --------------------------------------------------------------
def dp_phase(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import SyntheticLM
    from repro.launch.cache import enable_compile_cache
    from repro.optim.adamw import AdamWConfig, adamw_update
    from repro.train.step import init_state, loss_fn, make_dp_train_step

    devices = tpu_devices(4)[:4]
    log(f"device: {devices[0].device_kind}, count {len(jax.devices())}")
    log(f"compile cache: {enable_compile_cache()}")
    n = len(devices)
    mesh = jax.make_mesh((n,), ("dp",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = configs.get_config(DP_ARCH)
    shape = ShapeConfig("dp", DP_SEQ, DP_BATCH_PER_CHIP * n, "train",
                        microbatches=1)
    opt_cfg = AdamWConfig()
    replicated = NamedSharding(mesh, P())
    ds = SyntheticLM(vocab=cfg.vocab, seq_len=DP_SEQ,
                     global_batch=DP_BATCH_PER_CHIP * n, seed=seed)
    batches = [jax.device_put({k: jnp.asarray(v) for k, v in
                               ds.batch_at(i).items()},
                              NamedSharding(mesh, P("dp")))
               for i in range(DP_STEPS)]

    def reference(state, batch):
        def body(params, batch):
            loss, grads = jax.value_and_grad(
                lambda q: loss_fn(cfg, q, batch)[0])(params)
            grads = jax.tree.map(
                lambda g: lax.psum(g.astype(jnp.float32), "dp") / n, grads)
            return lax.psum(loss, "dp") / n, grads
        loss, grads = jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P("dp")), out_specs=(P(), P()),
            check_vma=False)(state["params"], batch)
        params, opt, metrics = adamw_update(opt_cfg, state["params"], grads,
                                            state["opt"])
        return ({"params": params, "opt": opt, "step": state["step"] + 1},
                dict(loss=loss, **metrics))

    init = jax.jit(lambda key: init_state(key, cfg), out_shardings=replicated)

    def run(name, step):
        state = init(jax.random.PRNGKey(seed))
        step = jax.jit(step, donate_argnums=(0,))
        losses, norms = [], []
        t0 = time.perf_counter()
        for batch in batches:
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
            norms.append(float(metrics["grad_norm"]))
        log(f"{name}: losses {losses} grad_norm {norms} "
            f"({time.perf_counter() - t0:.3f}s, compile included)")
        for leaf in jax.tree.leaves(state):
            held = {s.device for s in leaf.addressable_shards
                    if s.data.shape == leaf.shape}
            check(held == set(devices), f"{name}: replica on {held}")
        params = jax.device_get(state["params"])
        del state
        return losses, norms, params

    ref = run("reference (lax.psum)", reference)
    plain = run("plane dp, f32 reduce", make_dp_train_step(
        cfg, shape, opt_cfg, mesh=mesh, axis="dp", compressed=False))
    int8 = run("plane dp, int8 reduce", make_dp_train_step(
        cfg, shape, opt_cfg, mesh=mesh, axis="dp", compressed=True))

    # tolerances of tests/test_trace.py::test_dp_train_step_through_plane_*
    # (f32 plane reduce: every loss within 1e-5, params within 1e-4; int8
    # codec: the first loss, computed before any update, within 1e-5), and
    # for the int8 gradient norms the 0.02 relative error that
    # tests/test_remote.py allows compressed_psum
    for a, b in zip(plain[0], ref[0]):
        check(abs(a - b) < 1e-5, (plain[0], ref[0]))
    worst = max(float(abs(x - y).max()) for x, y in
                zip(jax.tree.leaves(plain[2]), jax.tree.leaves(ref[2])))
    check(worst < 1e-4, worst)
    check(abs(int8[0][0] - ref[0][0]) < 1e-5, (int8[0], ref[0]))
    for a, b in zip(int8[1], ref[1]):
        check(abs(a - b) / b < 0.02, (int8[1], ref[1]))
    log(f"dp sync: f32 plane matches lax.psum (max |param diff| {worst}); "
        "int8 plane within the codec's tolerance; every leaf replicated on "
        f"all {n} chips")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    tpu_devices(args.chips)          # before anything of the repo is imported
    device = dp_phase(args.seed) if args.chips == 4 else one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
