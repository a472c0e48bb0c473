"""Mixture-of-Experts with capacity-based top-k routing and explicit
expert-parallel dispatch through the XDMA remote engine.

Distributed path (``cfg.axes.model`` set + ambient mesh): the MoE sublayer
runs under ``shard_map``.  Tokens are sequence-split across the model axis;
each rank routes its slice locally (sort-based, no cross-device scatter),
builds an (E, C, d) dispatch buffer, and exchanges it with
:func:`repro.core.xdma_all_to_all` — optionally with Quantize/Dequantize
plugins on the wire (paper's compute-while-transfer).  Expert FFN runs on the
local expert shard; the return path mirrors the dispatch; an all-gather
rebuilds the sequence.  This is exactly the paper's "distributed half-XDMA"
pattern: the descriptor (routing geometry, capacity, plugin chain) is fixed
at compile time, the link carries only payload.

Local path (no mesh; the serving engine's path): dropless over the experts
held here.  The config's ``experts_held`` (first, count) names a contiguous
range of the router's ``n_experts`` outputs; the layer routes over all of
them (softmax, top-k, renormalise; or, with ``router_score`` "sigmoid",
top-k of sigmoid scores plus a correction bias, weighted by the unbiased
scores, renormalised and scaled by ``routed_scale``), keeps the assignments
whose expert is held, sorts them by expert, and runs the SwiGLU as grouped
products over the held experts (``lax.ragged_dot``).  No capacity: no token
is dropped at any batch or routing skew.  Assignments to absent experts add
nothing here; on the other chips of an expert-parallel deployment they are
those chips' part of the result.  Shared experts (``d_ff_shared``), which
every chip holds, add once beside the held ones.  The ``shard_map`` paths
hold every expert, route by softmax with no shared experts, and keep their
capacity buffers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import plugins as XP
from repro.core import api as xdma
from repro.core.api import XDMAQueue
from repro.core.descriptor import Endpoint, XDMADescriptor, reduce_descriptor
from repro.layers import mlp as F
from repro.runtime.topology import HW_FLOPS
from repro.sharding import constrain, P


def init_moe(key, cfg):
    """Router over all ``n_experts`` (with its correction bias when routing
    by sigmoid); weights of the held experts only, and of the shared ones."""
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    n = cfg.held_experts[1]
    ks = jax.random.split(key, 4)
    init = jax.nn.initializers.normal(stddev=d ** -0.5)
    down = jax.nn.initializers.normal(stddev=f ** -0.5)
    p = {
        "router": init(ks[0], (d, E), jnp.float32),
        "w_gate": init(ks[1], (n, d, f), jnp.float32),
        "w_up": init(ks[2], (n, d, f), jnp.float32),
        "w_down": down(ks[3], (n, f, d), jnp.float32),
    }
    if cfg.router_score == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.d_ff_shared:
        p["shared"] = F.init_swiglu(jax.random.fold_in(key, 4), d,
                                    cfg.d_ff_shared)
    return p


def _route(cfg, router_w, tokens, bias=None):
    """tokens (T, d) -> (gates (T,k), expert ids (T,k), aux load-balance loss).
    ``bias`` is the sigmoid router's per-expert correction."""
    logits = tokens.astype(jnp.float32) @ router_w             # (T, E)
    if cfg.router_score == "sigmoid":
        # DeepSeek-V3 noaux_tc, one group: the bias chooses, the unbiased
        # scores weigh; no auxiliary loss
        scores = jax.nn.sigmoid(logits)
        _, eidx = lax.top_k(scores + bias.astype(jnp.float32), cfg.top_k)
        gates = jnp.take_along_axis(scores, eidx, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        return gates * cfg.routed_scale, eidx, jnp.zeros((), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e f_e * P_e
    E = cfg.n_experts
    f_e = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0)
    f_e = f_e / jnp.maximum(f_e.sum(), 1.0)
    p_e = probs.mean(0)
    aux = E * jnp.sum(f_e * p_e)
    return gates, eidx, aux


def _held(cfg, eidx):
    """eidx (T, k) router outputs -> (held (T*k,) bool, group (T*k,) int32:
    the held expert's local index, ``count`` for an absent expert, and the
    (count,) sizes of the held groups)."""
    first, count = cfg.held_experts
    local = eidx.reshape(-1).astype(jnp.int32) - first
    held = (local >= 0) & (local < count)
    group = jnp.where(held, local, count)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
    return held, group, sizes


def _counts(n, held, sizes):
    return jnp.stack([jnp.asarray(n, jnp.int32), held.sum(dtype=jnp.int32),
                      (sizes > 0).sum(dtype=jnp.int32)])


def routing_counts(cfg, eidx):
    """int32 (3,): assignments (tokens x top_k), those to held experts, and
    held experts that received at least one token."""
    held, _, sizes = _held(cfg, eidx)
    return _counts(eidx.size, held, sizes)


def _moe_held(cfg, p, tokens):
    """Dropless MoE on a (T, d) token slab over the held experts.

    Returns (y (T, d), aux, routing counts)."""
    T, d = tokens.shape
    k = cfg.top_k
    gates, eidx, aux = _route(cfg, p["router"], tokens, p.get("router_bias"))
    held, group, sizes = _held(cfg, eidx)
    # a token holds at most min(k, count) held assignments: the sorted
    # prefix of that length covers every held one, the rest are absent
    M = T * min(k, cfg.held_experts[1])
    sel = jnp.argsort(group, stable=True)[:M]
    dt = tokens.dtype
    xs = XP.GatherScatter(indices=sel // k, axis=0)(tokens)
    g = lax.ragged_dot(xs, p["w_gate"].astype(dt), sizes)
    u = lax.ragged_dot(xs, p["w_up"].astype(dt), sizes)
    out = lax.ragged_dot(jax.nn.silu(g) * u, p["w_down"].astype(dt), sizes,
                         preferred_element_type=jnp.float32)
    # rows past the held groups come out of ragged_dot as zeros; their
    # weight is zero too
    w = jnp.where(held, gates.reshape(-1), 0.0)[sel][:, None]
    y = jnp.zeros((T, d), jnp.float32).at[sel // k].add(out * w)
    if "shared" in p:
        s = p["shared"]
        h = (jax.nn.silu(tokens @ s["w_gate"].astype(dt))
             * (tokens @ s["w_up"].astype(dt)))
        y = y + jnp.matmul(h, s["w_down"].astype(dt),
                           preferred_element_type=jnp.float32)
    return y.astype(dt), aux, _counts(T * k, held, sizes)


def _dispatch(cfg, tokens, eidx, gates, capacity):
    """Sort-based local dispatch. Returns (buffer (E,C,d), slot (T*k,), keep, order)."""
    T, d = tokens.shape
    k, E, C = cfg.top_k, cfg.n_experts, capacity
    flat_e = eidx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    tok_of = order // k
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)                # sentinel = dropped
    # The expert-order permute is the XDMA GatherScatter stage (index-driven
    # reorder on the stream) — the same plugin a fused dispatch descriptor
    # would emit into its kernel.
    permute = XP.GatherScatter(indices=tok_of, axis=0)
    contrib = jnp.where(keep[:, None], permute(tokens), 0)
    buf = jnp.zeros((E * C + 1, d), tokens.dtype).at[slot].add(contrib)
    return buf[:-1].reshape(E, C, d), slot, keep, order, tok_of


def _expert_ffn(cfg, p, buf):
    """buf (E_local, C*, d) -> same shape; SwiGLU per expert."""
    dt = buf.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))


def _combine(cfg, out_buf, slot, keep, order, gates, T, d):
    flat = jnp.concatenate([out_buf.reshape(-1, d),
                            jnp.zeros((1, d), out_buf.dtype)], 0)
    vals = flat[jnp.minimum(slot, flat.shape[0] - 1)]
    w = gates.reshape(-1)[order].astype(vals.dtype)[:, None]
    y = jnp.zeros((T, d), out_buf.dtype).at[order // cfg.top_k].add(vals * w * keep[:, None])
    return y


# -- every remaining collective as a movement-plane task ---------------------
# Since the movement-plane refactor (DESIGN.md §9) the MoE sublayer issues NO
# raw collectives: the a2a exchange was already descriptor-driven, and the
# residual lax.psum / lax.all_gather / lax.pmean now lower through `reduce`
# and `peer` endpoint descriptors, so a capture() trace sees every byte the
# layer moves.
def _pmean(x, axes, n_total: int):
    """lax.pmean through the plane: reduce-endpoint psum, then the local
    divide (same decomposition pmean itself uses, so bit-identical)."""
    return xdma.transfer(x, reduce_descriptor(axes, n_total)) / n_total


@functools.lru_cache(maxsize=None)
def _hop_desc(axis: str, n: int) -> XDMADescriptor:
    perm = tuple((i, (i + 1) % n) for i in range(n))
    return XDMADescriptor(dst=Endpoint.multicast_axis(axis, perm))


def _ring_all_gather(x, axis_name: str, n: int):
    """``lax.all_gather(x, axis, axis=1, tiled=True)`` decomposed into n-1
    rotating one-hop broadcasts: an all-gather is n simultaneous multicasts
    (every rank's shard fans out to all peers), and on a ring each rotation
    step is one ``multicast_axis`` hop — the same collective permute a
    ``peer`` descriptor lowers to, so the decomposition stays pure data
    movement, bit-identical to the collective, with every hop recorded as a
    ``multicast`` endpoint in the capture ledger (DESIGN.md §14).

    ``x`` is ``(B, S_local, d)``; returns ``(B, n * S_local, d)`` ordered by
    source rank, exactly like the tiled all-gather it replaces.
    """
    if n == 1:
        return x
    parts = [x]
    for _ in range(n - 1):
        parts.append(xdma.transfer(parts[-1], _hop_desc(axis_name, n)))
    stacked = jnp.stack(parts)           # [j] = shard of rank (i - j) % n
    idx = lax.axis_index(axis_name)
    order = jnp.mod(idx - jnp.arange(n), n)
    ordered = jnp.take(stacked, order, axis=0)      # [s] = shard of rank s
    B, S, d = x.shape
    return jnp.moveaxis(ordered, 0, 1).reshape(B, n * S, d)


def _dispatch_queue(model_axis: str, dtype, wire_plugins) -> XDMAQueue:
    """The expert-parallel exchange as the Controller's task queue: task 0 is
    the dispatch all-to-all, task 1 the mirrored return — both endpoint-aware
    descriptors with the wire plugins on the pre host and Dequantize on the
    post (dst half-XDMA) host.  Built once per trace; the descriptor fixes
    geometry + plugin chain so the link carries only payload."""
    pre = tuple(wire_plugins)
    post = (XP.Dequantize(dtype),) if pre else ()
    return XDMAQueue([
        XDMADescriptor(dst=Endpoint.all_to_all(model_axis, split_axis=0,
                                               concat_axis=1),
                       pre=pre, post=post),
        XDMADescriptor(dst=Endpoint.all_to_all(model_axis, split_axis=1,
                                               concat_axis=0),
                       pre=pre, post=post),
    ], name="moe_dispatch")


def _moe_tokens(cfg, p, tokens, *, model_axis: str, n_model: int,
                wire_plugins=(), scheduler=None, overlap_chunks: int = 2):
    """Capacity-buffer MoE on a (T, d) token slab, a2a over ``model_axis``.

    With a :class:`~repro.runtime.DistributedScheduler` the dispatch buffer is
    split into ``overlap_chunks`` capacity slices, each running its own
    dispatch-a2a -> expert FFN -> return-a2a chain: chunks alternate over the
    topology's links while FFN runs on a compute engine, so chunk i+1's
    dispatch overlaps chunk i's FFN in the scheduled timeline (the paper's
    compute-while-transfer at link granularity).  Slot indexing is unchanged —
    chunk c is capacity rows [c*Cc, (c+1)*Cc) of every expert — so the math
    matches the unchunked queue path.
    """
    T, d = tokens.shape
    k, E = cfg.top_k, cfg.n_experts
    gates, eidx, aux = _route(cfg, p["router"], tokens)
    capacity = int(cfg.capacity_factor * k * T // E) + 1

    queue = _dispatch_queue(model_axis, tokens.dtype, wire_plugins)
    chunked = scheduler is not None and overlap_chunks > 1
    buf, slot, keep, order, tok_of = _dispatch(cfg, tokens, eidx, gates, capacity)

    if chunked:
        # pad the *buffer* (not the capacity) to a chunk multiple: slot/keep
        # were computed with the real capacity, so token dropping is identical
        # to the unchunked path and the pad slots are never referenced
        cap_pad = -(-capacity // overlap_chunks) * overlap_chunks
        if cap_pad != capacity:
            buf = jnp.pad(buf, ((0, 0), (0, cap_pad - capacity), (0, 0)))
        links = scheduler.topology.link_names
        Cc = cap_pad // overlap_chunks
        # simulated FFN cost: 3 (Eloc, n*Cc, d)x(d, f) einsums per chunk at a
        # nominal accelerator rate — enough to place compute on the timeline
        ffn_s = 6.0 * E * Cc * d * cfg.d_ff_expert / HW_FLOPS
        futs = []
        for c in range(overlap_chunks):
            sub = lax.slice_in_dim(buf, c * Cc, (c + 1) * Cc, axis=1)
            f_out = scheduler.submit(sub, queue.descriptors[0],
                                     link=links[c % len(links)],
                                     label=f"a2a_dispatch[{c}]")
            f_ffn = scheduler.submit_compute(
                lambda b: _expert_ffn(cfg, p, b), f_out,
                resource="expert_ffn", cost_s=ffn_s,
                label=f"expert_ffn[{c}]")
            futs.append(scheduler.submit(f_ffn, queue.descriptors[1],
                                         link=links[c % len(links)],
                                         label=f"a2a_return[{c}]"))
        scheduler.flush()
        out = jnp.concatenate([f.result() for f in futs], axis=1)
        out = out[:, :capacity]          # drop the pad slots before combine
    else:
        # (E, C, d) -> (E_local, n_model*C, d): the XDMA dispatch tunnel
        buf = queue.run_task(buf, 0)
        out = _expert_ffn(cfg, p, buf)
        out = queue.run_task(out, 1)
    y = _combine(cfg, out, slot, keep, order, gates, T, d)
    return y, aux


def _expert_ffn_tp(cfg, p, buf, model_axis, n_model):
    """TP experts: d_ff sharded over the model axis; the per-layer all-reduce
    is a ``reduce``-endpoint XDMA task (the plane's spelling of psum)."""
    dt = buf.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))
    return xdma.transfer(out, reduce_descriptor(model_axis, n_model))


def ep_enabled(cfg, n_model: int) -> bool:
    return cfg.n_experts % n_model == 0


def moe_apply(cfg, p, x, *, mesh=None, scheduler=None, overlap_chunks: int = 2,
              with_counts: bool = False):
    """x (B, S, d) -> (y, aux_loss), and with ``with_counts`` (local path
    only) the routing counts of :func:`routing_counts` as a third output.

    Distributed (cfg.axes.model set + mesh given): runs under shard_map.
      * EP path (E %% n_model == 0, S %% n_model == 0): sequence-split tokens,
        XDMA all_to_all dispatch to the expert shard, mirrored return.
      * TP path (otherwise, incl. decode S=1): tokens replicated over model,
        expert d_ff sharded, one psum (Megatron-style).
    Local (no mesh): dropless over the held experts (:func:`_moe_held`).

    ``scheduler`` (a :class:`~repro.runtime.DistributedScheduler`) routes the
    EP dispatch through chunked per-link FIFOs so the a2a overlaps expert FFN
    in the scheduled timeline (see :func:`_moe_tokens`); pass a fresh one per
    call and read ``scheduler.report()`` afterwards.
    """
    B, S, d = x.shape
    axes = cfg.axes
    if axes.model is None or mesh is None:
        y, aux, counts = _moe_held(cfg, p, x.reshape(-1, d))
        y = y.reshape(B, S, d)
        return (y, aux, counts) if with_counts else (y, aux)
    if cfg.held_experts != (0, cfg.n_experts):
        raise NotImplementedError("the shard_map MoE paths hold every expert")
    if cfg.router_score != "softmax" or cfg.d_ff_shared:
        raise NotImplementedError("the shard_map MoE paths route by softmax "
                                  "with no shared experts")
    if with_counts:
        raise NotImplementedError("routing counts come from the local path only")

    n_model = mesh.shape[axes.model]
    bspec = axes.batch_spec
    all_axes = tuple(mesh.axis_names)
    n_total = int(mesh.size)
    wire = (XP.Quantize(),) if getattr(cfg, "moe_wire_int8", False) else ()
    use_ep = ep_enabled(cfg, n_model) and S % n_model == 0 and S >= n_model

    def body_ep(xl, router_w, w_gate, w_up, w_down):
        # xl: (B_local, S, d) replicated over model; split S across model ranks
        r = lax.axis_index(axes.model)
        Bl = xl.shape[0]
        Sl = S // n_model
        xs = lax.dynamic_slice(xl, (0, r * Sl, 0), (Bl, Sl, d))
        pl = {"router": router_w, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        y, aux = _moe_tokens(cfg, pl, xs.reshape(-1, d),
                             model_axis=axes.model, n_model=n_model,
                             wire_plugins=wire, scheduler=scheduler,
                             overlap_chunks=overlap_chunks)
        y = _ring_all_gather(y.reshape(Bl, Sl, d), axes.model, n_model)
        aux = _pmean(aux, all_axes, n_total)
        return y, aux

    def body_ep_nosplit(xl, router_w, w_gate, w_up, w_down):
        # decode-scale EP: too few tokens to seq-split, so every model rank
        # routes the full local slab (identical dispatch), the a2a moves only
        # the tiny (E, C, d) token buffer — NEVER the expert weights (a
        # TP<->EP weight reshard inside the decode loop costs ~60 GB/step).
        pl = {"router": router_w, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        y, aux = _moe_tokens(cfg, pl, xl.reshape(-1, d),
                             model_axis=axes.model, n_model=n_model,
                             wire_plugins=wire, scheduler=scheduler,
                             overlap_chunks=overlap_chunks)
        aux = _pmean(aux, all_axes, n_total)
        return y.reshape(xl.shape), aux

    tp_ok = cfg.d_ff_expert % n_model == 0

    def body_tp(xl, router_w, w_gate, w_up, w_down):
        pl = {"router": router_w, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        tokens = xl.reshape(-1, d)
        gates, eidx, aux = _route(cfg, router_w, tokens)
        T = tokens.shape[0]
        capacity = int(cfg.capacity_factor * cfg.top_k * T // cfg.n_experts) + 1
        buf, slot, keep, order, _ = _dispatch(cfg, tokens, eidx, gates, capacity)
        if tp_ok:
            out = _expert_ffn_tp(cfg, pl, buf, axes.model, n_model)
        else:
            out = _expert_ffn(cfg, pl, buf)    # replicated experts (fallback)
        y = _combine(cfg, out, slot, keep, order, gates, T, d)
        aux = _pmean(aux, all_axes, n_total)
        return y.reshape(xl.shape), aux

    if use_ep:
        body = body_ep
        wspecs = [P(axes.model, None, None)] * 3
    elif ep_enabled(cfg, n_model):
        body = body_ep_nosplit
        wspecs = [P(axes.model, None, None)] * 3
    elif tp_ok:
        body = body_tp
        wspecs = [P(None, None, axes.model), P(None, None, axes.model),
                  P(None, axes.model, None)]
    else:
        body = body_tp
        wspecs = [P(), P(), P()]
    in_specs = (P(bspec, None, None), P(), *wspecs)
    out_specs = (P(bspec, None, None), P())
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    y, aux = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux
