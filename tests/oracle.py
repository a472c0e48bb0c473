"""Pure-numpy oracle for ``xdma.transfer`` — the differential-test ground truth.

Everything here is deliberately *independent* of the JAX implementation: the
layout algebra is re-derived as a pure-numpy *pattern walk* (a flat gather
driven by ``AffinePattern.addresses()`` — see :func:`to_logical` /
:func:`relayout_oracle`), every registered plugin has a numpy
re-implementation, and remote movements are modelled on a size-1 mesh axis
(where the link collective is the identity, so the oracle is the plugin
composition around an identity link).  ``tests/test_differential.py`` asserts
``xdma.transfer == oracle`` over randomly generated descriptors.

Payload pytrees mirror the engine's: :class:`OQTensor` / :class:`OCTensor`
are plain-numpy twins of ``QTensor`` / ``CTensor`` with the same fields.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Sequence, Tuple

import ml_dtypes
import numpy as np

from repro.core import layouts as L
from repro.core import plugins as P
from repro.core.descriptor import XDMADescriptor


@dataclasses.dataclass
class OQTensor:
    values: np.ndarray
    scales: np.ndarray


@dataclasses.dataclass
class OCTensor:
    values: np.ndarray
    mask: np.ndarray


# -- layout algebra, re-derived as a pattern walk -----------------------------
# The oracle walks ``AffinePattern.addresses()`` with a flat numpy gather —
# the address stream IS the layout semantics (one code path for tiled,
# permuted, padded, and rank-3+ layouts), and it never touches the JAX
# reshape/transpose implementation it is testing.
def _plain(layout: L.Layout) -> bool:
    return (layout.tile is None and not layout.is_permuted
            and not layout.is_padded)


def to_logical(x: np.ndarray, layout: L.Layout) -> np.ndarray:
    if _plain(layout):
        return x
    logical = layout.logical_shape(x.shape)
    pat = L.affine_pattern(layout, logical)
    return np.ascontiguousarray(x).reshape(-1)[pat.addresses()].reshape(logical)


def from_logical(x: np.ndarray, layout: L.Layout) -> np.ndarray:
    if _plain(layout):
        return x
    layout.check(x.shape)
    pat = L.affine_pattern(layout, x.shape)
    phys = layout.physical_shape(x.shape)
    out = np.zeros((int(np.prod(phys)),), dtype=x.dtype)
    out[pat.addresses()] = np.ascontiguousarray(x).reshape(-1)
    return out.reshape(phys)


def relayout_oracle(x: np.ndarray, src_layout: L.Layout, dst_layout: L.Layout,
                    *, transpose: bool = False) -> np.ndarray:
    """Ground truth for a pure relayout: the composed ``src⁻¹∘dst`` pattern
    walked as one flat gather/scatter (stride padding reads back as zeros)."""
    logical = src_layout.logical_shape(x.shape)
    pair = L.relayout_pair(src_layout, dst_layout, logical,
                           transpose=transpose)
    if pair is None:
        raise ValueError("no common loop-nest refinement for this pair")
    out_logical = (tuple(logical[:-2]) + (logical[-1], logical[-2])
                   if transpose else tuple(logical))
    phys = dst_layout.physical_shape(out_logical)
    flat = pair.gather(np.ascontiguousarray(x).reshape(-1),
                       int(np.prod(phys)))
    return flat.reshape(phys)


# -- plugin semantics, re-implemented with numpy ------------------------------
def apply_plugin(p: P.Plugin, x: Any) -> Any:
    if isinstance(p, P.Identity):
        return x
    if isinstance(p, P.Transpose):
        return np.swapaxes(x, -1, -2)
    if isinstance(p, P.Cast):
        return x.astype(np.dtype(p.dtype))
    if isinstance(p, P.Scale):
        return x * np.asarray(p.alpha, dtype=x.dtype)
    if isinstance(p, P.BiasAdd):
        return x + np.asarray(p.bias, dtype=x.dtype)
    if isinstance(p, P.RMSNormPlugin):
        xf = x.astype(np.float32)
        rms = 1.0 / np.sqrt(np.mean(xf * xf, axis=-1, keepdims=True) + p.eps)
        y = xf * rms
        if p.weight is not None:
            y = y * np.asarray(p.weight, dtype=np.float32)
        return y.astype(x.dtype)
    if isinstance(p, P.Quantize):
        xf = x.astype(np.float32)
        amax = np.max(np.abs(xf), axis=-1, keepdims=True)
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.round(xf / scale), -127, 127).astype(np.int8)
        return OQTensor(values=q, scales=scale)
    if isinstance(p, P.Dequantize):
        return (x.values.astype(np.float32) * x.scales).astype(np.dtype(p.dtype))
    if isinstance(p, P.GatherScatter):
        return np.take(x, np.asarray(p.indices), axis=p.axis)
    if isinstance(p, P.Compress):
        m = x.shape[-2]
        blocks = x.reshape(x.shape[:-2] + (m // p.block_rows, p.block_rows,
                                           x.shape[-1]))
        mask = np.any(blocks != 0, axis=(-1, -2))
        return OCTensor(values=x, mask=mask)
    if isinstance(p, P.Decompress):
        v, mask = x.values, x.mask
        block_rows = v.shape[-2] // mask.shape[-1]
        keep = np.repeat(mask, block_rows, axis=-1).astype(v.dtype)
        return v * keep[..., :, None]
    if isinstance(p, P.ReduceStage):
        if p.op == "max":
            return np.max(x, axis=-2, keepdims=p.keepdims)
        # jnp.sum accumulates half-precision inputs in f32; match it
        acc = x.astype(np.float32) if x.dtype.itemsize < 4 else x
        return np.sum(acc, axis=-2, keepdims=p.keepdims).astype(x.dtype)
    raise NotImplementedError(f"oracle has no model for plugin {p.name!r}")


def apply_chain(plugins: Sequence[P.Plugin], x: Any) -> Any:
    for p in plugins:
        x = apply_plugin(p, x)
    return x


def _write(y: Any, layout: L.Layout) -> Any:
    if isinstance(y, OQTensor):
        return OQTensor(values=from_logical(y.values, layout), scales=y.scales)
    if isinstance(y, OCTensor):
        return OCTensor(values=from_logical(y.values, layout), mask=y.mask)
    return from_logical(y, layout)


def oracle_transfer(x, desc: XDMADescriptor) -> Any:
    """Ground truth for ``xdma.transfer(x, desc)``.

    Local movements are exact by construction; remote movements assume the
    size-1 mesh axis the differential tests run on, where peer / all_to_all /
    psum links are the identity and the movement reduces to the two plugin
    hosts around it.  (Reduce descriptors with a Quantize/Dequantize codec
    take the ``compressed_psum`` two-phase path instead — keep codecs out of
    generated reduce chains, or model them separately.)
    """
    x = np.asarray(x)
    if desc.movement == "reduce" and any(isinstance(p, P.Quantize)
                                         for p in desc.pre):
        raise NotImplementedError("oracle does not model the compressed_psum "
                                  "codec; keep Quantize out of reduce chains")
    logical = to_logical(x, desc.src.layout)
    y = apply_chain(desc.pre, logical)     # pre host (src half-XDMA)
    # the link: identity on a size-1 axis, for all three remote kinds
    y = apply_chain(desc.post, y)          # post host (dst half-XDMA)
    return _write(y, desc.dst.layout)


# -- excess precision: the answers XLA may give for half-precision sums -------
# XLA may drop an f32 -> bf16 -> f32 convert pair (the CPU backend does), so a
# half-precision stage's output can reach the next stage unrounded.  Elsewhere
# that is within the half-precision tolerance; a sum, though, adds up the
# per-term rounding (and can cancel to near zero).  These answers model the
# elision exactly instead of widening the tolerance: every subset of the
# half-precision stages may skip its rounding.
_HALF = (np.dtype(np.float16), np.dtype(ml_dtypes.bfloat16))


def _chain_rounding_at(plugins, x: np.ndarray, keep) -> Any:
    """apply_chain with the value carried in float32, rounded to its
    half-precision dtype only after the stages ``keep`` marks."""
    dtype = x.dtype
    v = x.astype(np.float32)
    for p, k in zip(plugins, keep):
        dtype = np.dtype(p.out_dtype(dtype))
        if not isinstance(p, P.Cast):
            v = apply_plugin(p, v)
        if k and dtype in _HALF and isinstance(v, np.ndarray):
            v = v.astype(dtype).astype(np.float32)
    return v.astype(dtype)


def oracle_answers(x, desc: XDMADescriptor) -> list:
    """Every answer ``xdma.transfer(x, desc)`` may give: the strict
    :func:`oracle_transfer`, and for a sum over half-precision values, the
    same chain with each subset of its half-precision roundings elided."""
    want = oracle_transfer(x, desc)
    chain = tuple(desc.pre) + tuple(desc.post)
    if (not any(isinstance(p, P.ReduceStage) and p.op == "sum" for p in chain)
            or any(isinstance(p, (P.Quantize, P.Dequantize)) for p in chain)):
        return [want]
    dtype, sites = np.asarray(x).dtype, []
    for i, p in enumerate(chain[:-1]):     # the stored output always rounds
        dtype = np.dtype(p.out_dtype(dtype))
        if dtype in _HALF:
            sites.append(i)
    logical = to_logical(np.asarray(x), desc.src.layout)
    answers = [want]
    for kept in itertools.product((True, False), repeat=len(sites)):
        if all(kept):
            continue                       # the strict answer, already in
        keep = [True] * len(chain)
        for i, k in zip(sites, kept):
            keep[i] = k
        y = _chain_rounding_at(chain, logical, keep)
        answers.append(_write(y, desc.dst.layout))
    return answers


def assert_matches_any(got: Any, wants: Sequence[Any], **kw) -> None:
    """got ~= at least one of ``wants`` (see :func:`assert_matches`); on a
    miss, reports the mismatch against the strict answer ``wants[0]``."""
    for want in wants[1:]:
        try:
            assert_matches(got, want, **kw)
            return
        except AssertionError:
            pass
    assert_matches(got, wants[0], **kw)


def assert_matches(got: Any, want: Any, *, rtol: float = 2e-5,
                   atol: float = 1e-5, context: str = "") -> None:
    """got (jax, QTensor/CTensor/array) ~= want (oracle).  Tolerances are for
    float drift (np vs XLA reduction order, rsqrt rounding); integer payloads
    allow one quantization step."""
    if isinstance(want, OQTensor):
        dv = np.abs(np.asarray(got.values, np.int32) -
                    want.values.astype(np.int32))
        assert dv.max(initial=0) <= 1, f"{context}: int8 values off by >1 step"
        np.testing.assert_allclose(np.asarray(got.scales), want.scales,
                                   rtol=rtol, atol=atol, err_msg=context)
        return
    if isinstance(want, OCTensor):
        np.testing.assert_array_equal(np.asarray(got.mask), want.mask,
                                      err_msg=context)
        got = got.values
        want = want.values
    got = np.asarray(got)
    assert got.shape == want.shape, f"{context}: {got.shape} != {want.shape}"
    assert got.dtype == want.dtype, f"{context}: {got.dtype} != {want.dtype}"
    if want.dtype == np.dtype(np.int8):
        assert np.abs(got.astype(np.int32) -
                      want.astype(np.int32)).max(initial=0) <= 1, context
        return
    f32 = np.float32
    np.testing.assert_allclose(got.astype(f32), want.astype(f32),
                               rtol=rtol, atol=atol, err_msg=context)


def chain_tolerance(*descs, logical=None) -> dict:
    """rtol/atol for oracle comparisons, scaled to the chain's precision loss.

    One float ulp of np-vs-XLA drift upstream of a rounding stage can flip
    that rounding: a Quantize/Dequantize roundtrip turns it into one int8
    quantum (~amax/127), a half-precision Cast into one bf16 ulp (relative
    2^-8).  Plain float chains stay at float32 comparison noise.

    With ``logical`` (the chain's logical input, numpy) each
    ``ReduceStage("sum")`` widens the absolute tolerance by what a sum of
    its rows can differ by: np sums pairwise and XLA in its own order, and
    the first-order bound on a sum's rounding error is linear in its number
    of terms (the tolerances above are calibrated at 128 terms).  The
    rounding XLA may skip in a sum of half-precision terms is not a
    tolerance: :func:`oracle_answers` models it."""
    chain = [p for d in descs for p in tuple(d.pre) + tuple(d.post)]
    if any(isinstance(p, P.Dequantize) for p in chain):
        return dict(rtol=5e-2, atol=0.25)
    half = any(isinstance(p, P.Cast) and np.dtype(p.dtype).itemsize < 4
               for p in chain)
    tol = dict(rtol=2e-2, atol=1e-2) if half else dict(rtol=2e-5, atol=1e-5)
    if logical is not None:
        y = np.asarray(logical)
        for p in chain:
            if isinstance(p, P.ReduceStage) and p.op == "sum":
                tol["atol"] *= max(1.0, y.shape[-2] / 128)
            y = apply_plugin(p, y)
    return tol
