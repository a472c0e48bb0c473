"""Bytes of the KV relayout round trip, from shapes.

One request stores the K and V of every layer, (1, S, KV, hd) each, with
RMSNorm and tiling (read once, write once), then loads them back
transposed (read once, write once): four passes over 2 x L x S x KV x hd
elements.  That is the work the relayout rate counts.

The least HBM traffic is two of those passes: the inputs read once and the
outputs written once.  The tiled intermediate need not reach HBM (XLA may
place a kernel's operands in VMEM), so shares of the HBM peak use this.
"""
from __future__ import annotations


def request_bytes(layers: int, seq_len: int, kv_heads: int, head_dim: int,
                  itemsize: int) -> int:
    return 4 * 2 * layers * seq_len * kv_heads * head_dim * itemsize


def request_min_hbm_bytes(layers: int, seq_len: int, kv_heads: int,
                          head_dim: int, itemsize: int) -> int:
    return request_bytes(layers, seq_len, kv_heads, head_dim, itemsize) // 2
