"""The paper's §III-C loop on a live model: disaggregated prefill/decode with
XDMA KV movement.

  PYTHONPATH=src python examples/kv_cache_serving.py

Flow (paper Fig. 1): a prefill stage computes the KV cache (GeMM cluster,
tiled layout), XDMA streams it — RMSNorm fused on store, transpose fused on
load — and a decode stage consumes it.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro import core as C
from repro.models import lm
from repro.serving.engine import ServingEngine
from repro.serving.transfer import kv_load_transposed, kv_prefill_store

# reduced qwen3 with a KV geometry that matches the MXU tile (d_kv = 512,
# like the paper's DeepSeek-V3 KV shape)
cfg = dataclasses.replace(configs.smoke_config("qwen3-1.7b"), dtype=jnp.float32,
                          n_heads=8, n_kv_heads=8, head_dim=64)
params = lm.init_params(jax.random.PRNGKey(0), cfg)

# ---- prefill stage ---------------------------------------------------------
B, S = 2, 64
prompt = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)}
cache = lm.init_cache(cfg, B, max_len=S + 32, dtype=jnp.float32)
logits, cache = jax.jit(lambda p, b, c: lm.prefill(cfg, p, b, c))(params, prompt, cache)
print("prefill done; cache pos =", int(cache["pos"]))

# ---- XDMA movement: store the K cache tiled (+norm), load transposed ------
k0 = cache["blocks"][0]["k"][0, :, :S]           # layer-0 K, (B, S, KV, hd)
tiled = kv_prefill_store(k0)
print("K stored tiled:", tiled.shape, "(paper Prefill workload)")
kt = kv_load_transposed(tiled)
print("K loaded as K^T:", kt.shape, "(paper Load workload)")

# the engine-level equivalent with an explicit descriptor:
desc = C.describe("MN", C.layout_for_dtype(jnp.float32), C.RMSNormPlugin())
print("descriptor:", desc.summary())

# ---- decode stage ----------------------------------------------------------
tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
outs = []
dec = jax.jit(lambda p, t, c: lm.decode_step(cfg, p, t, c))
for _ in range(8):
    outs.append(tok)
    logits, cache = dec(params, tok, cache)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
print("decoded:", jnp.concatenate(outs, 1)[0].tolist())
