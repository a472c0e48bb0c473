"""Serving launcher: batched greedy generation with the XDMA-tiled KV path.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 2 --prompt-len 16 --gen 12
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.data.pipeline import SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.models import lm
from repro.serving.engine import ServingEngine

log = logging.getLogger("repro.serve")


def main(argv=None):
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
    eng = ServingEngine(cfg, params, max_len=args.prompt_len + args.gen + 8)

    ds = SyntheticLM(vocab=cfg.vocab, seq_len=args.prompt_len,
                     global_batch=args.batch, seed=args.seed,
                     family=cfg.family, d_model=cfg.d_model,
                     encoder_seq=cfg.encoder_seq)
    raw = ds.batch_at(0)
    batch = {k: jnp.asarray(v) for k, v in raw.items() if k != "labels"}

    t0 = time.time()
    out = eng.generate(batch, args.gen)
    dt = time.time() - t0
    toks = args.batch * args.gen
    log.info("generated %dx%d tokens in %.2fs (%.1f tok/s)",
             args.batch, args.gen, dt, toks / dt)
    print(out)


if __name__ == "__main__":
    main()
