"""Serving launcher: multi-tenant engine + ECI-managed pool.

Serves ``--arch`` at its published widths with seeded weights; ``--smoke``
serves the architecture's reduced ``SMOKE_CONFIG`` instead (CPU-sized).
The KV page pool is held in the model's dtype.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b \
        --requests 12
    PYTHONPATH=src python -m repro.launch.serve --smoke    # reduced config
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import BlockPool, TieredKVCache
from repro.configs import get_config, get_smoke_config
from repro.core import ECICacheManager
from repro.launch.compile_cache import setup_compile_cache
from repro.models import model as M
from repro.models.attention import build_heads
from repro.serve.engine import MultiTenantEngine, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced SMOKE_CONFIG")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--pool-pages", type=int, default=512)
    ap.add_argument("--capacity", type=int, default=192)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    args = ap.parse_args()

    setup_compile_cache()
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    hq, hkv = build_heads(cfg, 1)
    pool = BlockPool(args.pool_pages, args.page_size, cfg.n_layers, hkv,
                     cfg.head_dim, dtype=jnp.dtype(cfg.dtype))
    manager = ECICacheManager(
        args.capacity, [f"tenant{i}" for i in range(args.tenants)],
        c_min=8, initial_blocks=args.capacity // max(args.tenants, 1))
    tiered = TieredKVCache(pool, manager, window_events=128)
    engine = MultiTenantEngine(cfg, params, tiered,
                               page_size=args.page_size,
                               max_pages_per_seq=32)

    rng = np.random.default_rng(0)
    shared = {t: rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
              for t in range(args.tenants)}
    t0 = time.perf_counter()
    for i in range(args.requests):
        t = i % args.tenants
        prompt = np.concatenate(
            [shared[t], rng.integers(0, cfg.vocab_size, 8).astype(np.int32)])
        engine.submit(Request(tenant=t, prompt=prompt,
                              max_new_tokens=args.max_new_tokens))
    engine.run(max_steps=args.requests * args.max_new_tokens + 8)
    dt = time.perf_counter() - t0

    done = len(engine.completed)
    toks = sum(len(r.generated) for r in engine.completed)
    print(f"served {done}/{args.requests} requests, {toks} tokens "
          f"in {dt:.1f}s ({toks / dt:.1f} tok/s)")
    print("pool:", tiered.summary())


if __name__ == "__main__":
    main()
