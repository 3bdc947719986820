"""How a serving cell gets its system: the program's own builder, found by
name, or this module's stand-in where the program has none.

The builder's contract (``builder()`` returns it):

    build_server(cfg, params, *, tenants, pool_pages, page_size,
                 max_pages_per_seq, pool_dtype, window_events, manager)

``cfg`` is the program's ``ModelConfig``, ``params`` its weights,
``pool_dtype`` a dtype or its name and ``manager`` a dict of
``ECICacheManager`` keywords.  It returns an object with the attributes
``pool`` (the KV page pool: ``BlockPool``'s page and metadata API, its
device arrays in whatever layout the model needs), ``manager``,
``tiered`` and ``engine`` (``MultiTenantEngine``).  The layout of the
pool is the program's to decide, so a model with another cache (latent
pages, a recurrent state) gets a cell with no edit to the harness.

``repro.launch.serve.build_server`` is the program's builder.  Until the
program has one, ``stand_in`` builds the system as
``repro.launch.serve.main`` builds it: the K and V pages of
``BlockPool(pool_pages, page_size, n_layers, kv_heads, head_dim)``, which
fit grouped-query attention only; it refuses a model of another
attention.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass

PROGRAM_BUILDER = ("repro.launch.serve", "build_server")


@dataclass
class ServingSystem:
    pool: object
    manager: object
    tiered: object
    engine: object


def builder():
    """The program's ``build_server`` where it has one, else ``stand_in``."""
    module, name = PROGRAM_BUILDER
    return getattr(importlib.import_module(module), name, stand_in)


def stand_in(cfg, params, *, tenants, pool_pages, page_size,
             max_pages_per_seq, pool_dtype, window_events, manager):
    """``repro.launch.serve.main``'s construction: pool, manager, tiered
    cache, engine, in that order."""
    import jax.numpy as jnp
    from repro.cache import BlockPool, TieredKVCache
    from repro.core import ECICacheManager
    from repro.models.attention import build_heads
    from repro.models.config import AttnKind
    from repro.serve.engine import MultiTenantEngine
    if cfg.attn == AttnKind.MLA:
        raise ValueError(
            f"{cfg.name}: latent attention needs the program's own "
            f"{'.'.join(PROGRAM_BUILDER)}; the stand-in builds K and V "
            "pages of grouped-query attention only")
    _, hkv = build_heads(cfg, 1)
    pool = BlockPool(pool_pages, page_size, cfg.n_layers, hkv, cfg.head_dim,
                     dtype=jnp.dtype(pool_dtype))
    mgr = ECICacheManager(tenant_names=[f"tenant{i}" for i in range(tenants)],
                          **manager)
    tiered = TieredKVCache(pool, mgr, window_events=window_events)
    engine = MultiTenantEngine(cfg, params, tiered, page_size=page_size,
                               max_pages_per_seq=max_pages_per_seq)
    return ServingSystem(pool, mgr, tiered, engine)
