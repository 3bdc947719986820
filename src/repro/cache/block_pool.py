"""Paged KV block pool — the framework's "SSD cache" device substrate.

The pool is a set of fixed-size pages living in device arrays
(``[n_pages, page_size, kv_heads, head_dim]`` per layer per k/v); page
*contents* stay on device and are only touched by JAX ops (scatter of fresh
KV, gather via block tables inside the paged-attention kernel).  Every
update donates the pool's arrays to one jitted program, so XLA writes the
existing buffers in place instead of copying the whole pool.  Page
*metadata* — free list, per-tenant LRU ordering, content keys for prefix
reuse — is host-side, exactly like vLLM's block manager.

Every metadata operation emits a block-access event (read = page re-use,
write = page admission) that the ECI-Cache ``Monitor`` consumes: the pool
IS the cache the paper's algorithms manage.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PageMeta", "BlockPool"]


@partial(jax.jit, donate_argnums=0)
def _set_page(pages, new, pid):
    """Each array of ``pages`` with page ``pid`` of every layer set to its
    entry of ``new``; ``pages`` is donated, so the write is in place."""
    return tuple(p.at[:, pid].set(n) for p, n in zip(pages, new))


@partial(jax.jit, donate_argnums=0)
def _set_tokens(pages, new, pids, offs):
    """Each array of ``pages`` with request b's token ``new[:, b]`` set at
    (every layer, ``pids[b]``, ``offs[b]``); donated, so in place."""
    return tuple(p.at[:, pids, offs].set(n) for p, n in zip(pages, new))


@dataclasses.dataclass
class PageMeta:
    tenant: int = -1
    key: tuple | None = None      # content key (tenant, prefix-page hash)
    dirty: bool = False
    pinned: bool = False          # in-flight pages are never evicted


class BlockPool:
    """Host-side manager of a device-resident paged pool.

    Device arrays (one per layer): k_pages/v_pages.  The manager hands out
    page ids; per-tenant LRU + quota enforcement implement the Actuator's
    partition decisions.
    """

    def __init__(self, n_pages: int, page_size: int, n_layers: int,
                 kv_heads: int, head_dim: int, dtype=jnp.bfloat16,
                 allocate_device: bool = True):
        self.n_pages = n_pages
        self.page_size = page_size
        self.shape = (n_layers, n_pages, page_size, kv_heads, head_dim)
        if allocate_device:
            self.k_pages = jnp.zeros(self.shape, dtype)
            self.v_pages = jnp.zeros(self.shape, dtype)
        else:                       # metadata-only mode (tests/benchmarks)
            self.k_pages = self.v_pages = None
        self.free: list[int] = list(range(n_pages - 1, -1, -1))
        self.meta: dict[int, PageMeta] = {}
        # per-tenant LRU of resident pages: tenant -> OrderedDict[page_id]
        self.lru: dict[int, OrderedDict[int, None]] = {}
        self.by_key: dict[tuple, int] = {}
        # pool_copies: device updates whose donation did not take, so the
        # update copied the pool (0 on every backend that honours donation)
        self.stats = {"admitted": 0, "evicted": 0, "reused": 0,
                      "writes": 0, "pool_copies": 0}
        # demote-on-evict hook: called as on_evict(pid, meta) after a page
        # leaves the pool (TieredKVCache pushes the victim into the managed
        # host tier — the L2 of the serving hierarchy)
        self.on_evict = None

    # ------------------------------------------------------------ metadata
    def resident(self, tenant: int) -> int:
        return len(self.lru.get(tenant, ()))

    def lookup(self, key: tuple) -> int | None:
        """Prefix-cache hit test; bumps LRU on hit."""
        pid = self.by_key.get(key)
        if pid is not None:
            m = self.meta[pid]
            self.lru[m.tenant].move_to_end(pid)
            self.stats["reused"] += 1
        return pid

    def allocate(self, tenant: int, key: tuple | None = None,
                 quota: int | None = None,
                 dirty: bool = False) -> tuple[int | None, list[int]]:
        """Allocate one page for ``tenant``; evicts LRU pages of the same
        tenant while over quota.  Returns (page_id | None, evicted_ids)."""
        evicted: list[int] = []
        q = self.lru.setdefault(tenant, OrderedDict())
        if quota is not None and quota <= 0:
            return None, evicted
        while quota is not None and len(q) >= quota:
            v = self._evict_one(tenant)
            if v is None:
                return None, evicted        # all resident pages pinned
            evicted.append(v)
        if not self.free:
            victim = self._evict_any(tenant)
            if victim is None:
                return None, evicted
            evicted.append(victim)
        pid = self.free.pop()
        self.meta[pid] = PageMeta(tenant, key, dirty)
        q[pid] = None
        if key is not None:
            self.by_key[key] = pid
        self.stats["admitted"] += 1
        self.stats["writes"] += 1
        return pid, evicted

    def _evict_one(self, tenant: int) -> int | None:
        q = self.lru[tenant]
        for pid in q:                       # LRU-first, skipping pinned
            if not self.meta[pid].pinned:
                break
        else:
            return None                     # everything in flight
        q.pop(pid)
        m = self.meta.pop(pid)
        if m.key is not None:
            self.by_key.pop(m.key, None)
        self.free.append(pid)
        self.stats["evicted"] += 1
        if self.on_evict is not None:
            self.on_evict(pid, m)
        return pid

    def pin(self, pid: int) -> None:
        if pid in self.meta:
            self.meta[pid].pinned = True

    def unpin(self, pid: int) -> None:
        if pid in self.meta:
            self.meta[pid].pinned = False

    def _evict_any(self, prefer_tenant: int) -> int | None:
        if self.lru.get(prefer_tenant):
            return self._evict_one(prefer_tenant)
        for t, q in self.lru.items():
            if q:
                v = self._evict_one(t)
                if v is not None:
                    return v
        return None

    def release_tenant(self, tenant: int) -> int:
        """Free all pages of a finished tenant (paper §6.3 retire)."""
        n = 0
        for pid in list(self.lru.get(tenant, ())):
            self.meta[pid].pinned = False
        while self.lru.get(tenant):
            if self._evict_one(tenant) is None:
                break
            n += 1
        return n

    def enforce_quota(self, tenant: int, quota: int) -> list[int]:
        """Actuator resize: shrink a tenant's residency to ``quota``."""
        out = []
        q = self.lru.setdefault(tenant, OrderedDict())
        while len(q) > quota:
            v = self._evict_one(tenant)
            if v is None:
                break
            out.append(v)
        return out

    # -------------------------------------------------------- device data
    def write_page(self, layer_slice_k: jax.Array, layer_slice_v: jax.Array,
                   pid: int) -> None:
        """Scatter one page of fresh KV into the pool (all layers).

        layer_slice_*: [n_layers, page_size, kv_heads, head_dim].
        """
        self._update(_set_page, (layer_slice_k, layer_slice_v),
                     np.int32(pid))

    def write_tokens(self, k_new: jax.Array, v_new: jax.Array,
                     pids: np.ndarray, offs: np.ndarray) -> None:
        """Write one decode step's tokens: request b's KV at (every layer,
        page ``pids[b]``, offset ``offs[b]``).

        k_new/v_new: [n_layers, B, kv_heads, head_dim].
        """
        self._update(_set_tokens, (k_new, v_new),
                     np.asarray(pids, np.int32), np.asarray(offs, np.int32))

    def _update(self, fn, new, *where) -> None:
        if self.k_pages is None:
            return
        old = (self.k_pages, self.v_pages)
        self.k_pages, self.v_pages = fn(old, new, *where)
        if not all(a.is_deleted() for a in old):
            self.stats["pool_copies"] += 1
