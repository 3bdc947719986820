"""Serving engine + tiered cache + block pool behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import BlockPool, TieredKVCache
from repro.configs import get_smoke_config
from repro.core import ECICacheManager, WritePolicy
from repro.models import model as M
from repro.models.attention import build_heads
from repro.serve.engine import MultiTenantEngine, Request

KEY = jax.random.PRNGKey(0)


def _engine(n_pages=256, window_events=10**9, capacity=128, page=8,
            tenants=("t0", "t1"), dtype=jnp.float32):
    cfg = get_smoke_config("qwen3_0_6b")
    params = M.init_params(cfg, KEY, tp=1)
    hq, hkv = build_heads(cfg, 1)
    pool = BlockPool(n_pages, page, cfg.n_layers, hkv, cfg.head_dim,
                     dtype=dtype)
    mgr = ECICacheManager(capacity, list(tenants), c_min=8,
                          initial_blocks=32)
    tiered = TieredKVCache(pool, mgr, window_events=window_events)
    return MultiTenantEngine(cfg, params, tiered, page_size=page,
                             max_pages_per_seq=16), pool, tiered, cfg, params


def test_prefix_reuse_across_requests():
    eng, pool, tiered, cfg, _ = _engine()
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    for _ in range(3):
        eng.submit(Request(tenant=0, prompt=prefix.copy(), max_new_tokens=2))
    eng.run(16)
    assert pool.stats["reused"] >= 4            # 2 shared pages × 2 reuses
    assert tiered.stats[0].hbm_hits >= 4


def test_paged_decode_matches_dense_decode():
    eng, pool, tiered, cfg, params = _engine()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    eng.submit(Request(tenant=0, prompt=prompt, max_new_tokens=4))
    eng.run(16)
    paged_tokens = eng.completed[0].generated

    cache = M.init_decode_cache(cfg, 1, 64)
    out = None
    for t in range(len(prompt)):
        out, cache = M.decode_step(params, cfg,
                                   jnp.asarray(prompt[t:t + 1]), cache)
    dense_tokens = [int(jnp.argmax(out[0, :cfg.vocab_size]))]
    for _ in range(3):
        out, cache = M.decode_step(
            params, cfg, jnp.asarray([dense_tokens[-1]], jnp.int32), cache)
        dense_tokens.append(int(jnp.argmax(out[0, :cfg.vocab_size])))
    assert paged_tokens == dense_tokens


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_in_place_pool_writes_are_the_eager_writes(dtype):
    """The donated page and token writes leave the pool bit-equal to the
    eager ``.at[].set`` updates they replace, and copy nothing."""
    eng, pool, tiered, cfg, _ = _engine(dtype=dtype)
    shadow = [jnp.zeros(pool.shape, dtype), jnp.zeros(pool.shape, dtype)]
    calls = {"pages": 0, "tokens": 0}
    write_page, write_tokens = pool.write_page, pool.write_tokens

    def page(k, v, pid):
        calls["pages"] += 1
        shadow[0] = shadow[0].at[:, pid].set(k)
        shadow[1] = shadow[1].at[:, pid].set(v)
        write_page(k, v, pid)

    def tokens(k_new, v_new, pids, offs):
        calls["tokens"] += 1
        # the engine's old eager scatter, from the requests themselves
        reqs = [r for r in eng.active if not r.done]
        pids_ = np.array([r.pages[r.length // eng.page] for r in reqs])
        offs_ = np.array([r.length % eng.page for r in reqs])
        L, B = k_new.shape[:2]
        li = np.repeat(np.arange(L), B)
        pi, oi = np.tile(pids_, L), np.tile(offs_, L)
        shadow[0] = shadow[0].at[li, pi, oi].set(
            k_new.reshape(L * B, *k_new.shape[2:]))
        shadow[1] = shadow[1].at[li, pi, oi].set(
            v_new.reshape(L * B, *v_new.shape[2:]))
        write_tokens(k_new, v_new, pids, offs)

    pool.write_page, pool.write_tokens = page, tokens
    rng = np.random.default_rng(4)
    for t, n in ((0, 13), (1, 20)):          # both cross a page in decode
        eng.submit(Request(tenant=t, prompt=rng.integers(
            0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=6))
    eng.run(16)
    assert len(eng.completed) == 2
    assert calls == {"pages": 5, "tokens": 5}
    assert pool.stats["pool_copies"] == 0
    assert tiered.summary()["pool_copies"] == 0
    for got, want in zip((pool.k_pages, pool.v_pages), shadow):
        got, want = np.asarray(got), np.asarray(want)
        assert np.count_nonzero(got.any(axis=(0, 3, 4))) == 13 + 5 + 20 + 5
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("write", ["page", "tokens"])
def test_donated_pool_write_leaves_no_stale_handle(write):
    pool = BlockPool(8, 4, 2, 2, 16, dtype=jnp.bfloat16)
    old = (pool.k_pages, pool.v_pages)
    if write == "page":
        new = jnp.ones((2, 4, 2, 16), jnp.bfloat16)
        pool.write_page(new, new, 3)
    else:
        new = jnp.ones((2, 3, 2, 16), jnp.bfloat16)
        pool.write_tokens(new, new, [1, 5, 6], [0, 3, 2])
    assert all(a.is_deleted() for a in old)
    for a in (pool.k_pages, pool.v_pages):
        assert not a.is_deleted()
        assert a.shape == pool.shape and a.dtype == jnp.bfloat16
        assert int(jnp.count_nonzero(a)) == new.size
    assert pool.stats["pool_copies"] == 0

    meta_only = BlockPool(8, 4, 2, 2, 16, allocate_device=False)
    meta_only.write_page(new, new, 3)
    meta_only.write_tokens(new, new, [1, 5, 6], [0, 3, 2])
    assert meta_only.k_pages is None and meta_only.v_pages is None
    assert meta_only.stats["pool_copies"] == 0


def test_pool_copies_counts_an_update_that_copied():
    """An update whose old buffers outlive it (no donation) is counted."""
    pool = BlockPool(8, 4, 2, 2, 16, dtype=jnp.float32)
    new = jnp.ones((2, 4, 2, 16), jnp.float32)
    copy = jax.jit(lambda pages, new, pid: tuple(
        p.at[:, pid].set(n) for p, n in zip(pages, new)))
    pool._update(copy, (new, new), np.int32(2))
    assert pool.stats["pool_copies"] == 1
    pool.write_page(new, new, 2)
    assert pool.stats["pool_copies"] == 1


def test_ro_policy_bypasses_admissions():
    eng, pool, tiered, cfg, _ = _engine()
    tiered.policies[1] = WritePolicy.RO
    rng = np.random.default_rng(2)
    eng.submit(Request(tenant=1,
                       prompt=rng.integers(0, cfg.vocab_size, 24
                                           ).astype(np.int32),
                       max_new_tokens=2))
    eng.run(8)
    assert tiered.stats[1].bypassed_writes > 0
    assert tiered.stats[1].hbm_writes == 0      # nothing admitted on write


def test_quota_enforcement_and_pinning():
    pool = BlockPool(64, 8, 2, 2, 16, allocate_device=False)
    for i in range(10):
        pid, _ = pool.allocate(0, key=("t0", i), quota=None)
        assert pid is not None
    pool.pin(next(iter(pool.lru[0])))           # pin the LRU page
    evicted = pool.enforce_quota(0, 4)
    assert pool.resident(0) == 4                # quota met
    assert len(evicted) == 6
    # the pinned page survived even though it was LRU-first
    assert any(pool.meta[p].pinned for p in pool.lru[0])


def test_pool_eviction_frees_keys():
    pool = BlockPool(4, 8, 1, 2, 16, allocate_device=False)
    pids = [pool.allocate(0, key=("k", i))[0] for i in range(4)]
    assert pool.lookup(("k", 0)) == pids[0]
    pool.allocate(0, key=("k", 9))              # full → evicts LRU ("k",1?)
    assert len(pool.free) == 0
    assert pool.stats["evicted"] == 1


def test_release_tenant():
    pool = BlockPool(16, 8, 1, 2, 16, allocate_device=False)
    for i in range(5):
        pool.allocate(3, key=("x", i))
    assert pool.resident(3) == 5
    n = pool.release_tenant(3)
    assert n == 5 and pool.resident(3) == 0
    assert len(pool.free) == 16


def _tiered(n_pages=32, capacity=16, capacity2=0, window_events=10**9,
            tenants=("t0", "t1")):
    pool = BlockPool(n_pages, 8, 2, 2, 16, allocate_device=False)
    mgr = ECICacheManager(capacity, list(tenants), c_min=2,
                          initial_blocks=8, capacity2=capacity2)
    return pool, mgr, TieredKVCache(pool, mgr, window_events=window_events)


def test_managed_host_demote_then_promote():
    """HBM victims land in the managed host tier; a later read is a host
    hit that promotes the page back into the pool."""
    pool, mgr, tiered = _tiered(n_pages=4, capacity2=64)
    for i in range(4):
        assert tiered.access_page(0, ("k", i), fresh=True) == "hbm"
    # pool full: admitting one more evicts the LRU page ("k", 0) -> demote
    tiered.access_page(0, ("k", 4), fresh=True)
    assert tiered.stats[0].demotions == 1
    assert ("k", 0) in tiered.host_lru[0]
    served = tiered.access_page(0, ("k", 0), fresh=False)
    assert served == "host"
    assert tiered.stats[0].host_hits == 1
    assert tiered.stats[0].promotions == 1
    assert ("k", 0) not in tiered.host_lru[0]       # exclusive levels
    assert pool.lookup(("k", 0)) is not None
    # unmanaged mode keeps the legacy "host retains everything" behaviour
    pool2, _, t2 = _tiered(n_pages=4, capacity2=0)
    for i in range(5):
        t2.access_page(0, ("k", i), fresh=True)
    assert t2.stats[0].demotions == 0
    assert t2.access_page(0, ("k", 0), fresh=False) == "host"


def test_managed_host_eviction_is_a_real_miss():
    """Pages falling off the managed host tier must be recomputed."""
    pool, mgr, tiered = _tiered(n_pages=4, capacity2=8)
    tiered.host_quotas[0] = 2
    for i in range(4):
        tiered.access_page(0, ("k", i), fresh=True)
    for i in range(4, 8):                 # 4 more admissions -> 4 demotions
        tiered.access_page(0, ("k", i), fresh=True)
    assert tiered.stats[0].demotions == 4
    assert tiered.stats[0].host_evictions == 2      # quota 2: oldest fell off
    assert len(tiered.host_lru[0]) == 2
    assert tiered.access_page(0, ("k", 0), fresh=False) == "miss"
    assert tiered.stats[0].misses == 1


def test_finish_tenant_redistributes_quota():
    """Retired tenants are excluded from partitioning and their freed
    space is redistributed at the next rebalance()."""
    pool, mgr, tiered = _tiered(n_pages=64, capacity=20, capacity2=30)
    # both tenants demand more than half the pool: infeasible regime
    for t in range(2):
        for i in range(40):
            tiered.access_page(t, (t, i), fresh=True)
        for i in range(40):
            tiered.access_page(t, (t, i), fresh=False)
    tiered.rebalance()
    before = dict(tiered.quotas)
    assert sum(v for v in before.values() if v) <= 20
    share_before = before[1]

    demo_before = tiered.stats[0].demotions
    hev_before = tiered.stats[0].host_evictions
    tiered.finish_tenant(0)
    assert pool.resident(0) == 0
    assert not mgr.tenants[0].active
    assert len(tiered.host_lru[0]) == 0
    # retiring pages are releases, not demotions: stats stay clean
    assert tiered.stats[0].demotions == demo_before
    assert tiered.stats[0].host_evictions == hev_before
    # tenant 1 keeps re-reading its working set: its demand stays above
    # the cache, so the freed space has a taker
    for _ in range(2):
        for i in range(40):
            tiered.access_page(1, (1, i), fresh=False)
    tiered.rebalance()
    d = mgr.history[-1]
    assert d.sizes[0] == 0                          # excluded from Alg. 1
    assert d.sizes2 is None or d.sizes2[0] == 0
    assert tiered.quotas[1] >= share_before         # freed space flows over
    assert tiered.quotas[0] == 0
    # retired tenant stays excluded and untouched on further rebalances
    for i in range(10):
        tiered.access_page(1, (1, 100 + i), fresh=True)
    tiered.rebalance()
    assert mgr.tenants[0].cache.capacity == 0
    assert mgr.tenants[0].cache2.capacity == 0


def test_monitor_batching_grows_and_flushes():
    pool, mgr, tiered = _tiered(window_events=10**9)
    # shrink the preallocated buffers so one doubling is exercised cheaply
    tiered._ev_tenant = np.empty(16, np.int32)
    tiered._ev_addr = np.empty(16, np.int64)
    tiered._ev_read = np.empty(16, bool)
    for i in range(20):
        tiered.access_page(i % 2, ("g", i), fresh=True)
    assert tiered._ev_addr.size == 32               # doubled once
    assert tiered._n_ev == 20
    tiered.rebalance()
    assert tiered._n_ev == 0
    assert tiered.rebalance_seconds > 0.0
    assert len(mgr.history) == 1                    # analyzer consumed them


def test_rebalance_applies_quotas():
    eng, pool, tiered, cfg, _ = _engine(window_events=4, capacity=16)
    rng = np.random.default_rng(3)
    for t in range(2):
        eng.submit(Request(tenant=t,
                           prompt=rng.integers(0, cfg.vocab_size, 32
                                               ).astype(np.int32),
                           max_new_tokens=3))
    eng.run(16)
    s = tiered.summary()
    for t, q in s["quotas"].items():
        if q is not None:
            assert pool.resident(t) <= max(q, pool.resident(t))  # no crash
    assert len(tiered.manager.history) >= 1     # analyzer ran
