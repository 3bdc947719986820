"""Two-level (ETICA) hierarchy: batch engine ≡ two-level interpreter.

Property tests assert exact agreement of per-level hits, write hits, cache
writes, latency and the final per-level LRU states over random traces ×
(C1, C2) capacities × per-level policies, cold and across warm multi-window
chains; plus the degenerate ``C2 == 0`` identity with the single-level
scheme, the device port of the RO eviction-token loop, the kernel's
both-level residency masks, the two-stage Eq.-2 solver, and the manager's
end-to-end engine equivalence.  Engine comparisons run through the shared
differential oracle harness (``tests/oracle.py``).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from oracle import (EngineDiff, RESULT_FIELDS, assert_results_equal,
                    assert_states_equal, examples, mk_trace, trace_strategy)
from repro.core import (ECICacheManager, Trace, WritePolicy,
                        assign_write_policy_levels, build_hit_ratio_function,
                        greedy_allocate, make_manager, reuse_distances,
                        simulate, simulate_batch, two_level_solve)
from repro.core.simulator import LRUCache, rebalance_levels
from repro.data.traces import msr_trace

POLICIES = [WritePolicy.WB, WritePolicy.WT, WritePolicy.RO]


def two_level_strategy(max_n=50, max_addr=8):
    return trace_strategy(max_n=max_n, max_addr=max_addr)


# ------------------------------------------------ engine ≡ oracle (cold)
@settings(max_examples=examples(200), deadline=None)
@given(two_level_strategy(), st.integers(0, 5), st.integers(0, 5),
       st.sampled_from(POLICIES), st.sampled_from(POLICIES),
       st.sampled_from([0.0, 10.0]))
def test_two_level_batch_equals_simulate_cold(trace_list, c1, c2, p1, p2,
                                              flush):
    EngineDiff([c1], [p1], [c2], [p2],
               flush=flush).run_window([mk_trace(trace_list)])


@settings(max_examples=examples(50), deadline=None)
@given(st.lists(st.tuples(two_level_strategy(max_n=30), st.integers(0, 5),
                          st.integers(0, 5), st.sampled_from(POLICIES),
                          st.sampled_from(POLICIES)),
                min_size=1, max_size=3),
       st.sampled_from([0.0, 10.0]))
def test_two_level_warm_multi_window_chain(windows_spec, flush):
    """Warm cross-window per-level state must stay byte-identical (content,
    order, dirty flags) between the interpreter and the batch engine."""
    diff = EngineDiff([c1 for _, c1, _, _, _ in windows_spec],
                      [p for _, _, _, p, _ in windows_spec],
                      [c2 for _, _, c2, _, _ in windows_spec],
                      [p for _, _, _, _, p in windows_spec], flush=flush)
    diff.run_windows([[mk_trace(tl) for tl, _, _, _, _ in windows_spec]
                      for _ in range(3)])


@settings(max_examples=examples(100), deadline=None)
@given(two_level_strategy(max_n=60, max_addr=5), st.integers(1, 3),
       st.integers(1, 3))
def test_two_level_ro_under_pressure(trace_list, c1, c2):
    """Small caps + few addresses force the two-level RO pressure path."""
    EngineDiff([c1], [WritePolicy.RO], [c2], [WritePolicy.WB],
               flush=10.0).run_window([mk_trace(trace_list)])


@settings(max_examples=examples(100), deadline=None)
@given(two_level_strategy(max_n=40), st.integers(0, 6),
       st.sampled_from(POLICIES), st.sampled_from([0.0, 10.0]))
def test_capacity2_zero_is_single_level(trace_list, cap, policy, flush):
    """C2 == 0 must reproduce each single-level engine bit-identically
    (old single-level API vs the same engine with the two-level knobs)."""
    t = mk_trace(trace_list)
    for eng in (simulate, simulate_batch):
        ca, cb = LRUCache(cap), LRUCache(cap)
        r_old = eng(t, cap, policy, flush_cost=flush, cache=ca)
        r_new = eng(t, cap, policy, flush_cost=flush, cache=cb,
                    capacity2=0, policy2=WritePolicy.RO)
        for f in RESULT_FIELDS:
            assert getattr(r_old, f) == getattr(r_new, f), f
        assert r_new.read_hits_l2 == 0 and r_new.cache_writes_l2 == 0
        assert r_old.total_latency == r_new.total_latency  # bit-identical
        assert_states_equal(ca, cb)


def test_rebalance_levels_invariant():
    """Growing L1 refills it from L2's MRU; union order is preserved."""
    c1, c2 = LRUCache(4), LRUCache(4)
    c1.set_state_arrays(np.array([7, 8], np.int64), np.array([True, False]))
    c2.set_state_arrays(np.array([1, 2, 3], np.int64),
                        np.array([False, True, False]))
    rebalance_levels(c1, c2)
    assert list(c1._od.items()) == [(2, True), (3, False), (7, True),
                                    (8, False)]
    assert list(c2._od.items()) == [(1, False)]


def test_promotion_and_demotion_counting(engine_diff):
    """r(a) r(b) r(a) at C1=1, C2=1: second r(a) is an L2 hit (a was
    demoted by r(b)); the promotion writes L1 and demotes b to L2."""
    t = Trace(np.array([0, 1, 0], np.int64), np.ones(3, bool))
    r = engine_diff([1], [WritePolicy.WB], [1], [WritePolicy.WB],
                    t_fast2=4.0).run_window([t])[0]
    assert (r.read_hits, r.read_hits_l2) == (0, 1)
    assert r.cache_writes == 3          # 2 installs + 1 promotion
    assert r.cache_writes_l2 == 2       # a demoted, then b demoted
    assert r.total_latency == pytest.approx(2 * 20.0 + 4.0)


def test_clean_l2_flushes_at_demotion(engine_diff):
    """policy2 != WB: the dirty victim flushes when demoted, not at union
    eviction; L2 content stays clean."""
    t = Trace(np.array([0, 1], np.int64), np.array([False, True]))
    diff = engine_diff([1], [WritePolicy.WB], [1], [WritePolicy.RO],
                       flush=5.0)
    r = diff.run_window([t])[0]
    # w(0) installs dirty; r(1) demotes 0 -> flush charged at demote
    assert r.total_latency == pytest.approx(1.0 + 20.0 + 5.0)
    assert list(diff.got2[0]._od.items()) == [(0, False)]


# ------------------------------------------------ kernel both-level masks
def test_residency_levels_ops_ref_match_host():
    from repro.core.batch_sim import _stack_distances_host
    from repro.core.trace import prev_next_occurrence
    from repro.kernels.cache_sim.ops import residency_levels_accel
    rng = np.random.default_rng(11)
    addrs = rng.integers(0, 40, 600).astype(np.int64)
    prev, nxt = prev_next_occurrence(addrs)
    cap1 = rng.integers(0, 6, 600)
    captot = cap1 + rng.integers(0, 6, 600)
    sd = _stack_distances_host(prev, nxt)
    hot = prev >= 0
    want1 = hot & (sd >= 0) & (sd < cap1)
    wantu = hot & (sd >= 0) & (sd < captot)
    l1, un = residency_levels_accel(prev, nxt, cap1, captot,
                                    use_kernel=False)
    assert np.array_equal(l1, want1)
    assert np.array_equal(un, wantu)


@pytest.mark.slow
def test_residency_levels_kernel_interpret():
    from repro.core.batch_sim import _stack_distances_host
    from repro.core.trace import prev_next_occurrence
    from repro.kernels.cache_sim.ops import residency_levels_accel
    rng = np.random.default_rng(12)
    addrs = rng.integers(0, 30, 400).astype(np.int64)
    prev, nxt = prev_next_occurrence(addrs)
    cap1 = rng.integers(0, 5, 400)
    captot = cap1 + rng.integers(0, 5, 400)
    sd = _stack_distances_host(prev, nxt)
    hot = prev >= 0
    l1, un = residency_levels_accel(prev, nxt, cap1, captot, use_kernel=True)
    assert np.array_equal(l1, hot & (sd >= 0) & (sd < cap1))
    assert np.array_equal(un, hot & (sd >= 0) & (sd < captot))


# ------------------------------------------------ two-stage Eq.-2 solver
def test_shifted_hit_ratio_curve():
    t = msr_trace("prn_1", 1200, seed=4)
    h = build_hit_ratio_function(reuse_distances(t, "urd"))
    for base in (0, 5, h.max_useful_size // 2, h.max_useful_size + 10):
        sh = h.shifted(base)
        assert sh.edges[0] == 0
        assert np.all(np.diff(sh.edges) > 0)
        assert np.all(np.diff(sh.heights) >= 0) and sh.heights[0] == 0.0
        for c in (1, 3, 17, 1000):
            assert sh(c) == pytest.approx(h(base + c) - h(base))
    assert h.shifted(0).max_useful_size == h.max_useful_size
    sat = h.shifted(h.max_useful_size + 10)
    assert sat.max_useful_size == 0 and sat.max_hit_ratio == 0.0


def test_two_level_solve_budgets_and_degenerate():
    traces = [msr_trace(n, 1500, seed=i)
              for i, n in enumerate(["wdev_0", "prn_1", "prxy_0", "web_0"])]
    hs = [build_hit_ratio_function(reuse_distances(t, "urd"))
          for t in traces]
    p1, p2 = two_level_solve(hs, 60, 150, 1.0, 4.0, 20.0, c_min=5,
                             partition_fn=greedy_allocate)
    assert int(p1.sizes.sum()) <= 60
    assert int(p2.sizes.sum()) <= 150
    # level-2 grants never exceed the residual useful mass
    for h, s1, s2 in zip(hs, p1.sizes, p2.sizes):
        assert int(s1) + int(s2) <= h.max_useful_size
    # degenerate: no L2 budget reproduces the single-level call exactly
    p1b, p2b = two_level_solve(hs, 60, 0, 1.0, 4.0, 20.0, c_min=5,
                               partition_fn=greedy_allocate)
    assert p2b is None
    assert np.array_equal(p1.sizes, p1b.sizes)


def test_assign_write_policy_levels():
    wr_heavy = Trace(np.array([1, 1, 1, 1], np.int64),
                     np.array([False, False, False, False]))
    assert assign_write_policy_levels(wr_heavy) == (WritePolicy.RO,
                                                    WritePolicy.RO)
    mixed = Trace(np.array([1, 1, 2, 2, 3, 3, 4, 4, 5, 5], np.int64),
                  np.array([False, False, True, True, True, True, True,
                            True, True, True]))
    # writeRatio = 0.1: below both thresholds -> WB everywhere
    assert assign_write_policy_levels(mixed) == (WritePolicy.WB,
                                                 WritePolicy.WB)
    # writeRatio in [w_threshold2, w_threshold): clean L2, buffering L1
    waw = Trace(np.array([1, 1, 1, 2, 2, 3, 3, 4, 4, 5], np.int64),
                np.array([False, False, False, True, True, True, True,
                          True, True, True]))
    p1, p2 = assign_write_policy_levels(waw, 0.5, 0.2)
    assert (p1, p2) == (WritePolicy.WB, WritePolicy.RO)


# --------------------------------------------------- manager end-to-end
def test_manager_two_level_batch_equals_lru():
    names = ["wdev_0", "hm_1", "prn_1", "web_0"]
    mgrs = {}
    for engine in ("batch", "lru"):
        mgr = make_manager("etica", 150, names, capacity2=400, c_min=10,
                           initial_blocks=30, t_fast=1.0, t_fast2=4.0,
                           t_slow=20.0, flush_cost=10.0, engine=engine)
        for w in range(3):
            traces = [msr_trace(nm, 600, seed=97 * w + i)
                      for i, nm in enumerate(names)]
            mgr.run_window(traces)
        mgrs[engine] = mgr
    mb, ml = mgrs["batch"], mgrs["lru"]
    for tb, tl in zip(mb.tenants, ml.tenants):
        assert_results_equal(tl.result, tb.result)
        assert tb.policy is tl.policy and tb.policy2 is tl.policy2
        assert tb.cache.capacity == tl.cache.capacity
        assert tb.cache2.capacity == tl.cache2.capacity
        assert_states_equal(tb.cache, tl.cache)
        assert_states_equal(tb.cache2, tl.cache2)
    for db, dl in zip(mb.history, ml.history):
        assert np.array_equal(db.sizes, dl.sizes)
        assert np.array_equal(db.sizes2, dl.sizes2)
        assert db.policies2 == dl.policies2
    d = mb.history[-1]
    assert int(d.sizes.sum()) <= 150
    assert int(d.sizes2.sum()) <= 400
    assert int(d.sizes2.sum()) > 0      # pressure regime: L2 gets used


def test_manager_two_level_dominates_single_tier():
    """ETICA headline at equal L1 budget: latency strictly improves while
    L1 cache writes do not increase (promotions replace miss installs)."""
    names = ["wdev_0", "hm_1", "prn_1", "web_0", "prxy_0"]
    kw = dict(c_min=10, initial_blocks=30, t_fast=1.0, t_slow=20.0,
              flush_cost=10.0)
    one = make_manager("eci", 150, names, **kw)
    two = make_manager("etica", 150, names, capacity2=400, t_fast2=4.0, **kw)
    for w in range(3):
        traces = [msr_trace(nm, 700, seed=97 * w + i)
                  for i, nm in enumerate(names)]
        one.run_window(list(traces))
        two.run_window(list(traces))
    s1, s2 = one.summary(), two.summary()
    assert s2["mean_latency"] < s1["mean_latency"]
    assert s2["cache_writes"] <= s1["cache_writes"]
    assert s2["read_hit_ratio_l2"] > 0


def test_history_limit_bounds_memory():
    mgr = ECICacheManager(500, ["a", "b"], c_min=8, initial_blocks=16,
                          history_limit=5)
    tr = msr_trace("wdev_0", 120, seed=0)
    for w in range(12):
        mgr.run_window([tr, tr])
    assert len(mgr.history) == 5
    # default is bounded too; None means unbounded
    assert ECICacheManager(10, ["a"]).history.maxlen == 256
    assert ECICacheManager(10, ["a"], history_limit=None).history.maxlen \
        is None
