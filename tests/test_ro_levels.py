"""Two-level RO (write-around) under eviction pressure: token replay ≡
interpreter oracle.

The differential suite for the per-level eviction-token formulation
(``batch_sim._ro_token_replay_levels``): small capacities + few addresses
force invalidation pressure, so every window here used to take the
per-access interpreter fallback and now must replay vectorized — bit-identical per-level hits, write hits, cache
writes (endurance), demotions, flush charges, latency and final per-level
LRU states, cold and across warm multi-window chains, with clean and
dirty-accepting L2 policies.  ``SimResult.fallback`` must stay 0
everywhere except genuinely degenerate windows.  Engine comparisons run
through the shared differential oracle harness (``tests/oracle.py``).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from oracle import (EngineDiff, assert_results_equal, examples, mk_trace,
                    trace_strategy)
from repro.core import Trace, WritePolicy, make_manager, simulate_batch
from repro.core.simulator import LRUCache
from repro.core.trace import prev_next_occurrence


def ro_strategy(max_n=60, max_addr=5):
    return trace_strategy(max_n=max_n, max_addr=max_addr)


# --------------------------------------------- cold, both L2 dirty policies
@settings(max_examples=examples(200), deadline=None)
@given(ro_strategy(), st.integers(1, 3), st.integers(1, 3),
       st.sampled_from([WritePolicy.WB, WritePolicy.RO]),
       st.sampled_from([0.0, 10.0]))
def test_ro_pressure_cold_matches_interpreter(trace_list, c1, c2, p2, flush):
    t = mk_trace(trace_list)
    r = EngineDiff([c1], [WritePolicy.RO], [c2], [p2],
                   flush=flush).run_window([t])[0]
    # pressure stays on the token path; only the degenerate empty
    # two-level window takes the interpreter
    assert r.fallback == (1 if len(t) == 0 else 0)


# ------------------------------------ warm chains under sustained pressure
@settings(max_examples=examples(60), deadline=None)
@given(st.lists(ro_strategy(max_n=50, max_addr=5), min_size=2, max_size=4),
       st.integers(1, 3), st.integers(1, 3),
       st.sampled_from([WritePolicy.WB, WritePolicy.RO]))
def test_ro_pressure_warm_chain_matches_interpreter(windows, c1, c2, p2):
    """Warm per-level state (content, order, dirty flags) must survive the
    token replay byte-identically across windows; the first window runs WB
    to seed dirty blocks into the hierarchy before RO takes over."""
    diff = EngineDiff([c1], [WritePolicy.RO], [c2], [p2], flush=10.0)
    for w, tl in enumerate(windows):
        pol = WritePolicy.WB if w == 0 else WritePolicy.RO
        diff.run_window([mk_trace(tl)], policies=[pol])


# ------------------------------------------- per-level flush accounting
def test_clean_l2_flushes_at_demotion_under_pressure(engine_diff):
    """A dirty warm-L1 block demoted under RO pressure must flush at the
    demotion boundary (clean L2) or at its final L2 eviction (WB L2) —
    one flush either way, charged on the vectorized path."""
    # warm L1 = {9 (dirty)}; reads to 0,1,2 demote 9, then push it out of
    # the 1-block L2 entirely
    t = Trace(np.array([0, 1, 2], np.int64), np.ones(3, bool))
    for p2 in (WritePolicy.RO, WritePolicy.WB):
        diff = engine_diff([1], [WritePolicy.RO], [1], [p2], flush=5.0)
        for caches in (diff.ref1, diff.got1):
            caches[0].set_state_arrays(np.array([9], np.int64),
                                       np.array([True]))
        r = diff.run_window([t])[0]
        # clean2 (p2=RO): flush when 9 demotes; WB L2: flush when 9 is
        # finally evicted from L2 — one 5.0 charge either way
        assert r.total_latency == pytest.approx(3 * 20.0 + 5.0), p2
        assert r.cache_writes_l2 == 3       # 9, 0, 1 each demoted
        assert r.fallback == 0
        assert list(diff.got1[0]._od) == [2], p2
        assert list(diff.got2[0]._od) == [1], p2


def test_ro_pressure_endurance_counters(engine_diff):
    """cache_writes = installs + promotions; cache_writes_l2 = demotions —
    checked against the interpreter on a promotion-heavy pressure mix."""
    rng = np.random.default_rng(3)
    t = Trace(rng.integers(0, 5, 300).astype(np.int64),
              rng.random(300) < 0.7)
    r_b = engine_diff([2], [WritePolicy.RO], [2],
                      [WritePolicy.WB]).run_window([t])[0]
    assert r_b.fallback == 0
    assert r_b.cache_writes == (r_b.reads - r_b.read_hits
                                - r_b.read_hits_l2) + r_b.read_hits_l2
    assert r_b.cache_writes_l2 > 0              # pressure ⇒ demotions


# ----------------------------------------------------- fallback telemetry
def test_manager_pressure_mix_no_fallback():
    """A pressure-heavy all-RO two-level deployment must keep every window
    on the vectorized path (ro_fallback_windows == 0) and still agree with
    the interpreter engine exactly."""
    names = ["wdev_0", "hm_1", "prn_1"]
    from repro.data.traces import msr_trace
    mgrs = {}
    for engine in ("batch", "lru"):
        mgr = make_manager("etica", 30, names, capacity2=60, c_min=5,
                           initial_blocks=10, w_threshold=0.0,
                           flush_cost=10.0, engine=engine)
        for w in range(3):
            traces = [msr_trace(nm, 400, seed=31 * w + i)
                      for i, nm in enumerate(names)]
            mgr.run_window(traces)
        mgrs[engine] = mgr
    mb, ml = mgrs["batch"], mgrs["lru"]
    assert all(t.policy is WritePolicy.RO for t in mb.tenants)
    assert mb.summary()["ro_fallback_windows"] == 0
    for tb, tl in zip(mb.tenants, ml.tenants):
        assert_results_equal(tl.result, tb.result)
        assert list(tb.cache._od.items()) == list(tl.cache._od.items())
        assert list(tb.cache2._od.items()) == list(tl.cache2._od.items())


def test_degenerate_windows_still_count_as_fallback():
    """Empty two-level windows and warm L2 behind a dead C2 <= 0 level are
    the only remaining interpreter replays — flagged in telemetry."""
    empty = Trace(np.zeros(0, np.int64), np.zeros(0, bool))
    r = simulate_batch(empty, 2, WritePolicy.RO, capacity2=2)
    assert r.fallback == 1
    # warm L2 content behind a dead level
    c1, c2 = LRUCache(2), LRUCache(0)
    c2.set_state_arrays(np.array([7], np.int64), np.array([False]))
    t = Trace(np.array([7, 8], np.int64), np.ones(2, bool))
    r = simulate_batch(t, 2, WritePolicy.RO, cache=c1, cache2=c2)
    assert r.fallback == 1
    # while an ordinary pressure window is not a fallback
    t = Trace(np.arange(6, dtype=np.int64), np.ones(6, bool))
    assert simulate_batch(t, 1, WritePolicy.RO, capacity2=1).fallback == 0


# ------------------------------------------------------ RO guard counts
def _live_count_case(seed, n, space, p_occ):
    rng = np.random.default_rng(seed)
    addrs = rng.integers(0, space, n).astype(np.int64)
    occ = rng.random(n) < p_occ
    _, nxt = prev_next_occurrence(addrs)
    return np.minimum(nxt, n), occ


def test_ro_live_counts_ref_matches_numpy():
    """The guard's host prefix sum equals the dense (i, j)-plane oracle."""
    import jax.numpy as jnp

    from repro.core.batch_sim import _live_counts
    from repro.kernels.cache_sim.ref import live_counts_ref
    nxt, occ = _live_count_case(5, 500, 30, 0.6)
    got = _live_counts(nxt, np.flatnonzero(occ))
    dense = np.asarray(live_counts_ref(jnp.asarray(nxt, jnp.int32),
                                       jnp.asarray(occ, jnp.int32)))
    assert np.array_equal(got, dense)


@pytest.mark.slow
def test_ro_live_counts_kernel_interpret():
    import jax.numpy as jnp

    from repro.core.batch_sim import _live_counts
    from repro.kernels.cache_sim.kernel import live_count_scan
    nxt, occ = _live_count_case(6, 400, 25, 0.5)
    ker = live_count_scan(jnp.asarray(nxt, jnp.int32),
                          jnp.asarray(occ, jnp.int32), interpret=True)
    assert np.array_equal(_live_counts(nxt, np.flatnonzero(occ)),
                          np.asarray(ker))
