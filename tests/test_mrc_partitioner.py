"""MRC construction + Eq.-2 partitioners: exactness and invariants."""
import itertools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (HitRatioFunction, Trace, WritePolicy,
                        aggregate_latency, build_hit_ratio_function,
                        greedy_allocate, pgd_solve, reuse_distances,
                        simulate)


def _trace(addrs, reads=None):
    a = np.asarray(addrs, np.int64)
    r = np.ones(len(a), bool) if reads is None else np.asarray(reads, bool)
    return Trace(a, r)


def test_mattson_inclusion_exactness():
    """For a read-only trace, H(c) must equal the LRU simulator's measured
    hit ratio at every capacity (stack-distance ⇔ LRU inclusion)."""
    rng = np.random.default_rng(0)
    addrs = rng.zipf(1.5, 800) % 50
    t = _trace(addrs)
    h = build_hit_ratio_function(reuse_distances(t, "trd"))
    for c in [1, 2, 3, 5, 8, 13, 21, 34, 50, 64]:
        sim = simulate(t, c, WritePolicy.WB)
        assert sim.hit_ratio == pytest.approx(h(c), abs=1e-12), c


def test_hit_ratio_monotone_and_saturating():
    rng = np.random.default_rng(1)
    t = _trace(rng.integers(0, 40, 500))
    h = build_hit_ratio_function(reuse_distances(t, "trd"))
    vals = h(np.arange(0, 60))
    assert np.all(np.diff(vals) >= -1e-15)
    assert h(h.max_useful_size) == pytest.approx(h.max_hit_ratio)
    assert h(10**9) == pytest.approx(h.max_hit_ratio)


def _mk_h(edges, heights, n=1000):
    return HitRatioFunction(np.asarray(edges, np.int64),
                            np.asarray(heights, float), n)


def _brute_force_best(hs, capacity, t_fast, t_slow):
    """Exhaustive search over breakpoint combinations (tiny instances)."""
    options = []
    for h in hs:
        opts = [int(e) for e in h.edges]
        options.append(opts)
    best, best_alloc = float("inf"), None
    for combo in itertools.product(*options):
        if sum(combo) <= capacity:
            lat = aggregate_latency(hs, np.array(combo), t_fast, t_slow)
            if lat < best - 1e-12:
                best, best_alloc = lat, combo
    return best, best_alloc


@settings(max_examples=60, deadline=None)
@given(st.lists(
    st.lists(st.tuples(st.integers(1, 20), st.floats(0.01, 0.2)),
             min_size=1, max_size=4),
    min_size=2, max_size=4),
    st.integers(5, 60))
def test_greedy_feasibility_and_bounds(steps_per_tenant, capacity):
    hs = []
    for steps in steps_per_tenant:
        sizes = np.cumsum([s for s, _ in steps])
        heights = np.minimum(np.cumsum([h for _, h in steps]), 1.0)
        hs.append(_mk_h(np.concatenate([[0], sizes]),
                        np.concatenate([[0.0], heights])))
    res = greedy_allocate(hs, capacity, 1.0, 20.0, c_min=0)
    assert int(res.sizes.sum()) <= max(capacity,
                                       sum(h.max_useful_size for h in hs))
    if not res.feasible:
        assert int(res.sizes.sum()) <= capacity
    for h, s in zip(hs, res.sizes):
        assert 0 <= s <= h.max_useful_size


def test_greedy_near_optimal_vs_brute_force():
    """Breakpoint greedy: exact on the hull, <= one-breakpoint knapsack gap
    at tight capacities (cap=4 exhibits the documented gap)."""
    hs = [
        _mk_h([0, 2, 5, 9], [0.0, 0.4, 0.6, 0.7]),
        _mk_h([0, 3, 7], [0.0, 0.5, 0.65]),
        _mk_h([0, 1, 4, 10], [0.0, 0.3, 0.5, 0.6]),
    ]
    for cap in (4, 8, 12, 16, 26):
        res = greedy_allocate(hs, cap, 1.0, 20.0, c_min=0)
        best, _ = _brute_force_best(hs, cap, 1.0, 20.0)
        assert res.latency <= best * 1.06 + 1e-9, (cap, res.latency, best)
    # ample capacity: exact
    res = greedy_allocate(hs, 26, 1.0, 20.0, c_min=0)
    best, _ = _brute_force_best(hs, 26, 1.0, 20.0)
    assert res.latency == pytest.approx(best, rel=1e-9)


def test_feasible_case_allocates_urd_sizes():
    hs = [_mk_h([0, 5], [0.0, 0.5]), _mk_h([0, 7], [0.0, 0.4])]
    res = greedy_allocate(hs, 100, 1.0, 20.0, c_min=1)
    assert res.feasible
    assert list(res.sizes) == [5, 7]
    res2 = pgd_solve(hs, 100, 1.0, 20.0, c_min=1)
    assert res2.feasible and list(res2.sizes) == [5, 7]


def test_pgd_respects_constraints_and_is_competitive():
    rng = np.random.default_rng(3)
    hs = []
    for _ in range(6):
        k = rng.integers(2, 6)
        sizes = np.sort(rng.choice(np.arange(1, 200), size=k, replace=False))
        heights = np.sort(rng.random(k)) * 0.8
        hs.append(_mk_h(np.concatenate([[0], sizes]),
                        np.concatenate([[0.0], heights])))
    cap = int(sum(h.max_useful_size for h in hs) * 0.5)
    res = pgd_solve(hs, cap, 1.0, 20.0, c_min=0)
    assert int(res.sizes.sum()) <= cap
    for h, s in zip(hs, res.sizes):
        assert 0 <= s <= h.max_useful_size
    greedy = greedy_allocate(hs, cap, 1.0, 20.0, c_min=0)
    # fmincon-analog is a local method: allow 25% optimality gap vs exact
    assert res.latency <= greedy.latency * 1.25 + 1e-9


def test_pgd_snap_keeps_c_min_floors():
    """Snapping c* down to a breakpoint must not cut a tenant below its
    ``min(c_min, urd)`` floor: a tenant whose breakpoints straddle c_min
    (here 38 and 120 around a floor of 50) stays at the floor."""
    hs = [_mk_h([0, 38, 120], [0.0, 0.05, 0.06]),
          _mk_h([0, 100, 400], [0.0, 0.6, 0.9]),
          _mk_h([0, 80, 300], [0.0, 0.5, 0.8])]
    cap = 450
    res = pgd_solve(hs, cap, 1.0, 20.0, c_min=50)
    assert not res.feasible
    assert int(res.sizes.sum()) <= cap
    assert all(s >= min(50, h.max_useful_size)
               for h, s in zip(hs, res.sizes))


def test_appendix_d_convexity_of_relaxation():
    """App. D: the relaxed objective is convex along any segment when the
    interpolated h is concave (checked numerically)."""
    h = _mk_h([0, 4, 10, 20], [0.0, 0.5, 0.8, 0.9])
    c = np.linspace(0, 20, 41)
    lat = (h.interp(c) * 1.0 + (1 - h.interp(c)) * 20.0)
    d2 = np.diff(lat, 2)
    assert np.all(d2 >= -1e-9)   # convex (non-increasing marginal gain)
