"""The plain reference agrees with brute force, and with the program's own
per-access paths where both run the same semantics."""
import numpy as np
import pytest

import reference as R

P = {"t_fast": 1.0, "t_slow": 20.0, "t_write_bypass": 1.2,
     "flush_cost": 10.0}


def brute_distances(addrs):
    out = np.full(len(addrs), -1, np.int64)
    last = {}
    for i, a in enumerate(addrs.tolist()):
        if a in last:
            out[i] = len(set(addrs[last[a] + 1:i].tolist()))
        last[a] = i
    return out


@pytest.mark.parametrize("n", [0, 1, 63, 64, 65, 200, 700])
def test_reuse_distances_match_brute_force(n):
    a = np.random.default_rng(n).integers(0, 30, n)
    assert np.array_equal(R.reuse_distances(a), brute_distances(a))


@pytest.mark.parametrize("policy", ["wb", "wt", "ro"])
def test_replay_matches_the_program_interpreter(policy):
    from repro.core.simulator import LRUCache, simulate
    from repro.core.write_policy import WritePolicy
    from repro.data.traces import msr_trace
    t, c = R.Tenant(40), LRUCache(40)
    t.policy = policy
    for w, name in enumerate(["wdev_0", "hm_1", "usr_0"]):
        tr = msr_trace(name, 900, seed=w)
        got = R.replay(t, tr.addrs, tr.is_read, P)
        exp = simulate(tr, c.capacity, WritePolicy(policy), 1.0, 20.0,
                       flush_cost=10.0, cache=c)
        for k in R.COUNTERS:
            assert got[k] == getattr(exp, k), k
        assert got["total_latency"] == pytest.approx(exp.total_latency,
                                                     rel=1e-12)
        assert list(t.od.items()) == list(c._od.items())
        t.resize(30 - 5 * w)
        c.resize(30 - 5 * w)


@pytest.mark.parametrize("sampled", [False, True])
def test_monitor_matches_the_program_monitor(sampled):
    from repro.core.monitor import analyze_windows
    from repro.data.traces import msr_trace
    names = ["wdev_0", "hm_1", "stg_1", "prn_1"]
    lens = [300, 900, 2000, 5000]
    traces = [msr_trace(nm, n, seed=11 * i) for i, (nm, n)
              in enumerate(zip(names, lens))]
    kw = (dict(sample_rate="auto", sample_target=512, sample_floor=64)
          if sampled else {})
    mon = analyze_windows(traces, window_seed=5, **kw)
    for i, tr in enumerate(traces):
        rate = R.shards_rate(len(tr), 512, 64) if sampled else 1.0
        ref = R.monitor(tr.addrs, tr.is_read, rate, R.shards_salt(5, i))
        assert np.array_equal(ref["edges"], mon.curves[i].edges)
        assert np.array_equal(ref["heights"], mon.curves[i].heights)
        assert ref["urd"] == int(mon.urd_sizes[i])
        assert ref["write_ratio"] == float(mon.write_ratios[i])
    assert sampled == bool(np.any(mon.sample_rates < 1))


def test_salt_and_filter_match_the_program():
    from repro.core.reuse_distance import shards_keep_mask, shards_salt
    a = np.random.default_rng(3).integers(0, 2**31, 5000)
    for w, t in [(0, 0), (3, 255), (2**20, 17)]:
        assert R.shards_salt(w, t) == shards_salt(w, t)
        assert np.array_equal(R.shards_keep(a, 0.3, R.shards_salt(w, t)),
                              shards_keep_mask(a, 0.3, shards_salt(w, t)))


def _curve(edges, heights):
    return {"edges": np.array(edges), "heights": np.array(heights),
            "urd": edges[-1]}


def test_partition_faults_count_each_broken_condition():
    curves = [_curve([0, 10, 30], [0.0, 0.2, 0.5]),
              _curve([0, 5, 40], [0.0, 0.1, 0.4])]
    assert R.partition_faults(np.array([30, 40]), True, curves, 80, 4) == 0
    assert R.partition_faults(np.array([30, 39]), True, curves, 80, 4) == 1
    # binding: on breakpoints, within budget, no further step fits
    assert R.partition_faults(np.array([30, 5]), False, curves, 40, 4) == 0
    assert R.partition_faults(np.array([10, 5]), False, curves, 40, 4) == 1
    assert R.partition_faults(np.array([30, 6]), False, curves, 40, 4) == 1
    assert R.partition_faults(np.array([30, 40]), False, curves, 40, 4) >= 1
    assert R.partition_faults(np.array([30, 5]), True, curves, 40, 4) == 1


def test_optimal_hits_matches_brute_force():
    import itertools
    rng = np.random.default_rng(4)
    for _ in range(20):
        curves = []
        for _ in range(3):
            e = np.unique(rng.integers(1, 25, 4))
            h = np.sort(rng.random(e.size))
            curves.append(_curve([0, *e.tolist()], [0.0, *h.tolist()]))
        cap, c_min = int(rng.integers(16, 50)), int(rng.integers(0, 6))
        opts = []
        for c in curves:
            f = min(c_min, c["urd"])
            opts.append([f] + [int(x) for x in c["edges"]
                               if f < x <= c["urd"]])
        best = max(sum(R.curve_at(c, s) for c, s in zip(curves, pick))
                   for pick in itertools.product(*opts) if sum(pick) <= cap)
        assert R.optimal_hits(curves, cap, c_min) == pytest.approx(best)


def test_partition_gap_is_the_objective_above_the_optimum():
    curves = [_curve([0, 10, 30], [0.0, 0.2, 0.5]),
              _curve([0, 5, 40], [0.0, 0.1, 0.4])]
    # capacity 40: the optimum is (30, 5), hits 0.6, latency 40 - 19 * 0.6
    assert R.partition_gap(np.array([30, 5]), curves, 40, 4, 1.0, 20.0) == 0
    gap = R.partition_gap(np.array([10, 5]), curves, 40, 4, 1.0, 20.0)
    assert gap == pytest.approx(19 * 0.3 / (40 - 19 * 0.6))
    # the URD sizes fit: sizes are judged exactly elsewhere
    assert R.partition_gap(np.array([30, 40]), curves, 70, 4, 1.0, 20.0) == 0
