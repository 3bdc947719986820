"""A run whose timed path is broken underneath comes out not correct,
for each fault this system can have on one chip.  (There is no exchange
between chips to leave out: every cell runs on one.)"""
import dataclasses

import numpy as np

from conftest import tiny


def test_a_replay_that_leaves_the_cache_state_unchanged(cpu_run,
                                                        monkeypatch):
    import copy
    import repro.core.manager as M
    real = M.simulate_many

    def on_copies(traces, **kw):
        kw["caches"] = copy.deepcopy(kw["caches"])
        return real(traces, **kw)
    monkeypatch.setattr(M, "simulate_many", on_copies)
    res = cpu_run(tiny("msr16-bind"))
    assert not res["correct"]
    c = res["checks"]
    assert (c["replay_counts_off"]["value"] > 0
            or c["latency_rel_gap"]["value"] > c["latency_rel_gap"]["limit"])


def test_half_the_tenants_left_out_of_the_replay(cpu_run, monkeypatch):
    import repro.core.manager as M
    real = M.simulate_many

    def half(traces, **kw):
        k = len(traces) // 2
        res, rds = real(traces[:k], **{key: (v[:k] if isinstance(v, list)
                                             else v) for key, v in kw.items()})
        pad = [M.SimResult(capacity=c.capacity) for c in kw["caches"][k:]]
        return res + pad, rds + [None] * len(pad)
    monkeypatch.setattr(M, "simulate_many", half)
    assert not cpu_run(tiny("msr16-bind"))["correct"]


def test_a_decision_altered_where_it_is_made(cpu_run, monkeypatch):
    import repro.core.manager as M
    real = M.pgd_solve

    def off_by_one(*a, **kw):
        p = real(*a, **kw)
        sizes = p.sizes.copy()
        sizes[0] += 1
        return dataclasses.replace(p, sizes=sizes)
    monkeypatch.setattr(M, "pgd_solve", off_by_one)
    c = tiny("msr16-bind")
    res = cpu_run(c, partition_fn=off_by_one)
    assert not res["correct"]
    assert res["checks"]["partition_off"]["value"] > 0


def test_a_hit_count_altered_where_it_is_produced(cpu_run, monkeypatch):
    import repro.core.manager as M
    real = M.simulate_many

    def one_more_hit(*a, **kw):
        res, rds = real(*a, **kw)
        res[-1].read_hits += 1
        return res, rds
    monkeypatch.setattr(M, "simulate_many", one_more_hit)
    res = cpu_run(tiny("msr16-bind"))
    assert not res["correct"]
    assert res["checks"]["replay_counts_off"]["value"] > 0


def test_a_curve_altered_where_it_is_produced(cpu_run, monkeypatch):
    import repro.core.manager as M
    real = M.analyze_windows

    def nudged(*a, **kw):
        mon = real(*a, **kw)
        mon.curves.heights[-1] = np.nextafter(
            np.nextafter(mon.curves.heights[-1], 0), 0) * (1 - 1e-9)
        return mon
    monkeypatch.setattr(M, "analyze_windows", nudged)
    assert not cpu_run(tiny("msr16-bind"))["correct"]
