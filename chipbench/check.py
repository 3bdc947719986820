"""The comparison that decides ``correct``.

After the measured window has closed, the plain reference
(``reference.py``) walks the same windows from the same tapes and judges
what the timed path produced, window by window:

  * replay: every tenant's running hit, write and cache-write counters
    after every window, exactly, and its running modelled latency within
    ``latency_rel_gap``;
  * monitor: URD sizes, curve breakpoints and denominators exactly,
    curve heights within ``curve_rel_gap``;
  * Alg. 3: every write policy, exactly;
  * Eq. 2: the decided sizes against the reference curves: its
    constraints exactly (``reference.partition_faults``), and its
    objective within ``partition_gap`` of the exact optimum
    (``reference.partition_gap``);
  * actuation: the capacities in force after each window equal the
    decision, and the final LRU content and dirty flags equal the
    reference's.

Replay and actuation are judged in every window (the reference's LRU
state needs them all).  Monitor, policies and partition are judged in a
sample of windows drawn from the seed, with the last window always in it.
The reference replays each window with the capacities the system decided
(judged above) and the policies it derives itself.

Each number has a limit (``limits.json``, with the readings it was set
from); a run is correct when every number is at or under its limit.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import reference as R

BENCH = Path(__file__).resolve().parent
SAMPLED_WINDOWS = 6


def limits() -> dict:
    return json.loads((BENCH / "limits.json").read_text())


def sample_windows(n_windows: int, seed: int) -> list[int]:
    """Windows whose monitor, policies and partition are judged."""
    if n_windows <= SAMPLED_WINDOWS:
        return list(range(n_windows))
    rng = np.random.default_rng(seed)
    rest = rng.choice(n_windows - 1, SAMPLED_WINDOWS - 1, replace=False)
    return sorted(int(w) for w in rest) + [n_windows - 1]


def _rel_gap(got: np.ndarray, ref: np.ndarray) -> float:
    ref = np.asarray(ref, np.float64)
    den = np.maximum(np.abs(ref), np.finfo(np.float64).tiny)
    gap = np.abs(np.asarray(got, np.float64) - ref) / den
    return float(gap.max(initial=0.0))


def judge(windows: list[list], rec, manager: dict, seed: int, lim: dict
          ) -> tuple[dict, set]:
    """Numbers compared, by name, and the windows where one broke its
    limit.

    ``windows[w]`` is window ``w``'s tapes as ``(addrs, is_read)`` pairs,
    ``rec`` the ``cell.Recorder`` of the run, ``manager`` the
    configuration's manager keys, ``lim`` the limits.
    """
    p = {"t_fast": float(manager["t_fast"]),
         "t_slow": float(manager["t_slow"]),
         "t_write_bypass": float(manager["t_write_bypass"]),
         "flush_cost": float(manager["flush_cost"])}
    capacity, c_min = int(manager["capacity"]), int(manager["c_min"])
    w_thr = float(manager["w_threshold"])
    sampled = manager.get("sample_rate") == "auto"
    target = int(manager.get("sample_target", 0))
    floor = int(manager.get("sample_floor", 0))

    n_ten = len(windows[0])
    tenants = [R.Tenant(int(manager["initial_blocks"]))
               for _ in range(n_ten)]
    run_cnt = np.zeros((n_ten, len(R.COUNTERS)), np.int64)
    run_lat = np.zeros(n_ten)
    out = {"replay_counts_off": 0, "latency_rel_gap": 0.0,
           "monitor_off": 0, "curve_rel_gap": 0.0, "policy_off": 0,
           "partition_off": 0, "partition_gap": 0.0,
           "actuation_off": 0}
    judged = set(sample_windows(len(windows), seed))
    bad = set()
    for w, tapes in enumerate(windows):
        before = dict(out)
        for i, (t, (addrs, is_read)) in enumerate(zip(tenants, tapes)):
            r = R.replay(t, addrs, is_read, p)
            run_cnt[i] += [r[k] for k in R.COUNTERS]
            run_lat[i] += r["total_latency"]
        out["replay_counts_off"] += int(np.sum(rec.counters[w] != run_cnt))
        out["latency_rel_gap"] = max(out["latency_rel_gap"],
                                     _rel_gap(rec.latency[w], run_lat))
        dec = rec.decisions[w]
        sizes = np.asarray(dec.sizes, np.int64)
        out["actuation_off"] += int(np.sum(rec.applied[w] != sizes))
        if w in judged:
            curves = []
            for i, (addrs, is_read) in enumerate(tapes):
                rate = (R.shards_rate(addrs.shape[0], target, floor)
                        if sampled else 1.0)
                curves.append(R.monitor(addrs, is_read, rate,
                                        R.shards_salt(w, i)))
            for i, (c, got) in enumerate(zip(curves, rec.curves[w])):
                same = (np.array_equal(c["edges"], got.edges)
                        and c["n"] == int(got.n_accesses)
                        and c["urd"] == int(rec.urd[w][i]))
                out["monitor_off"] += int(not same)
                if same:
                    out["curve_rel_gap"] = max(out["curve_rel_gap"],
                                               _rel_gap(got.heights,
                                                        c["heights"]))
            pol = ["ro" if c["write_ratio"] >= w_thr else "wb"
                   for c in curves]
            out["policy_off"] += sum(a != b.value
                                     for a, b in zip(pol, dec.policies))
            out["partition_off"] += R.partition_faults(
                sizes, dec.feasible, curves, capacity, c_min)
            out["partition_gap"] = max(
                out["partition_gap"],
                R.partition_gap(sizes, curves, capacity, c_min,
                                p["t_fast"], p["t_slow"]))
            for t, q in zip(tenants, pol):
                t.policy = q
        else:
            for t, q in zip(tenants, dec.policies):
                t.policy = q.value
        for t, s in zip(tenants, sizes.tolist()):
            t.resize(s)
        if any(out[k] > before[k] and out[k] > lim[k]["limit"] for k in out):
            bad.add(w)
    for t, (addrs, dirty) in zip(tenants, rec.state):
        ref_a = np.fromiter(t.od.keys(), np.int64, len(t.od))
        ref_d = np.fromiter(t.od.values(), bool, len(t.od))
        out["actuation_off"] += int(not (np.array_equal(ref_a, addrs)
                                         and np.array_equal(ref_d, dirty)))
    if out["actuation_off"] > lim["actuation_off"]["limit"]:
        bad.add(len(windows) - 1)
    return out, bad


def verdict(numbers: dict, lim: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) in the order of ``lim``."""
    shown = {k: {"value": numbers[k], "limit": lim[k]["limit"]}
             for k in lim if k in numbers}
    ok = len(shown) == len(lim) and all(
        v["value"] <= v["limit"] for v in shown.values())
    return ok, shown
