#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program on many seeds
and its control and planted faults on a few, at a cell's own size, in one
process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        --program-seeds a,b,... --control-seeds x,y,z [--controls k,...]

  * ``f32_reference`` (the control; every cell has to read it not
    correct): the plain reference in float32 put in the place of the
    replay's latency accounting and the monitor's curve heights, one
    precision below the configuration's float64;
  * ``first_fit`` (a planted fault, for cells where Eq. 2 binds; where it
    does not, the partitioner's answer is every tenant's URD size and
    there is nothing to split): a partitioner that keeps Eq. 2's
    constraints (floors, URD ceilings, breakpoints, a filled budget) but
    grants the budget tenant by tenant in index order, so only the
    objective shows it.

Each run prints one JSON line ``{"kind", "seed", "correct", "checks"}``.
The benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import reference as R  # noqa: E402
import run as RUN  # noqa: E402


def f32_reference(tapes: list, rec, manager: dict) -> None:
    """Replace the recorded running latencies and curve heights by the
    reference's, computed in float32 (replay on the recorded decisions)."""
    p = {k: float(manager[k]) for k in ("t_fast", "t_slow",
                                        "t_write_bypass", "flush_cost")}
    sampled = manager.get("sample_rate") == "auto"
    tenants = [R.Tenant(int(manager["initial_blocks"])) for _ in tapes[0]]
    run_lat = np.zeros(len(tenants), np.float32)
    for w, win in enumerate(tapes):
        dec = rec.decisions[w]
        curves = []
        for i, (t, (addrs, is_read)) in enumerate(zip(tenants, win)):
            r = R.replay(t, addrs, is_read, p, np.float32)
            run_lat[i] = np.float32(run_lat[i] + np.float32(
                r["total_latency"]))
            rate = (R.shards_rate(addrs.shape[0],
                                  int(manager["sample_target"]),
                                  int(manager["sample_floor"]))
                    if sampled else 1.0)
            c = R.monitor(addrs, is_read, rate, R.shards_salt(w, i),
                          np.float32)
            got = rec.curves[w][i]
            curves.append(type(got)(got.edges, c["heights"]
                                    if c["heights"].shape == got.heights.shape
                                    else got.heights, got.n_accesses))
        rec.latency[w] = run_lat.astype(np.float64)
        rec.curves[w] = curves
        for t, q, s in zip(tenants, dec.policies, dec.sizes):
            t.policy = q.value
            t.resize(int(s))


def first_fit(hs, capacity, t_fast, t_slow, c_min=0, **kw):
    """``pgd_solve``'s result with its sizes replaced, where Eq. 2 binds,
    by the floors plus, tenant by tenant, the largest breakpoint that
    still fits the budget."""
    import dataclasses
    from repro.core.partitioner import pgd_solve
    p = pgd_solve(hs, capacity, t_fast, t_slow, c_min=c_min, **kw)
    if p.feasible:
        return p
    urd = np.array([h.max_useful_size for h in hs], np.int64)
    sizes = np.minimum(c_min, urd)
    left = capacity - int(sizes.sum())
    for i, h in enumerate(hs):
        e = h.edges[(h.edges > sizes[i])
                    & (h.edges <= min(urd[i], sizes[i] + left))]
        if e.size:
            left -= int(e[-1]) - int(sizes[i])
            sizes[i] = int(e[-1])
    return dataclasses.replace(p, sizes=sizes)


CONTROLS = {"f32_reference": dict(in_place=f32_reference),
            "first_fit": dict(partition_fn=first_fit)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    args = ap.parse_args(argv)
    kinds = args.controls.split(",")
    runs = [("program", int(s), {}) for s in
            filter(None, args.program_seeds.split(","))]
    runs += [(k, int(s), kw) for s in
             filter(None, args.control_seeds.split(","))
             for k, kw in CONTROLS.items() if k in kinds]
    for kind, seed, kw in runs:
        try:
            res = RUN.run_cell(args.workload, seed, args.seconds, False,
                               **kw)
            line = {"kind": kind, "seed": seed, "correct": res["correct"],
                    "windows": res["attempted"],
                    "checks": {k: v["value"]
                               for k, v in res["checks"].items()}}
        except Exception as e:  # a control that crashes has failed
            line = {"kind": kind, "seed": seed, "correct": False,
                    "error": repr(e)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
