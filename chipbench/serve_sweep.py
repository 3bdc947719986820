#!/usr/bin/env python3
"""The sweep that finds a serving mix's knee: its schedule at several
fixed rates, one fresh server each, in one process.

    python3 chipbench/serve_sweep.py --workload <cell> --seed <n> \\
        --rates 2,3,4,... [--traffic <mix>]

Each rate prints one JSON line: the window's seconds, the seconds after
the last arrival until the last request ended (``drain_s``), the mean
backlog (requests waiting or in flight) in each quarter of the arrival
span, tokens per second, the tails, and the largest decode batch.  The
knee is the highest rate at which the backlog does not grow over the
schedule: its last quarter's mean stays within 1.5 times its second
quarter's.  Rates are tried in the order given, and the sweep stops at
the first whose backlog grows or never drains.  The benchmark's own
runs never run this; the rate written
into a mix file (``arrivals.rate``) is 0.8 times the knee this finds.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run as RUN  # noqa: E402
import serve_cell as S  # noqa: E402
import serve_control  # noqa: E402


def one_rate(name: str, bench, seed: int, rate: float) -> dict:
    import gc
    cell = S.ServeCell(name, bench)
    cell.mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"],
                                             rate=rate))
    server = S.Server(cell, seed)
    eng = server.engine
    log = S.Log()
    unhook = S.hook(server, log)
    try:
        S.warm_up(server, cell, seed, log)
        win = S.Window(cell, seed, 0.0, server.cfg.vocab_size)
        log.steps.clear()
        t0 = time.perf_counter()
        backlog = []

        def sample():
            backlog.append((time.perf_counter() - t0,
                            len(eng.waiting) + len(eng.active)))
        try:
            elapsed = S.serve(server, win, log, sample)
        except RuntimeError as e:         # a window that never drains
            return {"rate": rate, "seed": seed, "error": str(e),
                    "backlog_end": len(eng.waiting) + len(eng.active),
                    "completed": sum(r.done for r in win.requests()),
                    "failed": len(log.failed)}
    finally:
        unhook()
        del server, eng
        gc.collect()
    span = float(win.sched.due[-1])
    t = np.array([b[0] for b in backlog])
    n = np.array([b[1] for b in backlog], float)
    quarters = [float(n[(t >= q * span / 4) & (t < (q + 1) * span / 4)]
                      .mean()) if np.any((t >= q * span / 4)
                                         & (t < (q + 1) * span / 4))
                else 0.0 for q in range(4)]
    e2e = (S.end_to_end(win, log, elapsed) if len(log.failed) < len(win.items)
           else {})
    out = {"rate": rate, "seed": seed, "window_s": elapsed,
           "drain_s": elapsed - span, "backlog_quarters": quarters,
           "grows": quarters[3] > 1.5 * max(quarters[1], 1.0),
           "largest_batch": max(b for _, b, _ in log.steps),
           "failed": len(log.failed), **e2e}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--traffic", default=None)
    args = ap.parse_args(argv)
    RUN.setup_jax()
    name, bench = ((args.workload, None) if args.traffic is None
                   else serve_control.adhoc_bench(args.workload,
                                                  args.traffic))
    for r in map(float, args.rates.split(",")):
        line = one_rate(name, bench, args.seed, r)
        print(json.dumps(line), flush=True)
        if "error" in line or line["grows"]:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
