#!/usr/bin/env python3
"""Readings that set the limits of a serving cell's ``correct``: the
program on many seeds and its controls on a few, at the cell's own size
and load, in one process (the chip holds one).

    python3 chipbench/serve_control.py --workload <cell> --seconds <s> \\
        --program-seeds a,b,... --control-seeds x,y,z [--controls k,...] \\
        [--traffic <mix>] [--rate <requests/s>] [--audit]

  * ``fp8_reference`` (the control; a cell has to read it not correct):
    the plain reference computed in float8, one precision below the
    configuration's bf16, put in the program's place at each position of
    the same prompts and served tokens (``serve_check.judge``);
  * ``stale_page`` (a planted fault): at every decode step, the first page
    of each judged request's block table is swapped for a page of a
    request of another tenant (of another request where no other tenant is
    in the batch);
  * ``--audit``: every run also watches each request's pages at every
    decode step and reports, for the judged requests and for every request
    that lost a page, the step at which one of its pages was freed or
    handed to another key while it was still in flight (``null``: never),
    beside its numbers (for the latter: the reference's widest gap below
    its best at the served tokens, and how many served tokens were not the
    reference's best);
  * ``--traffic``: run the cell's configuration under another mix (a
    cell that is not in ``BENCHMARK.json``, named ``<config>.<mix>``).

Each run prints one JSON line ``{"kind", "seed", "correct", "checks",
...}``.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run as RUN  # noqa: E402
import serve_cell as S  # noqa: E402


def stale_page(server, log, tables):
    """Each judged request in the batch reads another request's page in
    place of its first one."""
    batch = log.batch
    judged = [i for i, r in enumerate(batch)
              if r.rid in log.judged_rids and r.pages]
    if not judged:
        return tables
    t = np.array(tables)
    for i in judged:
        r = batch[i]
        others = ([q for q in batch if q.tenant != r.tenant and q.pages]
                  or [q for q in batch if q is not r and q.pages])
        if others:
            t[i, 0] = others[0].pages[0]
    import jax.numpy as jnp
    return jnp.asarray(t)


class Audit:
    """Per request, the first decode step at which one of its pages
    changed hands while the request was in flight."""

    def __init__(self, then=None):
        self.owner: dict[tuple[int, int], tuple] = {}
        self.first_bad: dict[int, int] = {}
        self.steps = 0
        self.then = then

    def __call__(self, server, log, tables):
        self.steps += 1
        meta = server.pool.meta
        for r in log.batch:
            for pid in r.pages:
                m = meta.get(pid)
                now = (m.tenant, m.key) if m is not None else None
                was = self.owner.setdefault((r.rid, pid), now)
                if now != was and r.rid not in self.first_bad:
                    self.first_bad[r.rid] = self.steps
        return tables if self.then is None else self.then(server, log,
                                                          tables)


CONTROLS = {"fp8_reference": {"control": True},
            "stale_page": {"tables": stale_page}}


def adhoc_bench(workload: str, mix: str) -> tuple[str, dict]:
    """``BENCHMARK.json`` with a cell of ``workload``'s configuration under
    the mix ``mix`` added (for mixes whose cell is not in the file)."""
    bench = S.spec()
    base = {w["name"]: w for w in bench["workloads"]}[workload]
    name = f"{base['config']}.{mix}"
    bench["workloads"].append(dict(base, name=name, traffic=mix))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if workload in m.get("workloads", ()):
            m["workloads"] = [*m["workloads"], name]
    return name, bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--audit", action="store_true")
    ap.add_argument("--rate", type=float, default=None,
                    help="requests a second, in place of the mix's")
    args = ap.parse_args(argv)
    name, bench = ((args.workload, None) if args.traffic is None
                   else adhoc_bench(args.workload, args.traffic))
    kinds = args.controls.split(",")
    runs = [("program", int(s), {}) for s in
            filter(None, args.program_seeds.split(","))]
    runs += [(k, int(s), kw) for s in
             filter(None, args.control_seeds.split(","))
             for k, kw in CONTROLS.items() if k in kinds]
    for kind, seed, kw in runs:
        kw = dict(kw)
        audit = Audit(kw.pop("tables", None)) if args.audit else None
        tables = audit or kw.pop("tables", None)
        detail = {}
        try:
            cell = S.ServeCell(name, bench)
            if args.rate is not None:
                cell.mix = dict(cell.mix, arrivals=dict(
                    cell.mix["arrivals"], rate=args.rate))
            res = S.run_cell(cell, seed, args.seconds,
                             False, harness=RUN, tables=tables,
                             detail=detail, also_judge=(
                                 None if audit is None
                                 else lambda: audit.first_bad), **kw)
            line = {"kind": kind, "seed": seed, "correct": res["correct"],
                    "requests": res["attempted"], "failed": res["failed"],
                    "checks": {k: v["value"]
                               for k, v in res["checks"].items()},
                    "metrics": {k: v["value"]
                                for k, v in res["metrics"].items()},
                    "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
            line["urd_demand"] = detail.pop("urd_demand")
            if audit is not None:
                line["audit"] = {
                    "requests_hit": len(audit.first_bad),
                    "requests": {str(rid): {**d, "page_lost_at_step":
                                            audit.first_bad.get(rid)}
                                 for rid, d in detail.items()}}
        except Exception as e:  # a control that crashes has failed
            line = {"kind": kind, "seed": seed, "correct": False,
                    "error": repr(e)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
