"""The comparison that decides ``correct`` in a serving cell.

After the measured window has closed, the device memory peak has been
read and the server is freed, the cell's plain reference
(``serve_ref/<file>.py``, float32 at the highest matrix precision, with
the weights made again from the seed) judges what the timed path
produced:

  * ``served_off``: window requests that failed (raised, as on ``KV pool
    exhausted``, and were dropped) or ended with a token count other than
    they asked for: a server that sheds requests is not correct;
  * ``logit_rel_gap``: for each judged request (``serve_cell.judged``: the
    last of the window, the longest of the schedule and others drawn from
    the seed), the logits the program computed for each of its tokens (at
    the prompt's last position in prefill, then at every decode step),
    against the reference's over the prompt and the served tokens:
    the worst ``max|program - reference| / rms(reference row)``;
  * ``token_gap``: the widest gap by which a served token's reference
    logit lies below the reference's best at that position, in logits
    (greedy decoding serves the best token, so a sound run reads rounding
    near-ties only);
  * every ECI window of the run (warm-up's included), judged with the
    control plane's plain reference (``reference.py``) on the page events
    the manager recorded: ``monitor_off`` (URD size, curve breakpoints and
    access count), ``curve_rel_gap`` (curve heights), ``policy_off``
    (Alg. 3), ``partition_off`` and ``partition_gap`` (Eq. 2 over the
    reference curves), and ``actuation_off`` (the quotas in force after
    the window against the decision).

Each number has a limit (``serve_limits.json``, with the readings it was
set from); a run is correct when every number is at or under its limit.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

import reference as R
from check import _rel_gap, verdict  # noqa: F401  (the control plane's)

BENCH = Path(__file__).resolve().parent


def limits() -> dict:
    return json.loads((BENCH / "serve_limits.json").read_text())


@dataclasses.dataclass
class Served:
    """What the check reads, all on the host: per window request its
    prompt, tokens and asked length; the judged requests' logit rows;
    the ECI windows; the program's parameter shapes."""

    window: list[tuple[int, np.ndarray, list[int], int]]
    failed: set[int]
    judged: dict[int, np.ndarray]
    eci: list[dict]
    shapes: object
    vocab: int
    also: set = dataclasses.field(default_factory=set)

    @classmethod
    def collect(cls, win, log, server) -> "Served":
        rows = {}
        for rid in sorted(log.judged_rids):
            kept = log.kept.get(rid, [])
            rows[rid] = (np.stack([np.asarray(x) for x in kept])
                         if kept else np.zeros((0, 0), np.float32))
        window = [(r.rid, np.asarray(r.prompt), list(r.generated),
                   r.max_new_tokens) for r in win.requests()]
        return cls(window, set(log.failed), rows, log.eci, server.shapes,
                   server.cfg.vocab_size)


def judge(cell, seed: int, served: Served, lim: dict,
          detail: dict | None = None, control: bool = False
          ) -> tuple[dict, set]:
    """Numbers compared, by name, and the requests that broke a limit;
    ``detail`` (where given) gets each judged request's two gaps.

    ``control``: the reference in float8 (``fp8=True`` of the reference
    module) is put in the program's place: its logits at each position of
    the same prompts and served tokens are compared in place of the
    program's, and the token it puts first in place of the served one."""
    import gc
    gc.collect()
    out = {"served_off": 0, "logit_rel_gap": 0.0, "token_gap": 0.0}
    bad = set()
    for rid, _, gen, want in served.window:
        if rid in served.failed or len(gen) != want:
            out["served_off"] += 1
            bad.add(rid)
    ref = cell.ref
    w32 = ref.as_f32(ref.weights(seed, cell.model, served.shapes))
    w8 = ref.fp8_weights(w32) if control else None
    V = served.vocab
    by_rid = {rid: (p, g) for rid, p, g, _ in served.window}
    for rid, rows in served.judged.items():
        prompt, gen = by_rid[rid]
        if rid in served.failed or not gen:
            continue
        toks = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        pos = np.arange(len(prompt) - 1, len(toks))
        r = ref.logits(w32, cell.model, toks, pos)          # [n, V]
        rms = np.sqrt(np.mean(r.astype(np.float64) ** 2, axis=1))
        best = r.max(axis=1)
        if control:
            rows = ref.logits(w8, cell.model, toks, pos, fp8=True)
            gen = rows.argmax(axis=1)
        tok_gap = float(np.max(best - r[np.arange(len(gen)), gen]))
        if rows.shape[0] != len(gen):
            rel = 1e30        # a token without its logits (finite: JSON)
        else:
            rel = float(np.max(np.abs(rows[:, :V].astype(np.float64) - r)
                               .max(axis=1) / rms))
        if detail is not None:
            detail[rid] = {"logit_rel_gap": rel, "token_gap": tok_gap,
                           "tokens": len(gen)}
        out["logit_rel_gap"] = max(out["logit_rel_gap"], rel)
        out["token_gap"] = max(out["token_gap"], tok_gap)
        if (rel > lim["logit_rel_gap"]["limit"]
                or tok_gap > lim["token_gap"]["limit"]):
            bad.add(rid)
    for rid in sorted(served.also if detail is not None else ()):
        prompt, gen = by_rid[rid]
        if rid in served.failed or not gen:
            continue
        toks = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        r = ref.logits(w32, cell.model, toks,
                       np.arange(len(prompt) - 1, len(toks)))
        gaps = r.max(axis=1) - r[np.arange(len(gen)), gen]
        detail[rid] = {"token_gap": float(gaps.max()), "tokens": len(gen),
                       "tokens_off_best": int(np.sum(gaps > 0))}
    del w32, w8
    out.update(judge_eci(served.eci, cell.config["manager"],
                         cell.config["tenants"]))
    return out, bad


def judge_eci(windows: list[dict], manager: dict, n_tenants: int) -> dict:
    """The ECI windows against the control plane's reference (exact
    monitor: the serving configurations sample nothing)."""
    capacity, c_min = int(manager["capacity"]), int(manager["c_min"])
    w_thr = float(manager.get("w_threshold", 0.5))
    t_fast = float(manager.get("t_fast", 1.0))
    t_slow = float(manager.get("t_slow", 20.0))
    if manager.get("sample_rate") is not None:
        raise ValueError("the serving check judges an exact monitor only")
    out = {"monitor_off": 0, "curve_rel_gap": 0.0, "policy_off": 0,
           "partition_off": 0, "partition_gap": 0.0, "actuation_off": 0}
    empty = (np.zeros(0, np.int64), np.zeros(0, bool))
    for w in windows:
        dec = w["decision"]
        curves = [R.monitor(*w["tapes"].get(i, empty), 1.0, 0)
                  for i in range(n_tenants)]
        for i, (c, got) in enumerate(zip(curves, w["curves"])):
            same = (got is not None and np.array_equal(c["edges"], got.edges)
                    and c["n"] == int(got.n_accesses)
                    and c["urd"] == w["urd"][i])
            out["monitor_off"] += int(not same)
            if same:
                out["curve_rel_gap"] = max(out["curve_rel_gap"],
                                           _rel_gap(got.heights, c["heights"]))
        pol = ["ro" if c["write_ratio"] >= w_thr else "wb" for c in curves]
        out["policy_off"] += sum(a != b.value
                                 for a, b in zip(pol, dec.policies))
        sizes = np.asarray(dec.sizes, np.int64)
        out["partition_off"] += R.partition_faults(sizes, dec.feasible,
                                                   curves, capacity, c_min)
        out["partition_gap"] = max(out["partition_gap"], R.partition_gap(
            sizes, curves, capacity, c_min, t_fast, t_slow))
        q = w.get("quotas")
        out["actuation_off"] += int(q is None or list(map(int, q))
                                    != sizes.tolist())
    return out

