"""The one traffic generator of the benchmark's serving cells.

A serving mix is a data file beside this module (``<mix>.json``) with
``"kind": "serving"``; this module reads it and builds what a run sends:

  * ``tenants``: tenants ``0 .. tenants-1``; a request's tenant is drawn
    with probability proportional to ``1 / rank**zipf_s`` (tenant 0 is
    rank 1: a few clients send most of the traffic);
  * ``system_prompt``: tokens of each tenant's system prompt, which
    starts every prompt of that tenant (0: no shared prefix);
  * ``prompt`` and ``output``: the user turn's and the answer's lengths in
    tokens, lognormal with ``median`` and ``sigma``, rounded and clipped to
    ``[min, max]``;
  * ``arrivals``: an open loop; the gaps between due times are
    ``gamma`` draws of ``shape`` (coefficient of variation
    ``1/sqrt(shape)``; 1 is Poisson) with mean ``1 / rate`` seconds;
  * ``requests``: requests in one schedule;
  * ``base_seed``: the seed of the schedule.  Tenants, lengths and due
    times come from it alone, so every ``--seed`` sends the same sizes at
    the same times; the run's seed draws the token ids only;
  * ``warm_batch``: the largest decode batch set-up builds (``warmup``).

A measured window replays the schedule whole, as often as ``--seconds``
asks (``passes``), each pass with fresh user tokens.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One schedule: per request its tenant, user-turn and output lengths
    and its due time in seconds from the schedule's start."""

    tenant: np.ndarray
    user_len: np.ndarray
    out_len: np.ndarray
    due: np.ndarray

    def __len__(self) -> int:
        return int(self.tenant.shape[0])


def load_mix(name: str) -> dict:
    """The serving mix ``name`` (``traffic/<name>.json``)."""
    mix = json.loads((HERE / f"{name}.json").read_text())
    if mix.get("kind") != "serving":
        raise ValueError(f"traffic {name!r} is not a serving mix")
    return mix


def _lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def tenant_shares(mix: dict) -> np.ndarray:
    w = 1.0 / np.arange(1, mix["tenants"] + 1) ** mix["zipf_s"]
    return w / w.sum()


def schedule(mix: dict) -> Schedule:
    """The mix's schedule, from ``base_seed`` alone."""
    rng = np.random.default_rng(mix["base_seed"])
    n = int(mix["requests"])
    tenant = rng.choice(mix["tenants"], n, p=tenant_shares(mix))
    user = _lengths(rng, mix["prompt"], n)
    out = _lengths(rng, mix["output"], n)
    arr = mix["arrivals"]
    if arr["process"] != "gamma":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    k = float(arr["shape"])
    gaps = rng.gamma(k, 1.0 / (k * float(arr["rate"])), n)
    due = np.cumsum(gaps) - gaps[0]          # the first request is due at 0
    return Schedule(tenant.astype(np.int64), user, out, due)


def passes(mix: dict, seconds: float) -> int:
    """Whole schedules a window of at least ``seconds`` replays."""
    span = float(schedule(mix).due[-1])
    return max(1, math.ceil(seconds / span)) if span > 0 else 1


def system_prompts(mix: dict, seed: int, vocab: int) -> list[np.ndarray]:
    """Each tenant's system prompt, drawn from ``seed``."""
    rng = np.random.default_rng([seed, 0])
    return [rng.integers(0, vocab, int(mix["system_prompt"])).astype(np.int32)
            for _ in range(mix["tenants"])]


def prompts(mix: dict, sched: Schedule, seed: int, vocab: int,
            pass_no: int) -> list[np.ndarray]:
    """Pass ``pass_no``'s prompts: the tenant's system prompt followed by a
    user turn of fresh tokens drawn from ``seed``."""
    sys_p = system_prompts(mix, seed, vocab)
    rng = np.random.default_rng([seed, 1, pass_no])
    return [np.concatenate([sys_p[t], rng.integers(0, vocab, int(u))
                            .astype(np.int32)])
            for t, u in zip(sched.tenant.tolist(), sched.user_len.tolist())]


def page_counts(mix: dict, page: int) -> list[int]:
    """Every prompt length in pages that the mix can draw."""
    lo = int(mix["system_prompt"]) + int(mix["prompt"]["min"])
    hi = int(mix["system_prompt"]) + int(mix["prompt"]["max"])
    return list(range(-(-lo // page), -(-hi // page) + 1))


def warmup(mix: dict, seed: int, vocab: int, page: int
           ) -> list[tuple[int, np.ndarray, int]]:
    """Set-up's requests ``(tenant, prompt, max_new_tokens)``, sent at once.

    One request per prompt length in pages the mix can draw, and at least
    ``warm_batch`` requests; request ``j`` asks for ``j + 2`` tokens, so
    after the one prefill step the decode batch shrinks by one a step,
    from the number of requests down to 1.  Prompts start with the
    tenants' system prompts, so set-up also fills the prefix cache.
    """
    counts = page_counts(mix, page)
    n = max(int(mix["warm_batch"]), len(counts))
    sys_p = system_prompts(mix, seed, vocab)
    sys_len = int(mix["system_prompt"])
    rng = np.random.default_rng([seed, 2])
    out = []
    for j in range(n):
        pages = counts[j % len(counts)]
        user = max(pages * page - sys_len, 1)
        t = j % mix["tenants"]
        prompt = np.concatenate([sys_p[t], rng.integers(0, vocab, user)
                                 .astype(np.int32)])
        out.append((t, prompt, j + 2))
    return out
