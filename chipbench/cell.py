"""One cell of the benchmark, found by name: its configuration, its
traffic mix, the system under test built from them, and the closed loop
that drives it.

The system under test is ``repro.core.manager.ECICacheManager`` as a user
builds it: the configuration's ``manager`` keys are its constructor
arguments, every other argument keeps its default (batch replay engine,
host decision pipeline, fixed Δt).  One call of ``run_window`` is one
window: replay, monitor, Eq.-2 partition, Alg. 3, actuation.
"""
from __future__ import annotations

import hashlib
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH / "traffic"))

import generator  # noqa: E402  (the benchmark's own traffic generator)


class Cell:
    """A workload entry of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, name: str):
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.entry = cells[name]
        self.name = name
        cfg = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.config = json.loads((ROOT / cfg["file"]).read_text())
        self.mix = generator.load_mix(self.entry["traffic"])
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]

    @property
    def profiles(self) -> list[str]:
        t = self.config["tenants"]
        base = t["profiles"]
        return [base[i % len(base)] for i in range(int(t["count"]))]

    @property
    def manager_kwargs(self) -> dict:
        return dict(self.config["manager"])


def program_tapes(pool: list[list]) -> list[list]:
    """The generator's tapes as the program's ``Trace`` objects."""
    from repro.core.trace import Trace
    return [[Trace(t.addrs, t.is_read, t.name) for t in win] for win in pool]


class Recorder:
    """What each window of the system produced, kept for the check.

    Per window: every tenant's running replay counters and latency, its
    hit-ratio curve and URD size, the decision, and the capacities in
    force after actuation.  Reading them is a few attribute loads per
    tenant, inside the timed loop.
    """

    def __init__(self):
        self.counters: list[np.ndarray] = []
        self.latency: list[np.ndarray] = []
        self.curves: list[list] = []
        self.urd: list[np.ndarray] = []
        self.decisions: list = []
        self.applied: list[np.ndarray] = []

    def snap(self, mgr) -> None:
        res = [t.result for t in mgr.tenants]
        self.counters.append(np.array(
            [[r.reads, r.read_hits, r.writes, r.write_hits, r.cache_writes]
             for r in res], np.int64))
        self.latency.append(np.array([r.total_latency for r in res]))
        self.curves.append([t.h_fn for t in mgr.tenants])
        self.urd.append(np.array([t.urd_size for t in mgr.tenants],
                                 np.int64))
        self.decisions.append(mgr.history[-1])
        self.applied.append(mgr.allocated_sizes())

    def final_state(self, mgr) -> None:
        """Each tenant's LRU content (LRU first) and dirty flags."""
        self.state = [tuple(np.array(x) for x in t.cache.state_arrays())
                      if len(t.cache) else (np.zeros(0, np.int64),
                                            np.zeros(0, bool))
                      for t in mgr.tenants]


def build_manager(cell: Cell, **overrides):
    from repro.core.manager import ECICacheManager
    return ECICacheManager(tenant_names=cell.profiles,
                           **{**cell.manager_kwargs, **overrides})


class Run:
    """Set-up and the closed loop of one run of one cell.

    Set-up: the tape pool from the seed, the manager, then warm-up on that
    manager in whole cycles of the pool until its state (LRU contents,
    dirty flags, sizes, policies) at the end of a cycle equals its state
    at the end of the cycle before.  From there the state repeats with the
    pool, and the program's shapes follow that state and the tapes, so
    warm-up has built (or loaded from the persistent cache) every program
    the measured windows run.  A state that has not settled within
    ``max_warmup_cycles`` fails the run.  The measured loop runs whole
    cycles of the pool, so every run times the same windows.
    """

    def __init__(self, cell: Cell, seed: int, **overrides):
        self.cell = cell
        self.seed = seed
        self.overrides = overrides
        self.rec = Recorder()
        self.windows_run = 0
        self.annotate = None              # profiler span name per window

    def setup(self) -> None:
        t0 = time.perf_counter()
        self.pool = program_tapes(generator.build_pool(
            self.cell.profiles, self.cell.mix, self.seed))
        self.mgr = build_manager(self.cell, **self.overrides)
        t1 = time.perf_counter()
        last = None
        for _ in range(int(self.cell.mix["max_warmup_cycles"])):
            for _ in self.pool:
                self.step()
            key = self.state_key()
            if key == last:
                break
            last = key
        else:
            raise RuntimeError(
                f"{self.cell.name}: the manager's state did not repeat "
                f"with the pool within {self.cell.mix['max_warmup_cycles']} "
                "cycles")
        self.phases = {"pool": t1 - t0, "warmup": time.perf_counter() - t1}

    def state_key(self) -> str:
        """A digest of every tenant's LRU content, dirty flags, capacity
        and policy."""
        h = hashlib.blake2b(digest_size=16)
        for t in self.mgr.tenants:
            if len(t.cache):
                for a in t.cache.state_arrays():
                    h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
            h.update(f"|{len(t.cache)}|{t.cache.capacity}|{t.policy}|"
                     .encode())
        return h.hexdigest()

    def step(self) -> None:
        tapes = self.pool[self.windows_run % len(self.pool)]
        if self.annotate is None:
            self.mgr.run_window(tapes)
        else:
            import jax
            with jax.profiler.TraceAnnotation(self.annotate):
                self.mgr.run_window(tapes)
        self.windows_run += 1
        self.rec.snap(self.mgr)

    def measure(self, seconds: float, on_window=None) -> tuple[int, float]:
        """Windows back to back until ``seconds`` have passed, in whole
        pool cycles.  Returns (windows, seconds)."""
        n = 0
        t0 = time.perf_counter()
        while True:
            self.step()
            n += 1
            if on_window is not None:
                on_window(n)
            if (n % len(self.pool) == 0
                    and time.perf_counter() - t0 >= seconds):
                break
        return n, time.perf_counter() - t0
