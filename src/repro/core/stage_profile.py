"""The control plane's tracer: stage times, counters and host syncs, each
stage also a host span in the profiler's trace.

A ``StageProfile`` is created by the caller and handed down
(``ECICacheManager(profile=...)``, ``analyze_windows(profile=...)``, the
device and sharded pipelines, ``simulate_many(profile=...)``); every layer
that gets one records into it, and with ``None`` records nothing.  Stages
nest: each records the enclosing open stage as its parent, so a stage's
self time is its total less its children's (``self_time``).  Totals are
kept per stage name and counters per counter name, so nothing grows per
window; the start and end of each stage live in the profiler's trace,
where ``jax.profiler.TraceAnnotation`` puts them on the host plane, on
the clock of the device operations they launch.
"""
from __future__ import annotations

import contextlib
import time

import jax

__all__ = ["StageProfile"]


class StageProfile:
    """Per-stage wall time, counters and host-sync count of the control
    plane's layers.

    ``stage(name)`` times a stage and enters ``TraceAnnotation(name)``;
    ``step(name)`` tiles a function body with sibling stages (it ends the
    stage the previous ``step`` opened and opens ``name``; ``step(None)``
    only ends).  A stage left open by an exception is ended with its
    enclosing stage.  ``count(name, k)`` adds to ``counts[name]``.
    ``sync()`` marks one host synchronization (a blocking fetch or an
    explicit ``jax.block_until_ready`` fence).  ``end_window()`` counts a
    window, once, at the outermost caller: a layer that ends a window
    inside a caller's open stage leaves the count to that caller.

    With ``staged=True`` the device pipeline runs its stages as separate
    launches with a fence after each — attributing wall time per stage at
    the cost of extra syncs; the default fused mode performs exactly
    **one** sync per window (the result fetch), which ``syncs_per_window``
    exposes for the ≤1-sync assertion.
    """

    def __init__(self, staged: bool = False):
        self.staged = bool(staged)
        # open stages, outermost first: (name, start, annotation, stepped)
        self._open: list[tuple[str, float, jax.profiler.TraceAnnotation,
                               bool]] = []
        self.reset()

    def reset(self) -> None:
        """Drop every total, counter and count (e.g. those of warm-up)."""
        self.times: dict[str, float] = {}
        self.parents: dict[str, str | None] = {}
        self.counts: dict[str, int] = {}
        self.syncs = 0
        self.windows = 0

    def sync(self, k: int = 1) -> None:
        self.syncs += k

    def count(self, name: str, k: int = 1) -> None:
        self.counts[name] = self.counts.get(name, 0) + k

    def _enter(self, name: str, stepped: bool) -> int:
        self.parents[name] = self._open[-1][0] if self._open else None
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open.append((name, time.perf_counter(), ann, stepped))
        return len(self._open)

    def _exit(self, depth: int) -> None:
        """End the open stage at ``depth`` and every stage inside it."""
        while len(self._open) >= depth:
            name, t0, ann, _ = self._open.pop()
            self.times[name] = (self.times.get(name, 0.0)
                                + time.perf_counter() - t0)
            ann.__exit__(None, None, None)

    @contextlib.contextmanager
    def stage(self, name: str):
        depth = self._enter(name, False)
        try:
            yield
        finally:
            self._exit(depth)

    def step(self, name: str | None) -> None:
        if self._open and self._open[-1][3]:
            self._exit(len(self._open))
        if name is not None:
            self._enter(name, True)

    def end_window(self) -> None:
        if not self._open:
            self.windows += 1

    def self_time(self, name: str) -> float:
        """Seconds in stage ``name`` outside its child stages."""
        return self.times.get(name, 0.0) - sum(
            t for c, t in self.times.items() if self.parents.get(c) == name)

    @property
    def syncs_per_window(self) -> float:
        return self.syncs / max(self.windows, 1)

    def report(self) -> dict:
        return {"times_s": dict(self.times), "parents": dict(self.parents),
                "counts": dict(self.counts), "syncs": self.syncs,
                "windows": self.windows,
                "syncs_per_window": self.syncs_per_window}


def _pstage(profile: StageProfile | None, name: str):
    """``profile.stage(name)``; a no-op context without a profile."""
    return (profile.stage(name) if profile is not None
            else contextlib.nullcontext())


def _pstep(profile: StageProfile | None, name: str | None) -> None:
    """``profile.step(name)``; nothing without a profile."""
    if profile is not None:
        profile.step(name)
