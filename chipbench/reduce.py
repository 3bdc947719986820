"""Reduction of a profiler trace to device metrics.

Input: the events of one profiler session as ``(plane, line, name,
start_ns, duration_ns)`` tuples (``tracing.read_events``).  Device planes
are named ``/device:TPU:<k>``; the operations that run on a device are the
events of its ``XLA Ops`` line, each named by its HLO instruction
(``%cache_sim_scan.1 = ...`` for a kernel).  The benchmark's own host
spans (``tracing``) sit on the host planes and share the trace's clock.
"""
from __future__ import annotations

from bisect import bisect_left
from collections import defaultdict

OPS_LINE = "XLA Ops"


def device_planes(events: list[tuple]) -> list[str]:
    return sorted({e[0] for e in events if e[0].startswith("/device:TPU")})


def device_ops(events: list[tuple], plane: str) -> list[tuple]:
    return [e for e in events if e[0] == plane and e[1] == OPS_LINE]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merged ``[start, end)`` intervals, in order."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def host_spans(events: list[tuple], names) -> list[tuple]:
    """``(name, start, end)`` of host events with one of ``names``."""
    names = set(names)
    return [(e[2], e[3], e[3] + e[4]) for e in events
            if not e[0].startswith("/device:") and e[2] in names]


def busy(events: list[tuple], lo: int, hi: int) -> float:
    """Seconds in ``[lo, hi)`` in which an operation ran, averaged over the
    device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    tot = 0
    for p in planes:
        ivs = union([(e[3], e[3] + e[4]) for e in device_ops(events, p)])
        tot += sum(e - s for s, e in clip(ivs, lo, hi))
    return tot / len(planes) / 1e9


def kernel_ops(events: list[tuple], kernels, lo: int, hi: int) -> list:
    """Device operations named after any of ``kernels`` that start in
    ``[lo, hi)``."""
    return [e for p in device_planes(events) for e in device_ops(events, p)
            if lo <= e[3] < hi and any(k in e[2] for k in kernels)]


def kernel_seconds(events: list[tuple], kernels, lo: int, hi: int) -> float:
    """Device seconds of the operations of ``kernels`` in ``[lo, hi)``."""
    return sum(e[4] for e in kernel_ops(events, kernels, lo, hi)) / 1e9


def check_complete(events: list[tuple], windows: list[tuple],
                   kernels) -> None:
    """Raise where the trace has lost events: a window ``(name, start,
    end)`` with no device operation on some device, or fewer launches of
    ``kernels`` than windows (each window replays its tape through one)."""
    planes = device_planes(events)
    if not planes:
        raise RuntimeError("the trace holds no TPU operation")
    for p in planes:
        starts = sorted(e[3] for e in device_ops(events, p))
        for _, lo, hi in windows:
            i = bisect_left(starts, lo)
            if i == len(starts) or starts[i] >= hi:
                raise RuntimeError(f"the trace lost events: no operation "
                                   f"on {p} in the window at {lo} ns")
    lo, hi = min(w[1] for w in windows), max(w[2] for w in windows)
    n = len(kernel_ops(events, kernels, lo, hi))
    if n < len(windows) * len(planes):
        raise RuntimeError(f"the trace lost events: {n} launches of "
                           f"{'/'.join(kernels)} in {len(windows)} windows")


def op_name(name: str) -> str:
    """An operation's name without its HLO text (``%while.8 = ...``)."""
    return name.split(" = ", 1)[0]


def top_ops(events: list[tuple], lo: int, hi: int, k: int = 10) -> list:
    """The ``k`` device operations that took most time in ``[lo, hi)``."""
    per = defaultdict(int)
    for p in device_planes(events):
        for e in device_ops(events, p):
            if lo <= e[3] < hi:
                per[op_name(e[2])] += e[4]
    top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(events: list[tuple], lo: int, hi: int, spans: list[tuple],
              k: int = 10) -> list:
    """The ``k`` longest device-idle gaps in ``[lo, hi)`` on the first
    device, each named by the innermost host span around its midpoint
    (``host`` where none is)."""
    planes = device_planes(events)
    if not planes:
        return []
    ivs = clip(union([(e[3], e[3] + e[4])
                      for e in device_ops(events, planes[0])]), lo, hi)
    gaps, t = [], lo
    for s, e in ivs:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) // 2
        around = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(around, key=lambda sp: sp[2] - sp[1])[0] if around \
            else "host"
        out.append([name, (e - s) / 1e9])
    return out
