"""Instruments of the serving cells: nested host spans around the serving
program's layers, and the reading of a profiler trace.

Spans wrap four names of the program, on the run's own objects and in the
traced run only: ``MultiTenantEngine._prefill_one`` (one request's
prefill), ``MultiTenantEngine._decode_batch`` (one decode step of the
batch), ``TieredKVCache.rebalance`` (one ECI window: monitor, Eq. 2,
Alg. 3, quota enforcement) and ``BlockPool.write_page`` (one prefill page
scattered into the pool).  Each span is a host clock plus a
``jax.profiler.TraceAnnotation``; a span's self time leaves out the spans
nested in it.  ``write_page`` returns before the device has written the
page, so its span waits for the pool (every device array it holds, in
whatever layout the program gives it) before and after the call: in the
traced run its clock is the write's device time (the untraced run does
not wait).  A wrapped name that is gone raises: the traced run fails
rather than report a layer as 0.
"""
from __future__ import annotations

import functools
import glob
import time
from bisect import bisect_left, bisect_right
from collections import defaultdict
from pathlib import Path

import numpy as np

import reduce

PREFILL, DECODE = "_prefill_one", "_decode_batch"
REBALANCE, WRITE = "rebalance", "write_page"
SPANNED = ((PREFILL, "engine"), (DECODE, "engine"), (REBALANCE, "tiered"),
           (WRITE, "pool"))
WINDOW_SPAN = "chipbench_serve_window"
MODULES_LINE = "XLA Modules"
# the program every decode step launches (the jitted decode step)
DECODE_PROGRAM = "_decode_step_jit"
# decode steps a traced run profiles (the layer scan puts a few thousand
# operations a step in the trace); the spans cover the whole window
TRACE_STEPS = 100


class Spans:
    """Host-clock spans by name: every call's start, end and self time."""

    def __init__(self):
        self.calls: dict[str, list[tuple[float, float, float]]] = \
            defaultdict(list)
        self._stack: list[float] = []     # child time of each open span

    def wrap(self, name: str, fn, wait=None):
        import jax

        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            if wait is not None:
                wait()
            self._stack.append(0.0)
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(name):
                    out = fn(*args, **kwargs)
                    if wait is not None:
                        wait()
            finally:
                t1 = time.perf_counter()
                child = self._stack.pop()
                if self._stack:
                    self._stack[-1] += t1 - t0
                self.calls[name].append((t0, t1, t1 - t0 - child))
            return out
        return spanned

    def install(self, objs: dict) -> None:
        """Wrap the spanned names on ``objs`` (``engine``, ``tiered``,
        ``pool``): attributes of those objects, so nothing outlives the
        run."""
        import jax
        pool = objs["pool"]

        def wait_pool():
            """Every device array the pool holds, whatever its layout."""
            jax.block_until_ready([v for v in vars(pool).values()
                                   if isinstance(v, jax.Array)])
        for name, owner in SPANNED:
            o = objs[owner]
            setattr(o, name, self.wrap(name, getattr(o, name),
                                       wait_pool if name == WRITE else None))

    def self_ms(self, name: str) -> list[float]:
        return [1000.0 * s for _, _, s in self.calls.get(name, ())]


def read_events(trace_dir: Path, host_names) -> list[tuple]:
    """``(plane, line, name, start_ns, duration_ns)`` of every operation on
    a TPU's ``XLA Ops`` line, every program on its ``XLA Modules`` line,
    and the host events named in ``host_names``."""
    from jax.profiler import ProfileData
    names = set(host_names)
    keep = (reduce.OPS_LINE, MODULES_LINE)
    out = []
    for path in glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            dev = plane.name.startswith("/device:")
            if dev and not plane.name.startswith("/device:TPU"):
                continue
            for line in plane.lines:
                if dev and line.name not in keep:
                    continue
                for ev in line.events:
                    if dev or ev.name in names:
                        out.append((plane.name, line.name, ev.name,
                                    int(ev.start_ns), int(ev.duration_ns)))
    return out


def ops_only(events: list[tuple]) -> list[tuple]:
    """The events ``reduce`` reads: operations and host spans."""
    return [e for e in events if e[1] != MODULES_LINE]


def programs(events: list[tuple], lo: int, hi: int) -> list[tuple]:
    """``(name, start, duration)`` of the programs that start in
    ``[lo, hi)`` on the first device, each named without its id."""
    planes = reduce.device_planes(events)
    if not planes:
        return []
    return [(e[2].split("(", 1)[0], e[3], e[4]) for e in events
            if e[0] == planes[0] and e[1] == MODULES_LINE
            and lo <= e[3] < hi]


def top_programs(events: list[tuple], lo: int, hi: int, k: int = 10):
    """The ``k`` programs that took most device time in ``[lo, hi)``."""
    per = defaultdict(int)
    for name, _, dur in programs(events, lo, hi):
        per[name] += dur
    top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


class Busy:
    """Device-busy nanoseconds inside any interval, on the first device:
    the union of its operation intervals with a running sum."""

    def __init__(self, events: list[tuple]):
        planes = reduce.device_planes(events)
        ivs = reduce.union([(e[3], e[3] + e[4])
                            for e in reduce.device_ops(events, planes[0])]
                           ) if planes else []
        self.s = np.array([a for a, _ in ivs], np.int64)
        self.e = np.array([b for _, b in ivs], np.int64)
        self.cum = np.concatenate([[0], np.cumsum(self.e - self.s)])

    def within(self, lo: int, hi: int) -> int:
        if hi <= lo or not len(self.s):
            return 0
        i = bisect_right(self.e, lo)              # first interval ending > lo
        j = bisect_left(self.s, hi)               # intervals starting < hi
        if j <= i:
            return 0
        tot = int(self.cum[j] - self.cum[i])
        tot -= max(0, lo - int(self.s[i]))
        tot -= max(0, int(self.e[j - 1]) - hi)
        return tot
