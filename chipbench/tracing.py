"""Instruments of the benchmark: a compile counter, host spans around the
program's layers, and the profiler session of a traced run.

Spans wrap three names of the program, in the traced run only:
``repro.core.manager.simulate_many`` (replay),
``repro.core.manager.analyze_windows`` (monitor) and the manager's
``partition_fn`` (handed in as a wrapper around
``repro.core.partitioner.pgd_solve``).  Each span is a host clock plus a
``jax.profiler.TraceAnnotation``; the three calls return host arrays, so
their clocks include the device work they wait for.  A wrapped name that
is gone raises: the traced run fails rather than report a layer as 0.
The spans cover every measured window; the profiler covers the first
``TRACE_WINDOWS`` of them.
"""
from __future__ import annotations

import functools
import glob
import time
from collections import defaultdict
from pathlib import Path

from reduce import OPS_LINE

SPANNED = {"simulate_many": "repro.core.manager",
           "analyze_windows": "repro.core.manager"}
PARTITION_FN = ("pgd_solve", "repro.core.partitioner")
WINDOW_SPAN = "chipbench_window"
LAYER_SPANS = (*SPANNED, "partition_fn")
# every window replays its tape through one of these kernels
REPLAY_KERNELS = ("cache_sim_scan", "cache_sim_levels_scan")
# windows a traced run profiles: the RO token replays' loop bodies put
# about a million operations a window in the trace
TRACE_WINDOWS = 2


class CompileCounter:
    """Executables built in this process, compiled or loaded from the
    persistent cache (one lowering each), and the seconds spent compiling
    or loading them (events that do not nest)."""

    def __init__(self):
        import jax.monitoring as mon
        self.built = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.built += 1
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.seconds += secs


class Spans:
    """Host-clock spans per window, by layer name."""

    def __init__(self):
        self.open: dict[str, float] = defaultdict(float)
        self.per_window: list[dict[str, float]] = []

    def wrap(self, name: str, fn):
        import jax

        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args, **kwargs)
            self.open[name] += time.perf_counter() - t0
            return out
        return spanned

    def close_window(self) -> None:
        self.per_window.append(dict(self.open))
        self.open.clear()

    def install(self) -> dict:
        """Wrap the program's spanned names; returns the manager keyword
        that hands in the wrapped partitioner."""
        import importlib
        for name, mod in SPANNED.items():
            m = importlib.import_module(mod)
            setattr(m, name, self.wrap(name, getattr(m, name)))
        name, mod = PARTITION_FN
        fn = getattr(importlib.import_module(mod), name)
        return {"partition_fn": self.wrap("partition_fn", fn)}


def profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def read_events(trace_dir: Path, host_names) -> list[tuple]:
    """The events the reduction reads: every operation on a TPU's ``XLA
    Ops`` line, and the host events named in ``host_names``, as ``(plane,
    line, name, start_ns, duration_ns)``.  Other events are skipped
    unread; the kernels are found by the operation's name."""
    from jax.profiler import ProfileData
    names = set(host_names)
    out = []
    for path in glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            dev = plane.name.startswith("/device:")
            if dev and not plane.name.startswith("/device:TPU"):
                continue
            for line in plane.lines:
                if dev and line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    if dev or name in names:
                        out.append((plane.name, line.name, name,
                                    int(ev.start_ns), int(ev.duration_ns)))
    return out
