#!/usr/bin/env python3
"""Chip benchmark of ECI-Cache: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/``) and a
traffic mix (``traffic/``).  A configuration of ``"kind": "serving"`` is
run by ``serve_cell.py`` (``cell_runner``); any other is the control plane,
run here.  The run builds the tape pool from ``--seed``,
builds ``ECICacheManager`` from the configuration and warms it up over
whole cycles of the pool (set-up), then runs windows back to back
for ``--seconds`` (one window in flight, the last one finished and
counted).  With ``--trace 0`` it reports the cell's end-to-end metrics;
with ``--trace 1`` it wraps the program's layers in spans, profiles the
first ``tracing.TRACE_WINDOWS`` measured windows and reports the
per-layer metrics (``metrics/<name>.py`` each); on the chip a trace that
lost events fails the run.  Afterwards the plain reference judges every window the system
produced (``check.py``); each number compared is printed with its limit,
last on standard error and last in the result line.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (measured windows), ``metrics``, ``device``
and, traced, ``breakdown``.  A run that finds no TPU, fewer chips than
the cell asks for, or a device missing from ``peaks.json`` prints no
result and exits non-zero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import cell as C  # noqa: E402
import check  # noqa: E402
import reduce  # noqa: E402
import tracing  # noqa: E402

TRACE_DIR = ROOT / ".chipbench_trace"


class NoDevice(RuntimeError):
    """The machine lacks what the cell needs: no result is printed."""


def setup_jax():
    """The persistent compilation cache at ``<checkout>/.jax_cache``, over
    any directory the environment names, so that two checkouts share
    nothing; every program is cached."""
    import jax
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def require_chips(chips: int) -> dict:
    """The device table entry of the chip this run holds."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if devs[0].device_kind not in peaks:
        raise NoDevice(f"device {devs[0].device_kind!r} is not in "
                       "peaks.json")
    return peaks[devs[0].device_kind]


def device_info() -> dict:
    import jax
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def read_metric(name: str, ctx: dict):
    """``metrics/<name>.py``'s ``read(ctx)``.  A quantity split by the
    end-to-end metric it moves (``<quantity>.<part>``, such as
    ``device_idle_pct.serve``) reads with its quantity's reader where it
    has none of its own."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def tape_entries(run: C.Run) -> int:
    """Entries of the next window's replay tape: each replayed tenant's
    warm residents plus its accesses."""
    win = run.pool[run.windows_run % len(run.pool)]
    return sum(len(t.cache) + len(tr) for t, tr in zip(run.mgr.tenants, win)
               if len(tr) and t.cache.capacity > 0)


def traced_metrics(cell: C.Cell, spans: tracing.Spans, tape_entries: int,
                   peaks: dict, counts: dict, on_chip: bool):
    """The cell's per-layer metrics from the profiler session and the
    spans, the device's busy and window seconds, and the breakdown.  On
    the chip a trace that lost events fails the run."""
    names = [tracing.WINDOW_SPAN, *tracing.LAYER_SPANS]
    events = tracing.read_events(TRACE_DIR, names)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    spans_ev = reduce.host_spans(events, names)
    wins = [s for s in spans_ev if s[0] == tracing.WINDOW_SPAN]
    if on_chip:
        reduce.check_complete(events, wins, tracing.REPLAY_KERNELS)
    lo, hi = min(s[1] for s in wins), max(s[2] for s in wins)
    busy_s, window_s = reduce.busy(events, lo, hi), (hi - lo) / 1e9
    ctx = {"events": events, "bounds": (lo, hi), "traced_windows": len(wins),
           "spans": spans.per_window, "tape_entries": tape_entries,
           "peaks": peaks, "busy_s": busy_s, "window_s": window_s, **counts}
    metrics = {}
    for m in cell.per_layer:
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    layers = [s for s in spans_ev if s[0] != tracing.WINDOW_SPAN]
    breakdown = {"device_ops": reduce.top_ops(events, lo, hi),
                 "idle_gaps": reduce.idle_gaps(events, lo, hi, layers)}
    return metrics, {"busy_s": busy_s, "window_s": window_s}, breakdown


def run_cell(workload: str | C.Cell, seed: int, seconds: float,
             trace: bool, need_chips: bool = True, in_place=None,
             **overrides) -> dict:
    """One run; returns the result object (``checks`` comes last).

    ``need_chips=False`` skips the look for a chip (tests on the CPU).
    The control runs use the other two: ``overrides`` are extra manager
    arguments, and ``in_place(tapes, rec, manager)`` replaces recorded
    outputs before the check."""
    cell = workload if isinstance(workload, C.Cell) else C.Cell(workload)
    jax = setup_jax()
    peaks = require_chips(int(cell.entry["chips"])) if need_chips else {}
    compiles = tracing.CompileCounter()
    spans = tracing.Spans() if trace else None
    if trace:
        overrides = {**spans.install(), **overrides}
    run = C.Run(cell, seed, **overrides)
    run.setup()
    setup_s = time.perf_counter() - T_START
    print(f"chipbench: {cell.name} seed {seed}: set-up {setup_s:.3f} s, "
          f"{run.windows_run} warm-up windows, {compiles.built} programs built, {compiles.seconds:.3f} s "
          "compiling or loading; "
          + ", ".join(f"{k} {v:.3f} s" for k, v in run.phases.items()),
          file=sys.stderr, flush=True)

    built0 = compiles.built
    entries = [0]
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        spans.open.clear()
        entries[0] = tape_entries(run)
        jax.profiler.start_trace(str(TRACE_DIR),
                                 profiler_options=tracing.profiler_options())
        run.annotate = tracing.WINDOW_SPAN

    def stop_trace() -> None:
        jax.profiler.stop_trace()
        run.annotate = None

    def after_window(n: int) -> None:
        """Spans close every window; the profiler covers the first
        ``TRACE_WINDOWS`` of them."""
        if not trace:
            return
        spans.close_window()
        if n < tracing.TRACE_WINDOWS:
            entries[0] += tape_entries(run)
        elif n == tracing.TRACE_WINDOWS:
            stop_trace()

    n, elapsed = run.measure(seconds, after_window)
    in_window = compiles.built - built0
    print(f"chipbench: {n} windows in {elapsed:.3f} s, {in_window} programs "
          "built in the measured window", file=sys.stderr, flush=True)
    if trace and run.annotate is not None:
        stop_trace()
    device = device_info()

    extra = {}
    if trace:
        metrics, device_trace, extra["breakdown"] = traced_metrics(
            cell, spans, entries[0], peaks,
            {"compiles_in_window": in_window, "setup_programs": built0},
            need_chips)
        device.update(device_trace)
    else:
        values = {"window_ms": 1000.0 * elapsed / n, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the check: the system's state is freed before the reference runs
    run.rec.final_state(run.mgr)
    del run.mgr
    tapes = [[(t.addrs, t.is_read) for t in run.pool[w % len(run.pool)]]
             for w in range(run.windows_run)]
    if in_place is not None:
        in_place(tapes, run.rec, cell.manager_kwargs)
    lim = check.limits()
    t_check = time.perf_counter()
    numbers, bad = check.judge(tapes, run.rec, cell.manager_kwargs, seed,
                               lim)
    print(f"chipbench: the reference judged {run.windows_run} windows in "
          f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr,
          flush=True)
    ok, shown = check.verdict(numbers, lim)
    measured = set(range(run.windows_run - n, run.windows_run))
    return {"correct": ok, "attempted": n, "failed": len(bad & measured),
            "metrics": metrics, "device": device, **extra, "checks": shown}


def cell_runner(workload: str):
    """The run of ``workload``: ``serve_cell.run_cell`` where its
    configuration is of ``"kind": "serving"``, ``run_cell`` otherwise."""
    import serve_cell
    if serve_cell.is_serving(workload):
        return functools.partial(serve_cell.run_cell,
                                 harness=sys.modules[__name__])
    return run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = cell_runner(args.workload)(args.workload, args.seed,
                                       args.seconds, bool(args.trace))
    except NoDevice as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 1
    for name, v in result["checks"].items():
        print(f"check {name} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
