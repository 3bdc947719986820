"""Kernels layer: device milliseconds per window of the replay's
``cache_sim`` Pallas kernels (``cache_sim_scan``, ``cache_sim_levels_scan``),
summed from their events in the profiler trace, per traced window."""

import reduce

KERNELS = ("cache_sim_scan", "cache_sim_levels_scan")


def read(ctx: dict) -> float | None:
    s = reduce.kernel_seconds(ctx["events"], KERNELS, *ctx["bounds"])
    return 1000.0 * s / ctx["traced_windows"] if s > 0 else None
