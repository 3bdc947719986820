"""Kernels layer: the replay kernels' share of their roofline, in %.

The least work any replay counting must do per window is to read each
tape entry's occurrence links and occupancy and to write its count: 16
bytes (``prev``, ``nxt``, ``occ`` in, the count out, all int32) per entry
of the window tape, whose entries are the window's accesses plus the warm
residents replayed ahead of them.  Counting needs no floating-point
work, so the bound is bytes over the chip's HBM bandwidth
(``peaks.json``); the share is that least time over the kernels' device
time.  The same count judges any implementation of the counting, whether
it scans the whole (i, j) grid or not.
"""

import reduce

KERNELS = ("cache_sim_scan", "cache_sim_levels_scan")
BYTES_PER_ENTRY = 16


def read(ctx: dict) -> float | None:
    s = reduce.kernel_seconds(ctx["events"], KERNELS, *ctx["bounds"])
    if s <= 0:
        return None
    least = BYTES_PER_ENTRY * ctx["tape_entries"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / s
