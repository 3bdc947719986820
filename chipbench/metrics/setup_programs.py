"""Compilation layer: executables that set-up builds, compiled or loaded
from the persistent cache (``jax.monitoring`` lowering events).  The
program jits its replay on each exact tape length, so every window whose
tape lengths are new adds programs; a change that buckets those shapes
lowers this count, and with it the compile time of a checkout's first
run and the loading time of every later one."""


def read(ctx: dict) -> float | None:
    return float(ctx["setup_programs"])
