"""Device layer: share of the measured window in which no operation ran
on the chip, in %, from the union of the device's operation intervals in
the profiler trace."""


def read(ctx: dict) -> float | None:
    if ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
