"""Cache layer, pool writes: milliseconds per prefill page scattered into
the HBM page pool (``BlockPool.write_page``).  The traced run waits for
the pool before and after each call, so the span is the write's device
time; averaged over every page written in the measured window."""

NAME = "write_page"


def read(ctx: dict) -> float | None:
    vals = ctx["spans"].self_ms(NAME)
    return sum(vals) / len(vals) if vals else None
