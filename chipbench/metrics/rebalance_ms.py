"""ECI layer: host-clock milliseconds per rebalance
(``TieredKVCache.rebalance``: the window's page events into the monitor,
Alg. 1 and 3, Eq. 2, and quota enforcement on the pool), averaged over
the rebalances of the measured window."""

NAME = "rebalance"


def read(ctx: dict) -> float | None:
    vals = ctx["spans"].self_ms(NAME)
    return sum(vals) / len(vals) if vals else None
