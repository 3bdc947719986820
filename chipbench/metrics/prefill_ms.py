"""Engine layer, prefill: host-clock milliseconds per request inside
``MultiTenantEngine._prefill_one`` (the prompt's forward pass, its page
lookups and admissions, and the first token's fetch), leaving out the
pool writes and ECI rebalances nested in it; averaged over every
request of the measured window."""

NAME = "_prefill_one"


def read(ctx: dict) -> float | None:
    vals = ctx["spans"].self_ms(NAME)
    return sum(vals) / len(vals) if vals else None
