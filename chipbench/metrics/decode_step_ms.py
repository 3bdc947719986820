"""Engine layer, decode: host-clock milliseconds per decode step inside
``MultiTenantEngine._decode_batch`` (page admissions, the batched
one-token step over the pool, the scatter of the new keys and values,
and the tokens' fetch), leaving out ECI rebalances nested in it;
averaged over every step of the measured window."""

NAME = "_decode_batch"


def read(ctx: dict) -> float | None:
    vals = ctx["spans"].self_ms(NAME)
    return sum(vals) / len(vals) if vals else None
