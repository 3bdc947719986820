"""Partitioner layer: host-clock milliseconds per window inside the
manager's ``partition_fn`` (``repro.core.partitioner.pgd_solve``, the
Eq.-2 solver), averaged over the measured windows."""

NAME = "partition_fn"


def read(ctx: dict) -> float | None:
    vals = [w[NAME] for w in ctx["spans"] if NAME in w]
    return 1000.0 * sum(vals) / len(ctx["spans"]) if vals else None
