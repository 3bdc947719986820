"""Replay layer: host-clock milliseconds per window inside
``repro.core.batch_sim.simulate_many`` (the span around the manager's
call), averaged over the measured windows."""

NAME = "simulate_many"


def read(ctx: dict) -> float | None:
    vals = [w[NAME] for w in ctx["spans"] if NAME in w]
    return 1000.0 * sum(vals) / len(ctx["spans"]) if vals else None
