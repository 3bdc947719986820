"""Monitor layer: host-clock milliseconds per window inside
``repro.core.monitor.analyze_windows`` (the span around the manager's
call), averaged over the measured windows."""

NAME = "analyze_windows"


def read(ctx: dict) -> float | None:
    vals = [w[NAME] for w in ctx["spans"] if NAME in w]
    return 1000.0 * sum(vals) / len(ctx["spans"]) if vals else None
