"""Compilation layer: executables built inside the measured window,
compiled or loaded from the persistent cache (``jax.monitoring``
lowering events).  Set-up warms up every shape its traffic uses (the
control plane: whole cycles of the tape pool; a serving cell: every
prompt length in pages, decode batch and ECI window shape), so this
reads 0 unless a measured window met a program shape warm-up did not."""


def read(ctx: dict) -> float | None:
    return float(ctx["compiles_in_window"])
