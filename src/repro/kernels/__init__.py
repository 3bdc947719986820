"""Pallas TPU kernels for the performance-critical hot spots.

Each subpackage ships kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd wrapper: the kernel on TPU, the jnp oracle elsewhere) and
ref.py (pure-jnp oracle used by the allclose test sweeps).
"""
