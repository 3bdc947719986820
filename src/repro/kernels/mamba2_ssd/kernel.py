"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

The SSD algorithm splits the sequence into chunks of Q tokens: within a
chunk the SSM output is a masked quadratic form (two MXU matmuls), across
chunks a [headdim, state] recurrence is carried.  Grid:
(batch*heads, num_chunks) with the chunk axis innermost/sequential — the
carried state lives in fp32 VMEM scratch, exactly mirroring
``repro.models.mamba2.mamba2_forward``'s ``lax.scan`` (the jnp oracle).

Per chunk, with decay ``seg = cumsum(dt*A)``:
  y_intra = ((C Bᵀ) ⊙ L) (x·dt)      L[i,j] = exp(seg_i - seg_j), i>=j
  y_inter = (C · h_prev) ⊙ exp(seg)
  h_new   = h_prev · exp(seg_Q) + Σ_j exp(seg_Q - seg_j) B_j (x_j·dt_j)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["mamba2_ssd"]


def _kernel(x_ref, b_ref, c_ref, seg_ref, y_ref, h_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)        # [Q, P] (already x * dt)
    B = b_ref[0].astype(jnp.float32)        # [Q, N]
    C = c_ref[0].astype(jnp.float32)        # [Q, N]
    seg = seg_ref[0].astype(jnp.float32)    # [Q, 1]

    # intra-chunk quadratic part
    L = jnp.exp(jnp.clip(seg - seg.T, -60.0, 0.0))          # [Q, Q]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, L, 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Q,Q]
    y = jax.lax.dot_general(scores * L, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # [Q,P]

    # inter-chunk contribution from the carried state h [N, P]
    decay_in = jnp.exp(jnp.clip(seg, -60.0, 0.0))                      # [Q,1]
    y += decay_in * jax.lax.dot_general(
        C, h_scr[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update
    seg_last = seg[chunk - 1:chunk, :]                                 # [1,1]
    decay_out = jnp.exp(jnp.clip(seg_last - seg, -60.0, 0.0))          # [Q,1]
    s_new = jax.lax.dot_general(B, decay_out * x,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)    # [N,P]
    h_scr[...] = h_scr[...] * jnp.exp(jnp.clip(seg_last, -60.0, 0.0)) \
        + s_new
    y_ref[0] = y.astype(y_ref.dtype)


def mamba2_ssd(x_dt: jax.Array, B: jax.Array, C: jax.Array,
               seg: jax.Array, *, chunk: int,
               interpret: bool = False) -> jax.Array:
    """Chunked SSD scan.

    x_dt: [BH, S, P]   (x * dt, flattened batch*heads)
    B:    [BH, S, N]   (input matrix, already broadcast per head group)
    C:    [BH, S, N]
    seg:  [BH, S, 1]   per-chunk cumsum of dt*A (reset at chunk starts)
    returns y [BH, S, P]
    """
    BH, S, P = x_dt.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, ci: (b, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, ci: (b, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x_dt.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x_dt, B, C, seg)
