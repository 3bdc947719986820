"""URD/TRD reuse-distance counting — Pallas TPU kernel.

The paper's Analyzer spends its budget computing reuse distances (Appendix B
reports up to 22.7 s per window with modified PARDA on the host CPU).  On
TPU we use the counting formulation (DESIGN.md §5):

    RD(i) = #{ j : prev[i] < j < i  and  nxt[j] >= i }

(each distinct address between two touches contributes exactly one j — its
last occurrence inside the window).  This is an O(n²/tile) masked-count
over the (i, j) plane: embarrassingly parallel over i-tiles, sequential
accumulation over j-tiles — ideal VPU work, and ~3 orders of magnitude
faster than the pointer-chasing treap on host.  URD masking (only read
re-touches sample) is applied by the caller via ``sample_mask``.

Grid: (num_i_tiles, num_j_tiles), j innermost with an fp32 VMEM accumulator
revisited across j-tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["urd_scan"]


def _kernel(prev_ref, nxt_ref, out_ref, acc_scr, *, tile: int):
    ii = pl.program_id(0)
    jj = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(jj == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    prev_i = prev_ref[0]                                 # [1, tile] int32
    i_idx = ii * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), 0)                      # rows: i
    j_idx = jj * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), 1)                      # cols: j
    nxt_j = nxt_ref[0]                                   # [1, tile] int32

    contrib = (
        (j_idx > prev_i.reshape(tile, 1))
        & (j_idx < i_idx)
        & (nxt_j.reshape(1, tile) >= i_idx)
    )
    acc_scr[...] += jnp.sum(contrib.astype(jnp.float32), axis=1,
                            keepdims=True)

    @pl.when(jj == nj - 1)
    def _finalize():
        out_ref[0] = acc_scr[...].reshape(tile).astype(jnp.int32)


def urd_scan(prev: jax.Array, nxt: jax.Array, *, tile: int = 256,
             interpret: bool = False) -> jax.Array:
    """prev/nxt: int32[n] occurrence links -> counts int32[n].

    counts[i] = distinct addresses strictly between prev[i] and i.
    Cold accesses (prev[i] < 0) return counts over j<i with nxt>=i of the
    full prefix — callers mask them out with the sample mask.
    """
    n = prev.shape[0]
    nt = -(-n // tile)
    pad = nt * tile - n
    if pad:
        # padded i rows: prev = n (so j > prev never holds -> count 0)
        prev = jnp.pad(prev, (0, pad), constant_values=n)
        # padded j cols: nxt = -1 (so nxt >= i never holds -> no contribution)
        nxt = jnp.pad(nxt, (0, pad), constant_values=-1)
    prev2 = prev.reshape(nt, tile).astype(jnp.int32)
    nxt2 = nxt.reshape(nt, tile).astype(jnp.int32)

    kernel = functools.partial(_kernel, tile=tile)
    out = pl.pallas_call(
        kernel,
        grid=(nt, nt),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tile), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nt, tile), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(prev2, nxt2)
    return out.reshape(nt * tile)[:n]
