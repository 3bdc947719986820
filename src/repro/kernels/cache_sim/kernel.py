"""Occupancy-masked LRU stack-distance counting — Pallas TPU kernel.

The batch simulation engine (``repro.core.batch_sim``) turns window replay
into the counting problem

    SD(i) = #{ j : prev[i] < j < i,  occ[j],  nxt[j] >= i }

(an access is resident iff SD < capacity; see the batch_sim docstring for
the derivation).  This is the ``urd_scan`` counting formulation with one
extra per-``j`` occupancy mask: ``occ = 1`` everywhere for WB/WT (every
access installs or touches), ``occ = is_read`` for RO write-around.

Same counting as ``urd_scan``: O(n²/tile) masked counts over the (i, j)
plane, grid (num_i_tiles, num_j_tiles) with j innermost and an fp32 VMEM
accumulator revisited across j-tiles — pure VPU work.

Every per-access vector is laid out as ``[num_tiles, 1, tile]`` and walked
one ``(1, tile)`` row tile per grid step (``_row_spec``): the squeezed
leading axis keeps the block's last two dims equal to ``(1, tile)``, which
the TPU compiler accepts for ``tile`` a multiple of 128 (a ``(1, tile)``
block of a 2-D ``[num_tiles, tile]`` array is refused — its second-minor
dim is neither a multiple of 8 nor the full extent).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["cache_sim_scan", "cache_sim_segments_scan",
           "cache_sim_levels_scan", "live_count_scan"]


def _row_spec(tile: int, index_map) -> pl.BlockSpec:
    """One ``(1, tile)`` row tile of a ``[num_tiles, 1, tile]`` vector."""
    return pl.BlockSpec((None, 1, tile), index_map)


def _row_tiles(x: jax.Array, nt: int, tile: int) -> jax.Array:
    return x.reshape(nt, 1, tile).astype(jnp.int32)


def _i_map(i, j):
    return (i, 0, 0)


def _j_map(i, j):
    return (j, 0, 0)


def _kernel(prev_ref, nxt_ref, occ_ref, out_ref, acc_scr, *, tile: int):
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
    occ_j = occ_ref[0]                                   # [1, tile] int32

    contrib = (
        (j_idx > prev_i.reshape(tile, 1))
        & (j_idx < i_idx)
        & (nxt_j.reshape(1, tile) >= i_idx)
        & (occ_j.reshape(1, tile) > 0)
    )
    acc_scr[...] += jnp.sum(contrib.astype(jnp.float32), axis=1,
                            keepdims=True)

    @pl.when(jj == nj - 1)
    def _finalize():
        out_ref[0] = acc_scr[...].reshape(tile).astype(jnp.int32)


def cache_sim_scan(prev: jax.Array, nxt: jax.Array, occ: jax.Array, *,
                   tile: int = 256, interpret: bool = False) -> jax.Array:
    """prev/nxt int32[n] occurrence links, occ int32[n] -> counts int32[n].

    counts[i] = occupying distinct addresses strictly between prev[i] and i.
    Cold accesses (prev[i] < 0) return prefix counts — callers mask them.
    """
    n = prev.shape[0]
    nt = -(-n // tile)
    pad = nt * tile - n
    if pad:
        # padded i rows: prev = n (j > prev never holds -> count 0)
        prev = jnp.pad(prev, (0, pad), constant_values=n)
        # padded j cols: never occupy, and nxt = -1 as belt-and-braces
        nxt = jnp.pad(nxt, (0, pad), constant_values=-1)
        occ = jnp.pad(occ, (0, pad), constant_values=0)
    kernel = functools.partial(_kernel, tile=tile)
    out = pl.pallas_call(
        kernel,
        grid=(nt, nt),
        in_specs=[_row_spec(tile, _i_map), _row_spec(tile, _j_map),
                  _row_spec(tile, _j_map)],
        out_specs=_row_spec(tile, _i_map),
        out_shape=jax.ShapeDtypeStruct((nt, 1, tile), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="cache_sim_scan",
    )(*(_row_tiles(a, nt, tile) for a in (prev, nxt, occ)))
    return out.reshape(nt * tile)[:n]


def _segments_kernel(prev_ref, nxt_ref, occ_ref, out_ref, acc_scr, *,
                     tile: int, seg_width: int):
    ii = pl.program_id(0)
    jj = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(jj == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    prev_i = prev_ref[0]                                 # [1, tile] int32
    # the j plane is restricted to the i-tile's aligned block (the
    # segment's own block, or the i tile itself for segments below a tile)
    blk = max(seg_width, tile)
    j_base = (ii * tile) // blk * blk + jj * tile
    i_idx = ii * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), 0)                      # rows: i
    j_idx = j_base + jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), 1)                      # cols: j
    nxt_j = nxt_ref[0]                                   # [1, tile] int32
    occ_j = occ_ref[0]                                   # [1, tile] int32

    contrib = (
        (j_idx > prev_i.reshape(tile, 1))
        & (j_idx < i_idx)
        & (nxt_j.reshape(1, tile) >= i_idx)
        & (occ_j.reshape(1, tile) > 0)
    )
    if seg_width < tile:
        # several segments share the tile: keep j inside i's own segment
        contrib = contrib & (j_idx >= i_idx // seg_width * seg_width)
    acc_scr[...] += jnp.sum(contrib.astype(jnp.float32), axis=1,
                            keepdims=True)

    @pl.when(jj == nj - 1)
    def _finalize():
        out_ref[0] = acc_scr[...].reshape(tile).astype(jnp.int32)


def cache_sim_segments_scan(prev: jax.Array, nxt: jax.Array, occ: jax.Array,
                            *, seg_width: int, tile: int = 256,
                            interpret: bool = False) -> jax.Array:
    """``cache_sim_scan`` on a segment-aligned padded tape, restricted grid.

    The tape (length a multiple of ``seg_width``) holds one padded segment
    per ``seg_width``-aligned block (``batch_sim.padded_segment_layout``
    guarantees alignment), links are severed at segment boundaries and
    padding rows carry ``occ = 0``, so counting windows never cross blocks
    and every (i, j) tile outside the i-tile's own block contributes
    exactly zero (the dense proof lives in ``cache_sim_segments_ref``).
    The grid therefore shrinks from ``nt x nt`` to
    ``nt x (seg_width / tile)`` — the j loop visits only the aligned
    block, the kernel body is ``_kernel`` with the absolute j base offset.
    Segments narrower than a tile share it: the grid is ``nt x 1`` and the
    kernel masks ``j`` to the query's own ``seg_width`` block, so ``tile``
    stays a TPU-legal lane width whatever the segment width.
    Cold and padding rows return prefix counts — callers mask them.

    This is the TPU counting route of both the per-width host launches
    (``ops.stack_distances_segments_accel``) and the fused device window
    program (``ops.segment_counts_device``, inlined into
    ``core.device_pipeline``'s single-jit window decision — there the
    call traces into the surrounding program, so no host sync separates
    it from the curve/write-ratio/partition stages); off TPU the fused
    program substitutes the O(m log² w) ``cache_sim_segments_tree``
    oracle instead of this kernel's interpret mode.
    """
    n = prev.shape[0]
    nt = -(-n // tile)
    pad = nt * tile - n
    if pad:                                  # only when seg_width < tile
        prev = jnp.pad(prev, (0, pad), constant_values=n)
        nxt = jnp.pad(nxt, (0, pad), constant_values=-1)
        occ = jnp.pad(occ, (0, pad), constant_values=0)
    blk = max(int(seg_width), tile)
    jt = blk // tile

    def j_map(i, j):
        return ((i * tile) // blk * jt + j, 0, 0)

    kernel = functools.partial(_segments_kernel, tile=tile,
                               seg_width=int(seg_width))
    out = pl.pallas_call(
        kernel,
        grid=(nt, jt),
        in_specs=[_row_spec(tile, _i_map), _row_spec(tile, j_map),
                  _row_spec(tile, j_map)],
        out_specs=_row_spec(tile, _i_map),
        out_shape=jax.ShapeDtypeStruct((nt, 1, tile), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="cache_sim_segments_scan",
    )(*(_row_tiles(a, nt, tile) for a in (prev, nxt, occ)))
    return out.reshape(nt * tile)[:n]


def _live_kernel(nxt_ref, occ_ref, out_ref, acc_scr, *, tile: int):
    ii = pl.program_id(0)
    jj = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(jj == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    i_idx = ii * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), 0)                      # rows: i
    j_idx = jj * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), 1)                      # cols: j
    nxt_j = nxt_ref[0]                                   # [1, tile] int32
    occ_j = occ_ref[0]                                   # [1, tile] int32

    contrib = (
        (j_idx <= i_idx)
        & (nxt_j.reshape(1, tile) > i_idx)
        & (occ_j.reshape(1, tile) > 0)
    )
    acc_scr[...] += jnp.sum(contrib.astype(jnp.float32), axis=1,
                            keepdims=True)

    @pl.when(jj == nj - 1)
    def _finalize():
        out_ref[0] = acc_scr[...].reshape(tile).astype(jnp.int32)


def live_count_scan(nxt: jax.Array, occ: jax.Array, *, tile: int = 256,
                    interpret: bool = False) -> jax.Array:
    """nxt int32[n] occurrence links, occ int32[n] -> live counts int32[n].

    counts[i] = #{ j <= i : occ[j], nxt[j] > i } — the RO write-around
    live count (occupying tokens resident after access i assuming no
    eviction).  Same (i, j)-plane layout as ``cache_sim_scan`` with the
    interval test flipped to "covers i from the left".  The batch engine's
    RO guard computes these counts on the host (``batch_sim._live_counts``,
    an O(n) prefix sum); this kernel is on no serving path.
    """
    n = nxt.shape[0]
    nt = -(-n // tile)
    pad = nt * tile - n
    if pad:
        # padded j cols: never occupy, and nxt = -1 never covers a row
        nxt = jnp.pad(nxt, (0, pad), constant_values=-1)
        occ = jnp.pad(occ, (0, pad), constant_values=0)
    kernel = functools.partial(_live_kernel, tile=tile)
    out = pl.pallas_call(
        kernel,
        grid=(nt, nt),
        in_specs=[_row_spec(tile, _j_map), _row_spec(tile, _j_map)],
        out_specs=_row_spec(tile, _i_map),
        out_shape=jax.ShapeDtypeStruct((nt, 1, tile), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="live_count_scan",
    )(_row_tiles(nxt, nt, tile), _row_tiles(occ, nt, tile))
    return out.reshape(nt * tile)[:n]


def _levels_kernel(prev_ref, nxt_ref, occ_ref, cap1_ref, captot_ref,
                   l1_ref, un_ref, acc_scr, *, tile: int):
    ii = pl.program_id(0)
    jj = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(jj == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    prev_i = prev_ref[0]                                 # [1, tile] int32
    i_idx = ii * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), 0)
    j_idx = jj * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, tile), 1)
    nxt_j = nxt_ref[0]
    occ_j = occ_ref[0]

    contrib = (
        (j_idx > prev_i.reshape(tile, 1))
        & (j_idx < i_idx)
        & (nxt_j.reshape(1, tile) >= i_idx)
        & (occ_j.reshape(1, tile) > 0)
    )
    acc_scr[...] += jnp.sum(contrib.astype(jnp.float32), axis=1,
                            keepdims=True)

    @pl.when(jj == nj - 1)
    def _finalize():
        cnt = acc_scr[...].reshape(tile).astype(jnp.int32)
        hot = prev_i >= 0                                # cold rows -> 0
        l1_ref[0] = (hot & (cnt < cap1_ref[0])).astype(jnp.int32)
        un_ref[0] = (hot & (cnt < captot_ref[0])).astype(jnp.int32)


def cache_sim_levels_scan(prev: jax.Array, nxt: jax.Array, occ: jax.Array,
                          cap1: jax.Array, captot: jax.Array, *,
                          tile: int = 256, interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """Both-level residency masks in one launch (same counting layout).

    The accumulated count is compared in-kernel against the two per-access
    capacity thresholds (``cap1[i]`` = L1 blocks, ``captot[i]`` = L1 + L2
    blocks of the access's tenant): an access is an L1 hit iff
    ``SD < cap1`` and a hierarchy hit iff ``SD < captot`` — the exclusive
    two-level hierarchy's union is a single LRU stack (see batch_sim).
    Returns int32 0/1 masks ``(l1, union)``; cold rows are 0.
    """
    n = prev.shape[0]
    nt = -(-n // tile)
    pad = nt * tile - n
    if pad:
        prev = jnp.pad(prev, (0, pad), constant_values=n)
        nxt = jnp.pad(nxt, (0, pad), constant_values=-1)
        occ = jnp.pad(occ, (0, pad), constant_values=0)
        cap1 = jnp.pad(cap1, (0, pad), constant_values=0)
        captot = jnp.pad(captot, (0, pad), constant_values=0)
    shape3 = (nt, 1, tile)
    args = [_row_tiles(a, nt, tile) for a in (prev, nxt, occ, cap1, captot)]

    kernel = functools.partial(_levels_kernel, tile=tile)
    i_spec = _row_spec(tile, _i_map)
    j_spec = _row_spec(tile, _j_map)
    l1, un = pl.pallas_call(
        kernel,
        grid=(nt, nt),
        in_specs=[i_spec, j_spec, j_spec, i_spec, i_spec],
        out_specs=(i_spec, i_spec),
        out_shape=(jax.ShapeDtypeStruct(shape3, jnp.int32),
                   jax.ShapeDtypeStruct(shape3, jnp.int32)),
        scratch_shapes=[pltpu.VMEM((tile, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="cache_sim_levels_scan",
    )(*args)
    return (l1.reshape(nt * tile)[:n], un.reshape(nt * tile)[:n])
