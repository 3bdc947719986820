"""Pure-jnp oracles for the occupancy-masked stack-distance kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["cache_sim_ref", "cache_sim_levels_ref", "cache_sim_segments_ref",
           "cache_sim_segments_tree", "live_counts_ref"]


def cache_sim_ref(prev: jax.Array, nxt: jax.Array,
                  occ: jax.Array) -> jax.Array:
    """counts[i] = #{ j : prev[i] < j < i, occ[j], nxt[j] >= i } (dense O(n²)).

    With ``occ = 1`` everywhere this is the per-access LRU stack distance
    (the batch-sim hit oracle: resident ⟺ SD < capacity); restricting
    ``occ`` to reads gives the RO write-around live-distance.
    """
    n = prev.shape[0]
    i_idx = jnp.arange(n)[:, None]
    j_idx = jnp.arange(n)[None, :]
    contrib = ((j_idx > prev[:, None]) & (j_idx < i_idx)
               & (nxt[None, :] >= i_idx) & (occ[None, :] > 0))
    return jnp.sum(contrib, axis=1).astype(jnp.int32)


def cache_sim_segments_ref(prev: jax.Array, nxt: jax.Array, occ: jax.Array,
                           seg_width: int) -> jax.Array:
    """``cache_sim_ref`` on a segment-aligned padded tape (dense oracle).

    The tape is laid out in ``seg_width``-aligned blocks, one padded
    segment per block (``batch_sim.padded_segment_layout``), so no
    counting window ``(prev[i], i)`` of a hot access ever crosses a block
    — the ``j`` plane is masked to the query's own block and everything
    outside it is provably zero (severed links never reach past a segment,
    padding rows carry ``occ = 0``).  This is the jnp oracle for the
    width-restricted Pallas grid of ``cache_sim_segments_scan``, which
    simply never visits the masked-off (i, j) tiles.
    """
    n = prev.shape[0]
    i_idx = jnp.arange(n)[:, None]
    j_idx = jnp.arange(n)[None, :]
    same = (j_idx // seg_width) == (i_idx // seg_width)
    contrib = ((j_idx > prev[:, None]) & (j_idx < i_idx)
               & (nxt[None, :] >= i_idx) & (occ[None, :] > 0) & same)
    return jnp.sum(contrib, axis=1).astype(jnp.int32)


def cache_sim_segments_tree(prev: jax.Array, nxt: jax.Array, occ: jax.Array,
                            seg_width: int) -> jax.Array:
    """``cache_sim_segments_ref`` without the dense (i, j) plane.

    A merge-sort tree over the segment-aligned tape: for every level
    ``s = 1, 2, 4, ..., seg_width/2`` the occupying ``nxt`` values are
    sorted inside each aligned ``s``-block, and each query interval
    ``(prev[i], i)`` is peeled into its canonical aligned blocks (at most
    two per level), each contributing a single ``searchsorted`` count of
    ``nxt >= i``.  Exactly the counts of the dense oracle, but
    O(m log² w) work and O(m) memory — this is the off-TPU production
    route of the fused device window program
    (``core.device_pipeline`` via ``ops.segment_counts_device``), where
    the dense plane would be quadratic in the whole window tape.
    Non-occupying rows (pads) carry value 0, below every real query key.
    """
    m = prev.shape[0]
    if m == 0:
        return jnp.zeros(0, jnp.int32)
    levels = max(int(seg_width).bit_length() - 1, 0)    # seg_width = 2**L
    kdt = jnp.int32 if m * (m + 2) < 2**31 else jnp.int64
    big = m + 2                                         # value field size
    pos = jnp.arange(m, dtype=kdt)
    v = jnp.where(occ > 0, nxt.astype(kdt) + 1, 0)      # +1: query is i+1
    a = jnp.where(prev >= 0, prev.astype(kdt) + 1, pos)  # cold: empty [i, i)
    b = pos
    q = pos + 1
    cnt = jnp.zeros(m, kdt)
    for lev in range(levels):
        s = 1 << lev
        srt = v if s == 1 else jnp.sort(v.reshape(-1, s), axis=1).reshape(-1)
        keys = (pos // s) * big + srt                   # sorted composite
        # left peel: a sits on an odd s-block of its 2s-parent
        do = (a < b) & ((a // s) % 2 == 1)
        blk = a // s
        p = jnp.searchsorted(keys, blk * big + q, side="left")
        cnt = cnt + jnp.where(do, (blk + 1) * s - p.astype(kdt), 0)
        a = a + jnp.where(do, s, 0)
        # right peel
        do = (a < b) & ((b // s) % 2 == 1)
        b = b - jnp.where(do, s, 0)
        blk = b // s
        p = jnp.searchsorted(keys, blk * big + q, side="left")
        cnt = cnt + jnp.where(do, (blk + 1) * s - p.astype(kdt), 0)
    return cnt.astype(jnp.int32)


def live_counts_ref(nxt: jax.Array, occ: jax.Array) -> jax.Array:
    """counts[i] = #{ j <= i : occ[j], nxt[j] > i } (dense O(n²) oracle).

    The RO write-around *live count*: how many occupying tokens (reads, or
    warm pseudo-reads) are resident after access ``i`` assuming no
    eviction — the batch engine's RO no-eviction guard (computed on the
    host there, ``batch_sim._live_counts``).  With ``occ`` restricted to
    warm-L2 pseudo positions it counts the still-untouched warm-L2 blocks
    (the ``U2`` term of the per-level guard).
    """
    n = nxt.shape[0]
    i_idx = jnp.arange(n)[:, None]
    j_idx = jnp.arange(n)[None, :]
    contrib = ((j_idx <= i_idx) & (nxt[None, :] > i_idx)
               & (occ[None, :] > 0))
    return jnp.sum(contrib, axis=1).astype(jnp.int32)


def cache_sim_levels_ref(prev: jax.Array, nxt: jax.Array, occ: jax.Array,
                         cap1: jax.Array, captot: jax.Array
                         ) -> tuple[jax.Array, jax.Array]:
    """Both-level residency masks from one counting pass (jnp oracle).

    For the exclusive two-level hierarchy the union of the levels is a
    single LRU stack whose top ``cap1[i]`` entries are L1, so

        l1[i]    = prev[i] >= 0  and  SD(i) < cap1[i]
        union[i] = prev[i] >= 0  and  SD(i) < captot[i]

    (an access is an L2 hit iff ``union & ~l1``).  ``cap1``/``captot`` are
    per-access so one tape launch covers tenants with different quotas.
    """
    counts = cache_sim_ref(prev, nxt, occ)
    hot = prev >= 0
    return ((hot & (counts < cap1)).astype(jnp.int32),
            (hot & (counts < captot)).astype(jnp.int32))
