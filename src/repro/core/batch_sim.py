"""Vectorized multi-tenant window replay — the batch simulation engine.

Replaces the per-access Python loop in ``simulator.simulate`` with array
programs over occurrence links, for a whole Δt window of **all tenants at
once**.  The engine is *exact*: it reproduces ``simulate()``'s hits,
write_hits, cache_writes, flush charges, total latency and the final LRU
state (the interpreter remains the oracle, property-tested in
``tests/test_batch_sim.py``).

Hit-oracle math
===============

Let ``prev[i]``/``nxt[j]`` be the previous/next occurrence links of the
access stream (``trace.prev_next_occurrence``).  Define the *stack distance*

    SD(i) = #{ j : prev[i] < j < i,  nxt[j] >= i }

— the number of distinct addresses touched strictly between an access and
its previous occurrence (each contributes exactly one ``j``, its last
occurrence inside the window).  For an LRU partition of ``C`` blocks that
**allocates on every access** (the WB and WT policies: reads install on
miss, writes install or touch), Mattson stack inclusion gives the exact
oracle:

    access i is resident  ⟺  prev[i] >= 0  and  SD(i) < C.

``SD`` is computed without any per-access loop as ``SD(i) = F(i) − G(i)``:

  * ``F(i) = #{ j < i : nxt[j] >= i }`` is the number of occurrence
    intervals ``(j, nxt[j]]`` covering ``i`` — an O(n) difference-array
    cumsum (it equals the number of distinct addresses seen before ``i``).
  * ``G(i) = #{ j <= prev[i] : nxt[j] >= i }``.  Because ``nxt[prev[i]] ==
    i``, the queries are the points themselves and ``G`` is a dominance
    count over the point set ``(j, nxt[j])``; it is evaluated for *all*
    accesses at once with a bottom-up merge tree (log n rounds of
    block-sort + ``searchsorted``), O(n log² n) in vectorized numpy.

Write-policy effects
====================

WB/WT share the oracle above (identical stack content; they differ only in
latency/endurance accounting).  RO (write-around) breaks reuse chains at
writes — a write invalidates the cached copy, so a read whose previous
occurrence is a write is always a miss — and writes never install.  The
trace transform is: gate residency on ``is_read[prev[i]]`` and restrict
occupancy to reads.

**RO caveat (why there is a guard):** invalidation *frees the slot
immediately*, and LRU-with-deletion loses the stack property once a
capacity eviction has occurred.  Counterexample at C=2 for trace
``r(a) r(b) r(c) w(b) w(c) r(a)``: the real cache evicted ``a`` at
``r(c)``, so the final read misses, but after the two invalidations only
zero live blocks separate ``r(a)`` from its reuse, so any distance oracle
says hit.  The engine therefore computes the *live count*
``L(t) = #{ j <= t : is_read[j], nxt[j] > t }`` (an O(n) cumsum on the
host, ``_live_counts``); when
``max L <= C`` the cache never fills, no eviction can occur, and
``resident ⟺ live`` is exact — otherwise that tenant's window is replayed
by the O(n) *eviction-token* loop (below).  WB/WT never need the guard
(no deletions).

Endurance / latency / flush accounting are pure array reductions:
per-address *dirty chains* (segmented cumulative OR over residency
periods, grouped by address), suffix distinct-counts for end-of-trace
evictions, and ``bincount`` per tenant.  Warm cross-window state is
handled exactly by prepending the cache content as pseudo-read accesses
(LRU→MRU order) carrying their dirty flags; the prefix is excluded from
the reported stats.

Two-level hierarchy
===================

For the exclusive ETICA-style hierarchy (see ``simulator``): every touch
moves the block to the global MRU and every L1 victim is demoted to L2's
MRU, so the *union* of both levels is one LRU stack of ``C1 + C2`` blocks
whose top ``C1`` entries are L1 (after ``rebalance_levels`` restored the
"L1 full or L2 empty" invariant at window start).  The same ``SD`` array
therefore classifies each access against **two** thresholds in one pass:

    L1 hit  ⟺  SD < C1        L2 hit  ⟺  C1 <= SD < C1 + C2.

Warm state prepends L2 (LRU→MRU) then L1 (LRU→MRU) — the union stack.
Demotions (= L2 cache writes) are counted in closed form per tenant:
``installs_into_L1 − (final_L1 − initial_L1)`` where ``final_L1 =
min(distinct_addrs, C1)``.  Per-level write policies: ``policy2 != WB``
keeps L2 *clean* — dirty victims flush at demotion, so the dirty chains
segment at L1 exits (``SD >= C1``) instead of union exits, and the flush
eviction test uses the ``C1`` threshold.  Final per-level LRU state is the
union survivor stack split at depth ``C1``.  RO (write-around) keeps the
live-count guard, compared per level (``L1-live = live − untouched warm-L2
blocks``).

Two-level RO under eviction pressure (the token formulation, per level)
=======================================================================

When a two-level RO window fails the guard the stack property is gone
(invalidation leaves a *hole* in L1 that the next install fills without
demoting), but the eviction-token formulation generalizes: every read
position is a token, and each token additionally carries a **level**.
Three facts make the replay O(n) with two forward pointers:

  * *Recency is birth order, per level.*  A touch always creates a new
    token (hit = renewal, promotion = rebirth in L1), so within each
    level the LRU order is token-position order.
  * *Demotion order is position order.*  The demoted victim is always
    L1's minimum live position, which is non-decreasing over time, so L2's
    arrival order (warm L2 first, then demotions) is ascending position.
  * *Every live L1 position exceeds every live L2 position.*  After a
    demotion of position ``q`` all remaining L1 tokens sit above ``q``,
    and later births sit higher still — so the L2 victim scan never has
    to check levels (the lowest live token *is* the L2 victim), and the
    L1 victim scan (``_ro_token_replay_levels``'s ``b1``) just skips
    demoted tokens.

Invalidation frees a slot in whichever level holds the token (a hole:
the next install does *not* demote), an L2 read hit retires its token and
rebirths it in L1 (demoting L1's victim only when L1 is actually full),
and a demotion *transfers* the token to L2 — shortening its death time
only if L2 then overflows (the final eviction, flushed when dirty; with a
clean ``policy2`` the flush happens at the demotion boundary instead and
the token's dirty flag clears).  Afterwards every residency question is
vectorized exactly as in the single-level case, plus ``lvl[prev]`` splits
hits by level.  Both token loops run on the host on every backend; the
interpreter remains only for genuinely degenerate windows (empty two-level
windows, or warm L2 behind a dead ``C2 <= 0`` level), counted by
``SimResult.fallback``.  A step is a scalar pointer chase with no parallel
width: as a ``lax.fori_loop`` it took 4.97 µs of device time on a TPU v5e,
against 0.21 µs in the host loop.

On TPU the ``SD`` counting runs on-accelerator via the
``repro.kernels.cache_sim`` Pallas kernel (the occupancy-masked
generalization of ``urd_scan``); on CPU the merge-tree host path is used.
"""
from __future__ import annotations

import itertools

import numpy as np

from repro.core.reuse_distance import RDResult
from repro.core.simulator import (LRUCache, SimResult, rebalance_levels,
                                  simulate)
from repro.core.stage_profile import StageProfile, _pstep
from repro.core.trace import Trace, prev_next_occurrence
from repro.core.write_policy import WritePolicy

__all__ = [
    "count_prev_ge",
    "count_prev_ge_padded",
    "padded_segment_layout",
    "stack_distances",
    "reuse_distances_fast",
    "simulate_batch",
    "simulate_many",
]

# every padded segment width is a power of two and a multiple of the dense
# base-level block, so the base pass never spans two segments (64 trades a
# little dense work for two fewer sort-merge levels)
_PAD_MIN = 64
# single-tape ``count_prev_ge`` switches to the width-bounded sort-merge
# levels once the tape is long enough that searchsorted's global binary
# searches (log n probes over the whole tape per element) dominate
_SORT_MERGE_MIN = 1 << 15


# --------------------------------------------------------------- primitives
def count_prev_ge(y: np.ndarray) -> np.ndarray:
    """cnt[q] = #{ j < q : y[j] >= y[q] }, vectorized merge-tree counting.

    Bottom-up merge levels: at half-size ``s`` every element in the right
    half of a 2s-block counts the elements >= it in the left half — by
    direct broadcast for narrow blocks, by block-local ``searchsorted``
    (composite keys while blocks are many, a python loop once they are
    few) for wide ones.  O(n log² n) array work, int32 throughout, no
    per-element Python loop.  Requires ``0 <= y < 2**31 - 2``.

    Long tapes take the sort-merge level engine instead (the degenerate
    one-segment case of ``count_prev_ge_padded``): same counts, but each
    merge level is one SIMD ``np.sort`` of packed (value, side, position)
    keys instead of a global-array ``searchsorted``.
    """
    m = int(y.shape[0])
    out = np.zeros(m, dtype=np.int64)
    if m <= 1:
        return out
    if m >= _SORT_MERGE_MIN:
        w = _next_pow2(m)
        yp = np.zeros(w, dtype=np.int64)
        yp[:m] = np.asarray(y, dtype=np.int64) + 1   # pads sort below all
        return count_prev_ge_padded(
            yp, np.array([w], dtype=np.int64))[:m].astype(np.int64)
    y = y.astype(np.int32)
    base = np.int64(int(y.max()) + 2)

    # base level: all within-16-block pairs in one dense masked pass
    B0 = 16
    ms0 = -(-m // B0) * B0
    yp0 = np.full(ms0, -1, dtype=np.int32)
    yp0[:m] = y
    blk = yp0.reshape(-1, B0)
    lower = np.arange(B0)[:, None] < np.arange(B0)[None, :]   # j < q
    cnt0 = ((blk[:, :, None] >= blk[:, None, :]) & lower[None]) \
        .sum(axis=1, dtype=np.int64).reshape(-1)
    out[:] = cnt0[:m]

    idx = np.arange(m, dtype=np.int64)
    s, ell = B0, 4
    while s < m:
        width = 2 * s
        ms = -(-m // width) * width              # pad only to this level
        yp = np.full(ms, -1, dtype=np.int32)     # pad < every real value
        yp[:m] = y
        blocks = yp.reshape(-1, width)
        lefts = blocks[:, :s]                                    # [nb, s]
        rights = blocks[:, s:]                                   # [nb, s]
        nb = lefts.shape[0]
        lefts_s = np.sort(lefts, axis=1)
        if nb <= 16:
            n_lt = np.concatenate([
                np.searchsorted(lefts_s[b], rights[b])
                for b in range(nb)])
        else:
            if nb * int(base) < 2**31 - 1:       # int32 composite keys
                row = (np.arange(nb, dtype=np.int32)
                       * np.int32(base))[:, None]
                keys = (lefts_s + np.int32(1) + row).ravel()
                qkeys = (rights + np.int32(1) + row).ravel()
            else:
                row = (np.arange(nb, dtype=np.int64) * base)[:, None]
                keys = (lefts_s.astype(np.int64) + 1 + row).ravel()
                qkeys = (rights.astype(np.int64) + 1 + row).ravel()
            n_lt = (np.searchsorted(keys, qkeys)
                    - (np.arange(nb, dtype=np.int64) * s).repeat(s))
        # queries of this level = positions with bit `ell` set (ascending;
        # pads sit only at the tail, so a head-slice aligns them)
        sel = idx[(idx >> ell) & 1 == 1]
        out[sel] += s - n_lt.reshape(-1)[:sel.size]
        s, ell = width, ell + 1
    return out


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def padded_segment_layout(bounds: np.ndarray):
    """Segment-aligned power-of-two padding for a multi-segment tape.

    Each non-empty segment of ``bounds`` is padded to the next power of two
    (min ``_PAD_MIN``) and the padded segments are laid out in descending
    width order — prefix sums of descending powers of two are multiples of
    every following width, so **every segment starts at a multiple of its
    own padded width**.  A merge tree that stops at each segment's width
    therefore never builds a block spanning two segments.

    Returns ``(src, tpos, base_src, base_pad, widths, total)``:

      src      int[k]    original tape positions of the real entries,
                         grouped by segment in padded-layout order — or
                         ``None`` when the layout keeps the original
                         segment order AND the tape has no empty segments,
                         i.e. ``src`` would be ``arange`` (callers skip
                         their gathers)
      tpos     int[k]    their positions on the padded tape
      base_src int[k]    per-entry original segment start
      base_pad int[k]    per-entry padded segment start
      widths   int64[g]  padded width per non-empty segment (descending)
      total    int       padded tape length (``widths.sum()``)
      starts   int64[g]  original tape start per non-empty segment, in
                         the same (descending-width) layout order

    Index arrays are int32 when everything fits (half the gather traffic).
    """
    bounds = np.asarray(bounds, dtype=np.int64)
    lens = np.diff(bounds)
    act = np.flatnonzero(lens > 0)
    if act.size == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z, z, z, z, 0, z
    L = lens[act]
    W = (1 << np.ceil(np.log2(L)).astype(np.int64))
    W = np.where(W < L, W * 2, W)                # guard float rounding
    W = np.maximum(W, _PAD_MIN)
    order = np.argsort(-W, kind="stable")        # descending, ties stable
    Ws, Ls, segs = W[order], L[order], act[order]
    total = int(Ws.sum())
    row_base = np.concatenate([[0], np.cumsum(Ws)[:-1]]).astype(np.int64)
    csl = np.concatenate([[0], np.cumsum(Ls)[:-1]]).astype(np.int64)
    k = int(Ls.sum())
    idt = np.int32 if max(total, int(bounds[-1])) < 2**31 else np.int64
    loc = np.arange(k, dtype=idt) - np.repeat(csl.astype(idt), Ls)
    base_src = np.repeat(bounds[segs].astype(idt), Ls)
    base_pad = np.repeat(row_base.astype(idt), Ls)
    identity = (act.size == lens.size and int(bounds[0]) == 0
                and bool(np.all(W[:-1] >= W[1:])))
    src = None if identity else base_src + loc
    return src, base_pad + loc, base_src, base_pad, Ws, total, bounds[segs]


def padded_tape_links(prev: np.ndarray, nxt: np.ndarray, layout
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Scatter severed/clamped occurrence links onto the padded tape.

    ``prev``/``nxt`` live on the original multi-segment tape
    (``monitor._segment_links`` semantics); ``layout`` is the tape's
    ``padded_segment_layout``.  Returns ``(gprev, gnxt, gocc)`` on the
    padded tape: real entries carry their links shifted into padded
    coordinates, padding rows the cold/non-occupying sentinels
    (``gprev = -1``, self-``gnxt``, ``gocc = 0``) whose contributions to
    any in-segment dominance count are identically zero.  This is the one
    ingest format shared by the per-width accelerator launches
    (``kernels.cache_sim.ops.stack_distances_segments_accel``) and the
    fused device window program (``core.device_pipeline``).
    """
    src, tpos, base_src, base_pad, widths, total, _ = layout
    n = prev.shape[0]
    if src is None:                              # layout kept tape order
        src = np.arange(n, dtype=tpos.dtype if tpos.size else np.int64)
    shift = (tpos - src).astype(np.int64)
    gprev = np.full(total, -1, dtype=np.int64)
    gprev[tpos] = np.where(prev[src] >= 0, shift + prev[src], -1)
    gnxt = np.arange(total, dtype=np.int64)
    gnxt[tpos] = base_pad.astype(np.int64) + (nxt[src] - base_src)
    gocc = np.zeros(total, dtype=np.int32)
    gocc[tpos] = 1
    return gprev, gnxt, gocc


def count_prev_ge_padded(y: np.ndarray, seg_widths: np.ndarray) -> np.ndarray:
    """Width-bounded merge-tree counting on a padded, segment-aligned tape.

    ``y.size == seg_widths.sum()``; widths are powers of two
    ``>= _PAD_MIN`` in descending order and every width-W segment starts
    at a multiple of W (``padded_segment_layout``).  Returns, per position q,
    ``#{ j < q, same segment : y[j] >= y[q] }``: the merge recursion for a
    segment stops at its own padded width, so no merge level ever spans two
    segments and the deep global levels of the unpadded tree (whose
    contributions to in-segment queries provably cancel — see
    ``repro.core.monitor``) are simply never built.  Padding entries must
    carry ``y = 0`` with every real entry ``>= 1``; a pad then sorts below
    every real query and contributes nothing to its >=-count.

    Each level is one SIMD ``np.sort`` over packed
    ``(value << pb+1) | (is_left << pb) | local_position`` keys: after the
    sort, the k-th right-half element of a block at merged position p has
    exactly ``p - k`` strictly-smaller left elements (equal-valued lefts
    pack *above* rights, so ties count toward >=), and its own local
    position rides along in the low bits for the scatter back — no
    ``argsort`` and no ``searchsorted`` anywhere.  Counts are returned as
    int32 (they never exceed the segment width); tapes must be shorter
    than 2**31.
    """
    m = int(y.shape[0])
    if m == 0:
        return np.zeros(0, dtype=np.int32)
    seg_widths = np.asarray(seg_widths, dtype=np.int64)
    wmax = int(seg_widths[0])
    y = np.asarray(y)
    ymax = int(y.max(initial=0))
    vb = max(ymax.bit_length(), 1)                    # value bits
    pb = (wmax - 1).bit_length()                      # local-position bits
    kdt = np.int32 if vb + pb + 2 <= 32 else np.int64
    yk = y.astype(kdt, copy=False)
    # counts never exceed the segment width, and every index fits int32:
    # the whole pass runs in int32 to halve the memory traffic
    out = np.zeros(m, dtype=np.int32)
    # base level: dense all-pairs inside _PAD_MIN-blocks (every width
    # divides into whole blocks, so the dense pass never spans segments),
    # column-transposed so each of the B0(B0-1)/2 compares is contiguous
    blk_t = np.ascontiguousarray(yk.reshape(-1, _PAD_MIN).T)
    cnt_t = np.zeros(blk_t.shape, dtype=np.int32)
    for q in range(1, _PAD_MIN):
        cq, bq = cnt_t[q], blk_t[q]
        for j in range(q):
            cq += blk_t[j] >= bq
    out[:] = cnt_t.T.ravel()
    if wmax <= _PAD_MIN:
        return out
    ysh = yk << (pb + 1)                              # value field, reused
    csw = np.cumsum(seg_widths)
    iota = np.arange(m // 2, dtype=np.int32)
    kbuf = np.empty(m, dtype=kdt)                     # per-level scratch:
    abuf = np.empty(m, dtype=kdt)                     # reused allocations
    mbuf = np.empty(m, dtype=bool)
    s = _PAD_MIN
    while s < wmax:
        w = 2 * s
        # segments narrower than 2s have finished merging; the live
        # prefix of the descending-width layout is exactly width >= 2s
        n_seg = int(np.searchsorted(-seg_widths, -w, side="right"))
        mlvl = int(csw[n_seg - 1])
        nb = mlvl // w
        lpos = np.arange(w, dtype=kdt)
        combo = ((lpos < s).astype(kdt) << pb) | lpos
        kv = kbuf[:mlvl].reshape(nb, w)
        np.bitwise_or(ysh[:mlvl].reshape(nb, w), combo[None, :], out=kv)
        kv.sort(axis=1)                               # in-place SIMD sort
        M = kbuf[:mlvl]
        np.bitwise_and(M, kdt(1 << pb), out=abuf[:mlvl])
        np.equal(abuf[:mlvl], 0, out=mbuf[:mlvl])
        pf = np.flatnonzero(mbuf[:mlvl]).astype(np.int32)
        n_ge = np.int32(s) - ((pf & np.int32(w - 1))
                              - (iota[: pf.size] & np.int32(s - 1)))
        tgt = (pf & np.int32(-w)) + (M[pf] & np.int32((1 << pb) - 1))
        out[tgt] += n_ge
        s = w
    return out


def _coverage_counts(nxt: np.ndarray) -> np.ndarray:
    """F[i] = #{ j < i : nxt[j] >= i } via a difference array, O(n)."""
    n = nxt.shape[0]
    d = -np.bincount(np.minimum(nxt, n) + 1,
                     minlength=n + 2)[:n + 2]    # interval ends after nxt[j]
    d[1:n + 1] += 1                              # starts at j+1
    return np.cumsum(d)[:n + 1]


def _stack_distances_padded(prev: np.ndarray, nxt: np.ndarray,
                            bounds: np.ndarray,
                            layout=None) -> np.ndarray:
    """Exact SD for a multi-segment tape via the padded pow2 layout.

    One width-bounded counting pass covers every segment at once: real
    entries carry their segment-local ``nxt`` (>= 1), padding entries the
    sentinel ``y = 0`` / empty coverage interval, so the padded tape is
    bit-identical to running each segment alone (property-tested in
    ``tests/test_monitor_padding.py``).
    """
    n = prev.shape[0]
    sd = np.full(n, -1, dtype=np.int64)
    src, tpos, base_src, base_pad, widths, total, _ = \
        layout if layout is not None else padded_segment_layout(bounds)
    if tpos.size == 0:
        return sd
    # F needs no padded tape: on the severed/clamped original tape a
    # cross-segment interval can only reach a segment's *first* position
    # (which is cold), so the global coverage count equals the
    # segment-local one at every hot access — the same cancellation
    # argument as the merge tree's (see repro.core.monitor)
    F = _coverage_counts(nxt)
    gy = np.zeros(total, dtype=np.int32 if total < 2**31 else np.int64)
    if src is None:                              # layout kept tape order
        gy[tpos] = nxt - base_src                # local nxt in [1, L]
        cnt = count_prev_ge_padded(gy, widths)
        sh = np.flatnonzero(prev >= 0)
        gprev = (tpos[sh] - sh).astype(np.int64) + prev[sh]
        sd[sh] = F[sh] - (cnt[gprev] + 1)
        return sd
    gy[tpos] = nxt[src] - base_src               # assignment casts in place
    cnt = count_prev_ge_padded(gy, widths)
    pl = prev[src]
    hot = pl >= 0
    gprev = (tpos[hot] - src[hot]).astype(np.int64) + pl[hot]
    sh = src[hot]                                # same in-segment offset
    sd[sh] = F[sh] - (cnt[gprev] + 1)
    return sd


def _stack_distances_host(prev: np.ndarray, nxt: np.ndarray,
                          bounds: np.ndarray | None = None,
                          layout=None) -> np.ndarray:
    """Exact SD per access (occupancy = every access); -1 for cold.

    ``bounds`` (optional) splits the tape into independent contiguous
    blocks (one per tenant: links never cross).  Multi-segment tapes go
    through the segment-aligned padded layout — one width-bounded counting
    pass for all tenants, no per-segment Python loop (see
    ``padded_segment_layout`` / ``count_prev_ge_padded``); callers that
    already hold the tape's ``padded_segment_layout`` pass it as
    ``layout`` to avoid recomputing it.
    """
    n = prev.shape[0]
    sd = np.full(n, -1, dtype=np.int64)
    if n == 0:
        return sd
    if bounds is not None and len(bounds) > 2:
        return _stack_distances_padded(prev, nxt, bounds, layout)
    s, e = (0, n) if bounds is None else (int(bounds[0]), int(bounds[-1]))
    if e <= s:
        return sd
    pl = prev[s:e]
    nl = nxt[s:e] - s
    F = _coverage_counts(nl)
    cnt = count_prev_ge(nl)
    idx = np.flatnonzero(pl >= 0)                # links never cross blocks
    sd[s + idx] = F[idx] - (cnt[pl[idx] - s] + 1)
    return sd


def _accel_default() -> bool:
    """True when SD counting should run in the Pallas kernel (TPU host)."""
    global _ACCEL
    if _ACCEL is None:
        import jax
        _ACCEL = jax.default_backend() == "tpu"
    return _ACCEL


_ACCEL: bool | None = None


def stack_distances(trace: Trace, backend: str = "auto") -> np.ndarray:
    """Per-access LRU stack distances (TRD samples at every re-touch).

    backend: "host" (numpy merge tree), "accel" (cache_sim Pallas kernel /
    jnp oracle), or "auto" (kernel on TPU, host otherwise).
    """
    prev, nxt = prev_next_occurrence(trace.addrs)
    if backend == "auto":
        backend = "accel" if _accel_default() else "host"
    if backend == "accel":
        from repro.kernels.cache_sim.ops import stack_distances_accel
        return stack_distances_accel(prev, nxt)
    return _stack_distances_host(prev, nxt)


def reuse_distances_fast(trace: Trace, kind: str = "urd",
                         backend: str = "auto") -> RDResult:
    """Drop-in for ``reuse_distances`` built on the vectorized SD engine.

    Same output, no per-access Python loop: the production Analyzer path.
    """
    if kind not in ("trd", "urd"):
        raise ValueError(f"kind must be 'trd' or 'urd', got {kind!r}")
    sd = stack_distances(trace, backend)
    out = sd.copy()
    if kind == "urd":
        out[~trace.is_read] = -1
    return RDResult(out, kind)


# ------------------------------------------------------------ batch replay
def _live_counts(nxt: np.ndarray, occ_at: np.ndarray) -> np.ndarray:
    """``L[i] = #{j <= i : j in occ_at, nxt[j] > i}``: the blocks live after
    access ``i`` assuming no eviction, for the RO guard.

    Each occupying token is the interval ``[j, nxt[j])``: +1 at its birth,
    -1 at its death, prefix-summed (``nxt[j] > j``, so a token dead by
    ``i`` was born by ``i``).
    """
    m = nxt.shape[0]
    d = (np.bincount(occ_at, minlength=m + 1)
         - np.bincount(nxt[occ_at], minlength=m + 1))
    return np.cumsum(d[:m])


def _ro_token_replay(is_read_blk: np.ndarray, prev_blk: np.ndarray,
                     nxt_blk: np.ndarray, force_blk: np.ndarray,
                     cap: int) -> tuple[np.ndarray, np.ndarray, int]:
    """Exact RO (write-around) replay under capacity pressure, O(n).

    Token formulation: every read position ``j`` is a cache slot "token"
    alive on ``(j, nxt[j])`` — independent of hit/miss, because a read hit
    retires the previous token and births a new one simultaneously (net
    resident count 0) while a miss is a pure birth and a write-hit a pure
    death.  Evictions only *shorten* a token's death time, and the LRU
    victim is always the minimum live token, which is non-decreasing over
    time — so a single forward bottom pointer suffices and the whole
    replay is one O(n) integer pass with no dictionary.  Afterwards every
    residency question is vectorized: access ``i`` hit ⟺ its previous
    occurrence ``p`` was a read whose token survived to its natural death
    (``death[p] == i``).

    Returns (death, dirty, flushes): ``death[j]`` = when token j left the
    cache (== ``nxt_blk[j]`` iff never evicted), ``dirty[j]`` = the dirty
    flag the token carried (inherited from warm-prefix blocks through hit
    chains; RO installs are clean), ``flushes`` = dirty evictions.
    """
    n = int(is_read_blk.shape[0])
    rd = is_read_blk.tolist()
    pv = prev_blk.tolist()
    death = nxt_blk.tolist()
    dirty = force_blk.tolist()
    flushes = 0
    resident = 0
    b = 0                                        # oldest-resident candidate
    for t in range(n):
        p = pv[t]
        if rd[t]:
            if p >= 0 and rd[p] and death[p] == t:
                dirty[t] = dirty[p]              # hit: token renewal
            else:
                resident += 1                    # miss: install clean
                if resident > cap:
                    while not rd[b] or death[b] <= t:
                        b += 1
                    death[b] = t                 # evict oldest resident
                    if dirty[b]:
                        flushes += 1
                    resident -= 1
        elif p >= 0 and rd[p] and death[p] == t:
            resident -= 1                        # write-hit: invalidate
    return (np.asarray(death, dtype=np.int64),
            np.asarray(dirty, dtype=bool), flushes)


def _ro_token_replay_levels(is_read_blk: np.ndarray, prev_blk: np.ndarray,
                            nxt_blk: np.ndarray, force_blk: np.ndarray,
                            cap1: int, cap2: int, l2_end: int,
                            clean2: bool
                            ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                       int, int]:
    """Exact two-level RO replay under eviction pressure, O(n).

    The eviction-token loop generalized to the exclusive demote/promote
    hierarchy (see the module docstring): each token carries a *level*,
    recency within each level is token-position order, demotions transfer
    the L1 victim (minimum live L1 position — non-decreasing, so a forward
    pointer ``b1`` that skips demoted tokens suffices) into L2, and an L2
    overflow evicts the globally-lowest live token (``b2``; always L2,
    because live L1 positions provably sit above all live L2 positions).
    Invalidation frees a slot in whichever level holds the token; the next
    install fills the hole without demoting.  ``clean2`` flushes dirty
    victims at the demotion boundary (entering L2 clean) instead of at the
    final L2 eviction.

    Positions below ``l2_end`` are the warm-L2 pseudo-reads: their tokens
    are born directly in L2.  Warm-L1 pseudo-reads need no special case —
    they are read misses installing into a never-overflowing L1.

    Returns ``(death, dirty, lvl, flushes, demotions)``: ``death[j]`` =
    when token j left the hierarchy entirely (== ``nxt_blk[j]`` iff never
    evicted from L2), ``dirty[j]`` = the flag the token carried, ``lvl[j]``
    = the level it occupied when it died (splits hits per level),
    ``flushes`` = dirty evictions/demotion-flushes, ``demotions`` = L2
    cache writes.
    """
    n = int(is_read_blk.shape[0])
    rd = is_read_blk.tolist()
    pv = prev_blk.tolist()
    death = nxt_blk.tolist()
    dirty = force_blk.tolist()
    lvl = [1] * n
    flushes = demotions = 0
    res1 = res2 = 0
    b1 = b2 = 0                                  # per-level victim candidates
    for t in range(n):
        if t < l2_end:
            lvl[t] = 2                           # warm-L2 token: born in L2
            res2 += 1
            continue
        p = pv[t]
        if rd[t]:
            if p >= 0 and rd[p] and death[p] == t:
                dirty[t] = dirty[p]              # hit: token renewal
                if lvl[p] == 1:
                    continue                     # L1 hit: occupancy unchanged
                res2 -= 1                        # L2 hit: promote out of L2
            res1 += 1                            # install / rebirth into L1
            if res1 > cap1:
                while not rd[b1] or death[b1] <= t or lvl[b1] == 2:
                    b1 += 1                      # min live L1 token
                lvl[b1] = 2                      # demote into L2's MRU
                if clean2 and dirty[b1]:
                    flushes += 1                 # flush at the demotion
                    dirty[b1] = False
                res1 -= 1
                res2 += 1
                demotions += 1
                if res2 > cap2:
                    while not rd[b2] or death[b2] <= t:
                        b2 += 1                  # min live token == L2 victim
                    death[b2] = t                # evicted for good
                    if dirty[b2]:
                        flushes += 1
                    res2 -= 1
        elif p >= 0 and rd[p] and death[p] == t:
            if lvl[p] == 1:                      # write-hit: invalidate the
                res1 -= 1                        # holding level (a hole)
            else:
                res2 -= 1
    return (np.asarray(death, dtype=np.int64),
            np.asarray(dirty, dtype=bool),
            np.asarray(lvl, dtype=np.int8), flushes, demotions)


def _segment_heads(sorted_vals: np.ndarray) -> np.ndarray:
    head = np.ones(sorted_vals.shape[0], dtype=bool)
    head[1:] = sorted_vals[1:] != sorted_vals[:-1]
    return head


def simulate_many(traces: list[Trace], capacities=None, policies=None, *,
                  t_fast: float = 1.0, t_slow: float = 20.0,
                  t_write_bypass: float | None = None,
                  flush_cost: float = 0.0,
                  caches: list[LRUCache | None] | None = None,
                  capacities2=None, policies2=None,
                  caches2: list[LRUCache | None] | None = None,
                  t_fast2: float | None = None,
                  return_window_rd: bool = False,
                  profile: StageProfile | None = None):
    """Replay one window for every tenant at once (exact, vectorized).

    Mirrors ``simulate()`` per tenant: when ``caches[k]`` is given its
    capacity wins over ``capacities[k]``, warm content seeds the replay,
    and the cache object is left in the exact final LRU state.  The same
    holds per level: ``capacities2``/``caches2``/``policies2`` describe the
    second hierarchy level (see the module docstring — both levels are
    classified against the same stack-distance array).  RO tenants whose
    window fails the no-eviction guard (see module docstring) are replayed
    with the O(n) eviction-token loop — single-level or the per-level
    two-level generalization — so write-around windows under pressure
    never leave the vectorized path.  The per-access interpreter remains
    only for genuinely degenerate windows (an empty window with two
    levels, or warm L2 content behind a dead ``C2 <= 0`` level); those are
    flagged with ``SimResult.fallback = 1`` so deployments can measure how
    often it happens (``ECICacheManager`` aggregates the counter).

    With ``return_window_rd=True`` also returns, per tenant, the TRD
    sample array of the *window* trace (``reuse_distances(trace, "trd")``,
    -1 at cold accesses) — the tape's stack distances restricted to
    window-internal reuses, so the Analyzer gets its reuse distances for
    free from the same counting pass; ``None`` where the tenant was not
    replayed on the tape (empty window or zero capacity).

    ``profile`` tiles the call with stages: ``replay_tape`` (the tape,
    its occurrence links and warm prefixes), ``replay_live_counts`` and
    ``replay_ro_tokens`` (the RO guard and the token replays, where a
    tenant is RO), ``replay_residency`` (the counting pass and its fetch)
    and ``replay_account`` (hits, latency, dirty flags, final state).  It
    counts a sync per device program whose outputs the host waits on,
    and under ``ro_tokens`` the length of each tape a token replay walks.
    """
    _pstep(profile, "replay_tape")
    if t_write_bypass is None:
        t_write_bypass = 1.2 * t_fast
    if t_fast2 is None:
        t_fast2 = 3.0 * t_fast
    T = len(traces)
    caches = caches if caches is not None else [None] * T
    caches2 = caches2 if caches2 is not None else [None] * T
    if policies is None:
        policies = [WritePolicy.WB] * T
    if policies2 is None:
        policies2 = [WritePolicy.WB] * T
    results: list[SimResult | None] = [None] * T

    def run_interp(k: int) -> SimResult:
        """Exact per-tenant fallback through the stateful interpreter."""
        return simulate(traces[k], caps1[k], policies[k], t_fast, t_slow,
                        t_write_bypass=t_write_bypass, flush_cost=flush_cost,
                        cache=caches[k], capacity2=caps2[k],
                        policy2=policies2[k], t_fast2=t_fast2,
                        cache2=caches2[k])

    vec: list[int] = []
    caps1 = [0] * T
    caps2 = [0] * T
    for k in range(T):
        tr, c, c2 = traces[k], caches[k], caches2[k]
        cap = int(c.capacity if c is not None else capacities[k])
        cap2 = int(c2.capacity if c2 is not None else
                   (capacities2[k] if capacities2 is not None else 0))
        caps1[k], caps2[k] = cap, cap2
        pol = policies[k]
        two = cap2 > 0 or (c2 is not None and len(c2) > 0)
        n = len(tr)
        if n == 0:
            if two:                  # rebalance/flush side effects still run
                results[k] = run_interp(k)
                results[k].fallback = 1          # degenerate: telemetry
            else:
                results[k] = SimResult(capacity=cap, policy=pol.value)
            continue
        if cap <= 0 and not two:
            r = SimResult(capacity=cap, policy=pol.value)
            r.reads = int(np.sum(tr.is_read))
            r.writes = n - r.reads
            r.total_latency = r.reads * t_slow + r.writes * t_write_bypass
            results[k] = r
            continue
        if two and cap2 <= 0:        # degenerate warm L2 behind a dead level
            results[k] = run_interp(k)
            results[k].fallback = 1              # degenerate: telemetry
            continue
        vec.append(k)

    rds: list[np.ndarray | None] = [None] * T
    if not vec:
        _pstep(profile, None)
        return (results, rds) if return_window_rd else results

    # restore the hierarchy invariant before reading warm state (both
    # engines normalize identically — see simulator.rebalance_levels)
    for k in vec:
        c, c2 = caches[k], caches2[k]
        if c is not None and c2 is not None and len(c2) > 0:
            rebalance_levels(c, c2)

    # ------------------------------------------------------ build the tape
    # one contiguous block per tenant: [warm L2 prefix][warm L1 prefix]
    # (pseudo-reads carrying dirty flags, each LRU -> MRU: the union stack)
    # + [window accesses]; address ids remapped per tenant so blocks never
    # interact.
    parts_addr, parts_read, parts_force = [], [], []
    starts, l2_ends, bodies, ends = [], [], [], []
    off = 0
    for k in vec:
        tr, c, c2 = traces[k], caches[k], caches2[k]
        if c2 is not None and len(c2) > 0:
            paddrs2, pdirty2 = c2.state_arrays()
        else:
            paddrs2 = np.zeros(0, np.int64)
            pdirty2 = np.zeros(0, bool)
        if c is not None and len(c) > 0:
            paddrs, pdirty = c.state_arrays()
        else:
            paddrs = np.zeros(0, np.int64)
            pdirty = np.zeros(0, bool)
        parts_addr.append(np.concatenate([paddrs2, paddrs, tr.addrs]))
        parts_read.append(np.concatenate(
            [np.ones(paddrs2.size + paddrs.size, bool), tr.is_read]))
        parts_force.append(np.concatenate(
            [pdirty2, pdirty, np.zeros(len(tr), bool)]))
        starts.append(off)
        l2_ends.append(off + paddrs2.size)
        bodies.append(off + paddrs2.size + paddrs.size)
        off += paddrs2.size + paddrs.size + len(tr)
        ends.append(off)

    orig_addr = np.concatenate(parts_addr)
    is_read = np.concatenate(parts_read)
    force_dirty = np.concatenate(parts_force)
    m = off
    pos = np.arange(m, dtype=np.int64)
    starts_a = np.array(starts, np.int64)
    l2_ends_a = np.array(l2_ends, np.int64)
    bodies_a = np.array(bodies, np.int64)
    ends_a = np.array(ends, np.int64)
    lens = ends_a - starts_a
    tid = np.repeat(np.arange(len(vec), dtype=np.int64), lens)
    cap1_arr = np.array([caps1[k] for k in vec], np.int64)
    cap2_arr = np.array([caps2[k] for k in vec], np.int64)
    captot_arr = cap1_arr + cap2_arr
    cap1_of = np.repeat(cap1_arr, lens)
    captot_of = np.repeat(captot_arr, lens)
    pol_codes = np.array([{"wb": 0, "wt": 1, "ro": 2}[policies[k].value]
                          for k in vec], np.int64)
    clean2_arr = np.array([policies2[k] is not WritePolicy.WB
                           and caps2[k] > 0 and caps1[k] > 0 for k in vec],
                          bool)
    clean2_of = np.repeat(clean2_arr, lens)
    # hit-level boundary: hits whose previous occurrence precedes it are L2
    # hits (RO path); when L1 has no capacity the only level *is* L2
    l2b_arr = np.where(cap1_arr > 0, l2_ends_a, m)
    l2b_of = np.repeat(l2b_arr, lens)
    l2end_of = np.repeat(l2_ends_a, lens)       # true warm-L2 boundary
    pol_of = np.repeat(pol_codes, lens)
    end_of = np.repeat(ends_a, lens)
    counted = pos >= np.repeat(bodies_a, lens)
    is_write = ~is_read

    # occurrence links from per-tenant stable argsorts (cache-resident;
    # blocks never interact, so cross-block address collisions are severed
    # by forcing segment heads at block starts); the same ordering is
    # reused below for the dirty-chain segmented reductions
    ordi = np.empty(m, dtype=np.int64)
    for t in range(len(vec)):
        s, e = starts[t], ends[t]
        ordi[s:e] = s + np.argsort(orig_addr[s:e], kind="stable")
    sorted_vals = orig_addr[ordi]
    same_prev = np.zeros(m, dtype=bool)
    same_prev[1:] = sorted_vals[1:] == sorted_vals[:-1]
    same_prev[starts_a] = False                  # sever cross-block ties
    prev = np.full(m, -1, dtype=np.int64)
    prev[ordi[1:]] = np.where(same_prev[1:], ordi[:-1], -1)
    nxt = np.full(m, m, dtype=np.int64)
    nxt[ordi[:-1]] = np.where(same_prev[1:], ordi[1:], m)
    nxt_c = np.minimum(nxt, end_of)

    # clean-L2 policies flush any warm dirty L2 content up-front (the
    # interpreter does the same); the tape forgets those flags so the
    # token replays, dirty chains and final state all see a clean L2
    flush_pre = np.zeros(len(vec), np.int64)
    if force_dirty.any():
        for t in range(len(vec)):
            if not clean2_arr[t]:
                continue
            sl = slice(starts[t], l2_ends[t])
            nd = int(np.sum(force_dirty[sl]))
            if nd:
                flush_pre[t] = nd
                force_dirty[sl] = False

    # --------------------------------------- RO residency: guard or tokens
    # L[t] = live blocks after access t assuming no eviction; for a real
    # L1 level subtract U2[t] = still-untouched warm-L2 blocks (they live
    # in L2, not L1).  While L1-live <= C1 the level can never have filled,
    # so no eviction/demotion has occurred and resident ⟺ live is exact.
    # Tenants exceeding the bound are replayed by the O(n) eviction-token
    # loop — single-level (``_ro_token_replay``) when C2 == 0 or C1 == 0
    # (where L2 *is* the level), the per-level two-level generalization
    # (``_ro_token_replay_levels``) otherwise — still exact, still
    # loop-free afterwards: the loops only shorten token deaths and
    # transfer levels, and hits are recovered as ``death[prev] == i``
    # (split per level by ``lvl[prev]``).  The guard and the loops run on
    # the host on every backend.
    tokens: dict[int, tuple[np.ndarray, np.ndarray, int]] = {}
    tokens2: dict[int, tuple[np.ndarray, np.ndarray, np.ndarray,
                             int, int]] = {}
    if np.any(pol_codes == 2):
        _pstep(profile, "replay_live_counts")
        L = _live_counts(nxt_c, np.flatnonzero(is_read))
        w2 = np.flatnonzero(pos < l2end_of)      # warm-L2 pseudo positions
        U2 = _live_counts(nxt_c, w2) if w2.size else None
        _pstep(profile, "replay_ro_tokens")
        for t, k in enumerate(vec):
            if pol_codes[t] != 2:
                continue
            s, e = starts[t], ends[t]
            cap1, cap2 = int(cap1_arr[t]), int(cap2_arr[t])
            ro_cap = cap1 if cap1 > 0 else cap1 + cap2
            lt = L[s:e]
            if U2 is not None and cap1 > 0 and cap2 > 0:
                lt = lt - U2[s:e]
            if int(lt.max()) > ro_cap:
                if cap1 > 0 and cap2 > 0:
                    tokens2[t] = _ro_token_replay_levels(
                        is_read[s:e], prev[s:e] - s, nxt_c[s:e] - s,
                        force_dirty[s:e], cap1, cap2,
                        int(l2_ends_a[t] - s), bool(clean2_arr[t]))
                else:
                    tokens[t] = _ro_token_replay(
                        is_read[s:e], prev[s:e] - s, nxt_c[s:e] - s,
                        force_dirty[s:e], ro_cap)
                if profile is not None:
                    profile.count("ro_tokens", e - s)

    # -------------------------------------------------- residency oracle
    # (the kernel's counting window (prev[i], i) never crosses a tenant
    # block for hot accesses and cold rows are masked, so the whole tape
    # goes through one kernel launch on TPU)
    _pstep(profile, "replay_residency")
    sd = level_masks = None
    if _accel_default():
        from repro.kernels.cache_sim.ops import (residency_levels_accel,
                                                 stack_distances_accel)
        if return_window_rd:
            sd = stack_distances_accel(prev, nxt_c)
        else:
            # both-level residency straight off the kernel, one launch
            level_masks = residency_levels_accel(prev, nxt_c,
                                                 cap1_of, captot_of)
        if profile is not None:
            profile.sync()
    else:
        sd = _stack_distances_host(prev, nxt_c,
                                   bounds=np.concatenate([starts_a, [m]]))
    _pstep(profile, "replay_account")
    if return_window_rd:
        # window-internal reuse distances: reuses whose previous occurrence
        # is a warm-prefix pseudo-access are cold from the Analyzer's view
        for t, k in enumerate(vec):
            sl = slice(int(bodies_a[t]), int(ends_a[t]))
            rds[k] = np.where(prev[sl] >= bodies_a[t], sd[sl], -1)
    hot = prev >= 0
    prev_safe = np.maximum(prev, 0)
    if level_masks is not None:
        res_l1_sd, res_un_sd = level_masks
    else:
        res_l1_sd = hot & (sd < cap1_of) & (sd >= 0)
        res_un_sd = hot & (sd < captot_of) & (sd >= 0)
    res_ro = hot & is_read[prev_safe]
    resident = np.where(pol_of == 2, res_ro, res_un_sd)
    for t, rec in itertools.chain(tokens.items(), tokens2.items()):
        death = rec[0]
        s, e = starts[t], ends[t]
        pl = prev[s:e] - s
        pls = np.maximum(pl, 0)
        blk_read = is_read[s:e]
        resident[s:e] = ((pl >= 0) & blk_read[pls]
                         & (death[pls] == np.arange(e - s)))
    # split hits by level: WB/WT against the two stack thresholds, RO by
    # whether the previous occurrence is a still-untouched warm-L2 block —
    # or, under eviction pressure, by the level the token died in
    res_l2 = np.where(pol_of == 2,
                      resident & (prev_safe < l2b_of),
                      resident & ~res_l1_sd)
    for t, (_, _, lv, _, _) in tokens2.items():
        s, e = starts[t], ends[t]
        pls = np.maximum(prev[s:e] - s, 0)
        res_l2[s:e] = resident[s:e] & (lv[pls] == 2)
    res_l1 = resident & ~res_l2

    # ------------------------------------------------------- dirty chains
    # group by address, segment at installs (non-resident accesses — for a
    # clean L2 the chain instead segments at L1 exits, since demotion
    # flushes the block and it re-promotes clean); the dirty flag after
    # each access is a segmented reduction:
    #   WB       : OR of (is_write | forced) over the period so far
    #   WT / RO  : forced flag at the period head, cleared by any write
    #              (WT write-through propagates -> cached copy is clean;
    #               RO writes invalidate, the flag only matters for warm
    #               prefix blocks)
    chain_res = np.where(clean2_of & (pol_of != 2), res_l1, resident)
    head = _segment_heads(sorted_vals) | ~chain_res[ordi]
    head[starts_a] = True                        # sever cross-block ties
    head_pos = np.maximum.accumulate(np.where(head, np.arange(m), -1))
    any_force = bool(force_dirty.any())
    all_wb = bool(np.all(pol_codes == 0))
    w_wb = (is_write | force_dirty)[ordi].astype(np.int64)
    cw_wb = np.cumsum(w_wb)
    dirty_wb_s = (cw_wb - cw_wb[head_pos] + w_wb[head_pos]) > 0
    if any_force and not all_wb:
        w_any = is_write[ordi].astype(np.int64)
        cw_any = np.cumsum(w_any)
        seg_writes = cw_any - cw_any[head_pos] + w_any[head_pos]
        dirty_chain_s = force_dirty[ordi][head_pos] & (seg_writes == 0)
    else:
        # WT/RO blocks can only be dirty via warm-prefix flags
        dirty_chain_s = np.zeros(m, dtype=bool)
    dirty_after = np.empty(m, dtype=bool)
    dirty_after[ordi] = np.where(pol_of[ordi] == 0, dirty_wb_s,
                                 dirty_chain_s)

    # ------------------------------------------------- flush accounting
    # an eviction displaces the block last touched at j iff its next
    # occurrence misses, or (no next occurrence) >= C distinct addresses
    # follow it; dirty evictions charge flush_cost (WB/WT only: RO fast
    # path proved no evictions happen).  With a dirty-accepting L2 the
    # flush happens at the *union* eviction; with a clean L2 it happens at
    # the L1 exit (demotion) instead — same machinery, C1 threshold.
    last = nxt_c == end_of
    cl = np.cumsum(last.astype(np.int64))
    D = cl[end_of - 1] - cl
    if flush_cost > 0.0:
        flushcap_of = np.where(clean2_of & (pol_of != 2),
                               cap1_of, captot_of)
        miss_next = np.zeros(m, dtype=bool)
        nz = ~last
        miss_next[nz] = ~chain_res[nxt_c[nz]]
        evicted = np.where(last, D >= flushcap_of, miss_next)
        flush_ev = dirty_after & evicted & (pol_of != 2)
        flush_per = np.bincount(tid[flush_ev], minlength=len(vec))
    else:
        flush_per = np.zeros(len(vec), np.int64)
    flush_per += flush_pre
    for t, (_, _, fl) in tokens.items():         # RO evictions under pressure
        flush_per[t] += fl
    for t, (_, _, _, fl, _) in tokens2.items():  # incl. demotion flushes
        flush_per[t] += fl

    # ------------------------------------------------------- per-tenant stats
    # one fused bincount: code = 8*tenant + 4*is_read + level
    # (level: 0 = miss, 1 = L2 hit, 2 = L1 hit)
    lvl = res_l1.astype(np.int64) * 2 + res_l2.astype(np.int64)
    code = tid * 8 + is_read.astype(np.int64) * 4 + lvl
    cnts = np.bincount(code[counted], minlength=8 * len(vec)) \
        .reshape(len(vec), 8)
    reads_per = cnts[:, 4] + cnts[:, 5] + cnts[:, 6]
    rhits_per = cnts[:, 6]
    rhits2_per = cnts[:, 5]
    writes_per = cnts[:, 0] + cnts[:, 1] + cnts[:, 2]
    whits_per = cnts[:, 2]
    whits2_per = cnts[:, 1]
    # distinct union addresses per tenant block -> closed-form demotions
    U_per = np.bincount(tid[last], minlength=len(vec))

    for t, k in enumerate(vec):
        pol = policies[k]
        cap1, cap2 = int(cap1_arr[t]), int(cap2_arr[t])
        captot = cap1 + cap2
        r = SimResult(capacity=cap1, policy=pol.value, capacity2=cap2,
                      policy2=(policies2[k].value if cap2 > 0 else "wb"))
        r.reads = int(reads_per[t])
        r.read_hits = int(rhits_per[t])
        r.read_hits_l2 = int(rhits2_per[t])
        r.writes = int(writes_per[t])
        r.write_hits = int(whits_per[t])
        r.write_hits_l2 = int(whits2_per[t])
        l2h = r.read_hits_l2
        rmiss = r.reads - r.read_hits - l2h
        fl = int(flush_per[t])
        if pol is WritePolicy.WB:
            if cap1 > 0:
                r.cache_writes = rmiss + l2h + r.writes
                r.total_latency = (r.read_hits * t_fast + rmiss * t_slow
                                   + r.writes * t_fast + fl * flush_cost)
            elif captot > 0:
                r.cache_writes_l2 = rmiss + r.writes
                r.total_latency = (rmiss * t_slow + r.writes * t_fast2
                                   + fl * flush_cost)
            else:
                r.total_latency = (rmiss * t_slow
                                   + r.writes * t_write_bypass)
        elif pol is WritePolicy.WT:
            if cap1 > 0:
                r.cache_writes = rmiss + l2h + r.writes
            elif captot > 0:
                r.cache_writes_l2 = rmiss + r.writes
            r.total_latency = (r.read_hits * t_fast + rmiss * t_slow
                               + r.writes * t_write_bypass
                               + fl * flush_cost)
        else:
            if cap1 > 0:
                r.cache_writes = rmiss + l2h     # installs + promotions
                if t in tokens2:                 # demotions under pressure
                    r.cache_writes_l2 = int(tokens2[t][4])
            elif captot > 0:
                r.cache_writes_l2 = rmiss
            r.total_latency = (r.read_hits * t_fast + rmiss * t_slow
                               + r.writes * t_write_bypass
                               + fl * flush_cost)
        if l2h:
            r.total_latency += l2h * t_fast2
        if cap1 > 0 and cap2 > 0 and pol is not WritePolicy.RO:
            # every install into a full L1 demotes its victim into L2
            installs = (r.reads - r.read_hits) + (r.writes - r.write_hits)
            final_l1 = min(int(U_per[t]), cap1)
            init_l1 = int(bodies_a[t] - l2_ends_a[t])
            r.cache_writes_l2 = installs - (final_l1 - init_l1)

        # ------------------------------------------- final LRU state
        c = caches[k]
        c2v = caches2[k]
        if c is not None or c2v is not None:
            sl = slice(starts[t], ends[t])
            surv_lvl = None
            if t in tokens:
                death, tdirty, _ = tokens[t]
                keep = is_read[sl] & (death == ends[t] - starts[t])
                dirty_keep = tdirty[keep]
            elif t in tokens2:
                death, tdirty, tlvl, _, _ = tokens2[t]
                keep = is_read[sl] & (death == ends[t] - starts[t])
                dirty_keep = tdirty[keep]
                surv_lvl = tlvl[keep]
            else:
                blk_last = last[sl]
                if pol is WritePolicy.RO:
                    keep = blk_last & is_read[sl]
                else:
                    keep = blk_last & (D[sl] < captot)
                dirty_keep = dirty_after[starts[t]:ends[t]][keep]
            js = np.flatnonzero(keep) + starts[t]       # ascending = LRU->MRU
            if cap2 <= 0 and (c2v is None or len(c2v) == 0):
                if c is not None:
                    c.set_state_arrays(orig_addr[js], dirty_keep)
            else:
                # split the union survivor stack at depth C1 (WB/WT), by
                # warm-L2 pseudo position (RO: untouched blocks stay in
                # L2), or by the surviving token's level (RO pressure)
                if surv_lvl is not None:
                    in_l2 = surv_lvl == 2
                elif pol is WritePolicy.RO:
                    in_l2 = js < int(l2b_arr[t])
                else:
                    n1 = min(cap1, js.size)
                    in_l2 = np.arange(js.size) < js.size - n1
                if c is not None:
                    c.set_state_arrays(orig_addr[js[~in_l2]],
                                       dirty_keep[~in_l2])
                if c2v is not None:
                    d2k = dirty_keep[in_l2]
                    if clean2_arr[t]:
                        d2k = np.zeros(d2k.size, bool)
                    c2v.set_state_arrays(orig_addr[js[in_l2]], d2k)
        results[k] = r
    _pstep(profile, None)
    return (results, rds) if return_window_rd else results


def simulate_batch(trace: Trace, capacity: int,
                   policy: WritePolicy = WritePolicy.WB,
                   t_fast: float = 1.0, t_slow: float = 20.0,
                   t_write_bypass: float | None = None,
                   flush_cost: float = 0.0,
                   cache: LRUCache | None = None, *,
                   capacity2: int = 0,
                   policy2: WritePolicy = WritePolicy.WB,
                   t_fast2: float | None = None,
                   cache2: LRUCache | None = None) -> SimResult:
    """Drop-in vectorized replacement for ``simulator.simulate``."""
    return simulate_many([trace], [capacity], [policy], t_fast=t_fast,
                         t_slow=t_slow, t_write_bypass=t_write_bypass,
                         flush_cost=flush_cost, caches=[cache],
                         capacities2=[capacity2], policies2=[policy2],
                         caches2=[cache2], t_fast2=t_fast2)[0]
