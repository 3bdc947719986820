"""Jit'd wrapper: occurrence links -> LRU stack distances on accelerator.

``stack_distances_accel`` is the TPU path of the batch simulation engine
(``repro.core.batch_sim.stack_distances``): counting runs in the Pallas
kernel on TPU, or via the jnp oracle elsewhere.  Matches the numpy
merge-tree host path exactly (tested in ``tests/test_batch_sim.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cache_sim.kernel import (cache_sim_levels_scan,
                                            cache_sim_scan,
                                            cache_sim_segments_scan)
from repro.kernels.cache_sim.ref import (cache_sim_levels_ref, cache_sim_ref,
                                         cache_sim_segments_ref,
                                         cache_sim_segments_tree)

__all__ = ["cache_sim_op", "cache_sim_levels_op", "cache_sim_segments_op",
           "segment_counts_device", "stack_distances_accel",
           "residency_levels_accel", "stack_distances_segments_accel",
           "width_groups_of"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("use_kernel",))
def cache_sim_op(prev, nxt, occ, *, use_kernel: bool | None = None):
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel:
        return cache_sim_scan(prev, nxt, occ, interpret=not _on_tpu())
    return cache_sim_ref(prev, nxt, occ)


@partial(jax.jit, static_argnames=("use_kernel",))
def cache_sim_levels_op(prev, nxt, occ, cap1, captot, *,
                        use_kernel: bool | None = None):
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel:
        return cache_sim_levels_scan(prev, nxt, occ, cap1, captot,
                                     interpret=not _on_tpu())
    return cache_sim_levels_ref(prev, nxt, occ, cap1, captot)


@partial(jax.jit, static_argnames=("seg_width", "use_kernel"))
def cache_sim_segments_op(prev, nxt, occ, *, seg_width: int,
                          use_kernel: bool | None = None):
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel:
        return cache_sim_segments_scan(prev, nxt, occ, seg_width=seg_width,
                                       interpret=not _on_tpu())
    return cache_sim_segments_ref(prev, nxt, occ, seg_width)


def width_groups_of(widths: np.ndarray) -> tuple[tuple[int, int, int], ...]:
    """Static (seg_width, lo, hi) spans of a padded tape's width runs.

    ``widths`` is ``padded_segment_layout``'s descending power-of-two
    width vector; each distinct width is one contiguous, self-aligned
    chunk ``[lo, hi)`` of the padded tape.  The tuple is hashable, so it
    serves as (part of) the jit static shape-bucket key of the fused
    device window program — retraces are bounded by the distinct width
    *structures*, not by raw window lengths.
    """
    widths = np.asarray(widths, dtype=np.int64)
    if widths.size == 0:
        return ()
    csw = np.concatenate([[0], np.cumsum(widths)]).astype(np.int64)
    heads = np.flatnonzero(
        np.concatenate([[True], widths[1:] != widths[:-1]]))
    return tuple((int(widths[h0]), int(csw[h0]), int(csw[int(h1)]))
                 for h0, h1 in zip(heads, np.append(heads[1:], widths.size)))


def segment_counts_device(gprev, gnxt, gocc,
                          width_groups: tuple[tuple[int, int, int], ...],
                          use_kernel: bool | None = None):
    """Traceable multi-width SD counting over a whole padded tape.

    The in-jit core of both ``stack_distances_segments_accel`` (which
    wraps it in one jitted launch per width and syncs per launch) and the
    fused device window program (``core.device_pipeline``, which inlines
    it so *no* host sync separates counting from the downstream segment
    reduction).  ``gprev``/``gnxt`` hold padded-tape-global links
    (``batch_sim.padded_tape_links``); each static ``(w, lo, hi)`` group
    is counted with the width-``w`` restricted grid (Pallas kernel on
    TPU, the O(m log² w) merge-sort-tree oracle
    ``cache_sim_segments_tree`` elsewhere — the dense (i, j) plane would
    be quadratic in the window tape) after localizing links to the
    group's own chunk.  Returns int32 counts for the full padded tape.
    """
    if use_kernel is None:
        use_kernel = _on_tpu()
    parts = []
    for w, lo, hi in width_groups:
        gp = gprev[lo:hi]
        loc_prev = jnp.where(gp >= 0, gp - lo, -1).astype(jnp.int32)
        loc_nxt = (gnxt[lo:hi] - lo).astype(jnp.int32)
        occ = gocc[lo:hi].astype(jnp.int32)
        if use_kernel:
            parts.append(cache_sim_segments_scan(loc_prev, loc_nxt, occ,
                                                 seg_width=w,
                                                 interpret=not _on_tpu()))
        else:
            parts.append(cache_sim_segments_tree(loc_prev, loc_nxt, occ, w))
    if not parts:
        return jnp.zeros(0, jnp.int32)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def stack_distances_accel(prev: np.ndarray, nxt: np.ndarray,
                          occ: np.ndarray | None = None,
                          use_kernel: bool | None = None) -> np.ndarray:
    """int64 stack distances per access, -1 where cold (prev < 0)."""
    n = prev.shape[0]
    if occ is None:
        occ = np.ones(n, dtype=np.int32)
    counts = np.asarray(cache_sim_op(jnp.asarray(prev, jnp.int32),
                                     jnp.asarray(nxt, jnp.int32),
                                     jnp.asarray(occ, jnp.int32),
                                     use_kernel=use_kernel))
    out = np.full(n, -1, dtype=np.int64)
    hot = prev >= 0
    out[hot] = counts[hot].astype(np.int64)
    return out


def stack_distances_segments_accel(prev: np.ndarray, nxt: np.ndarray,
                                   bounds: np.ndarray | None = None,
                                   use_kernel: bool | None = None,
                                   layout=None, profile=None) -> np.ndarray:
    """SD counting for a multi-tenant *tape* (segment-severed links).

    The accelerator path of the fused monitor (``repro.core.monitor``):
    links are severed at tenant block boundaries and ``nxt`` is clamped to
    the owning block's end, so a hot access's counting window
    ``(prev[i], i)`` never crosses a segment and the cross-segment
    dominance contributions cancel.

    With ``bounds`` (the per-tenant segment offsets) the tape is re-laid
    out through ``batch_sim.padded_segment_layout`` — each segment padded
    to the next power of two and self-aligned, padding rows cold
    (``prev = -1``) and non-occupying (``occ = 0``) — and counted with
    **one launch per distinct padded width**, each launch restricted to
    the segment-aligned (i, j) grid blocks (``cache_sim_segments_scan`` /
    the ``cache_sim_segments_ref`` dense oracle).  Widths are powers of
    two, so jit retraces stay bounded.  Without ``bounds`` one
    unrestricted launch covers the whole tape, exactly like the batch
    replay engine's tape.

    ``profile`` (a ``core.stage_profile.StageProfile``) records one host
    sync per width launch — the per-window sync count this path pays and
    the fused device program eliminates.
    """
    if bounds is None or len(bounds) <= 2:
        if profile is not None:
            profile.sync()
        return stack_distances_accel(prev, nxt, use_kernel=use_kernel)
    from repro.core.batch_sim import (padded_segment_layout,
                                      padded_tape_links)
    n = prev.shape[0]
    out = np.full(n, -1, dtype=np.int64)
    lay = layout if layout is not None else padded_segment_layout(bounds)
    src, tpos, base_src, base_pad, widths, total, _ = lay
    if tpos.size == 0:
        return out
    if src is None:                              # layout kept tape order
        src = np.arange(n, dtype=tpos.dtype)
    # padded tape with sentinel links: pads never occupy and stay cold
    gprev, gnxt, gocc = padded_tape_links(prev, nxt, lay)
    # widths descend, so each distinct width is one contiguous, aligned
    # chunk of the padded tape -> one restricted-grid launch per width
    counts = np.empty(total, dtype=np.int64)
    for w, lo, hi in width_groups_of(widths):
        gp = gprev[lo:hi]
        c = cache_sim_segments_op(
            jnp.asarray(np.where(gp >= 0, gp - lo, -1), jnp.int32),
            jnp.asarray(gnxt[lo:hi] - lo, jnp.int32),
            jnp.asarray(gocc[lo:hi]),
            seg_width=w, use_kernel=use_kernel)
        if profile is not None:
            profile.sync()                       # np.asarray blocks below
        counts[lo:hi] = np.asarray(c).astype(np.int64)
    hot = prev[src] >= 0
    out[src[hot]] = counts[tpos[hot]]
    return out


def residency_levels_accel(prev: np.ndarray, nxt: np.ndarray,
                           cap1: np.ndarray, captot: np.ndarray,
                           occ: np.ndarray | None = None,
                           use_kernel: bool | None = None
                           ) -> tuple[np.ndarray, np.ndarray]:
    """Both-level residency as bool masks ``(l1, union)`` per access.

    The accelerator path of the two-level batch engine: one counting
    launch classifies every access of the tape against its tenant's L1 and
    L1+L2 capacity thresholds (see ``cache_sim_levels_scan``).
    """
    n = prev.shape[0]
    if occ is None:
        occ = np.ones(n, dtype=np.int32)
    l1, un = cache_sim_levels_op(jnp.asarray(prev, jnp.int32),
                                 jnp.asarray(nxt, jnp.int32),
                                 jnp.asarray(occ, jnp.int32),
                                 jnp.asarray(cap1, jnp.int32),
                                 jnp.asarray(captot, jnp.int32),
                                 use_kernel=use_kernel)
    return np.asarray(l1).astype(bool), np.asarray(un).astype(bool)
