"""Fused multi-tenant Monitor/Analyzer — one counting pass for all tenants,
optionally one *device program* for the whole window.

``ECICacheManager.analyze`` used to loop tenants in Python: a reuse-distance
pass, ``build_hit_ratio_function`` and the Alg.-3 write ratio per tenant, so
the control plane — not the simulated I/O — dominated at the ROADMAP's
thousand-tenant scale.  ``analyze_windows`` replaces that loop with batched
array code, in one of three pipelines:

  * ``pipeline="host"`` (default): the fused numpy path below — one padded
    tape, one counting pass, segment reductions.  Stage boundaries still
    cross the host: the counting pass syncs once per distinct padded width
    (``stack_distances_segments_accel``), and curves/write ratios/URD sizes
    are numpy reductions over the fetched distances.
  * ``pipeline="device"``: the same window, computed by **one jitted
    device program per window shape bucket** (``core.device_pipeline``).
    Ingest scatters the padded tape's links once, then counting
    (``ops.segment_counts_device`` — Pallas kernel on TPU, the
    ``cache_sim_segments_tree`` merge-sort-tree oracle elsewhere), the
    stacked-breakpoint curve build (a device twin of
    ``BatchedHitRatioFunctions``, reduced by per-row sort + run-length
    scatter), Alg.-3 write ratios (device bincount) and the URD sizes all
    run inside a single jit — **zero host syncs inside the window**, one
    sync to fetch the results.  Off TPU the program runs under
    ``jax.enable_x64`` and every output is bit-identical to
    the host pipeline (differential-tested in ``tests/test_monitor_scale``
    across both routes); on TPU it runs in f32/int32 with a documented
    tolerance.  ``precomputed_trd`` is ignored on this path — the program
    recounts on device (deterministically equal), which beats shipping
    per-tenant host arrays back in.  ``DeviceWindowPipeline`` extends the
    same program through the partition stage and double-buffers ingest
    across windows.
  * ``pipeline="sharded"``: the device program partitioned over a 1-D
    ``("shards",)`` mesh (``core.shard_pipeline``).  The padded tape is
    split **by whole tenant-segments** (greedy width-balanced assignment
    that keeps every shard's rows descending-pow2 self-aligned), and each
    shard runs the same counting/curve/write-ratio stage closures under
    ``shard_map`` on its resident chunk.  *Why sharding is exact*: the
    boundary-severing argument above is segment-local — occurrence links
    are clamped at segment ends and every pad/cross-segment dominance
    contribution cancels identically — so a shard holding whole segments
    computes exactly the counts the global tape would, with **no
    cross-device links at all**.  Only integer per-tenant summaries
    (breakpoint/URD/write counts) are ``psum``-reduced across shards
    (exact — each tenant lives wholly on one shard, foreign shards add
    zeros) and the device-resident curve store is ``all_gather``-ed once
    for the single replicated step, the envelope-walk budget cut — so
    curves, URD sizes, write ratios and allocations stay bit-identical
    to the fused host path at any shard count.  Still ≤ 1 host sync per
    window *per mesh*.  Default mesh:
    ``distributed.sharding.control_plane_mesh()`` over every local
    device (tests/CI force 8 host devices via ``XLA_FLAGS``).

All pipelines accept a ``StageProfile`` (``profile=``) recording per-stage
wall time and host-sync counts — ``benchmarks/bench_monitor_scale.py
--profile`` reports the breakdown, and the ≤1-sync-per-window(-per-mesh)
property of the device and sharded programs is asserted in tests.

The fused host path:

  * **One padded tape.**  All tenants' Δt window traces are concatenated
    into a single access tape with per-tenant segment offsets.  Occurrence
    links are severed at segment boundaries and ``nxt`` is clamped to the
    segment end, and the counting pass lays the segments out
    **power-of-two padded and self-aligned** (``batch_sim``'s
    ``padded_segment_layout``: each segment padded to the next power of
    two, segments ordered by descending padded width so every segment
    starts at a multiple of its own width).  The merge-tree stack-distance
    recursion then *stops at each segment's padded width*, so no merge
    level ever spans two tenants and the deep global-tape levels — which
    made the pre-padding fused pass *lose* to the per-tenant loop at 8M
    accesses — are never built at all.

    *Why padding is exact.*  Padding entries carry sentinel occurrence
    links (``prev = -1``, an empty coverage interval, counting value 0
    below every real segment-local ``nxt >= 1``), so a pad never enters a
    real access's dominance count — the same cancellation argument as the
    boundary severing: ``SD(i) = F(i) - G(i)`` only ever queries positions
    inside ``i``'s own segment, every cross-segment or pad contribution to
    ``F`` and ``G`` is identically zero there (a clamped link never
    reaches past its segment, a pad's interval is empty), and inside a
    self-aligned segment the width-bounded tree performs exactly the
    merges the segment-alone tree would.  The padded pass is therefore
    bit-identical to the per-tenant path — property-tested across
    adversarial shapes in ``tests/test_monitor_padding.py``.

    On TPU hosts the padded tape routes through the ``cache_sim`` ops
    layer instead (``stack_distances_segments_accel``): one Pallas kernel
    launch per distinct padded width, each with its grid restricted to the
    segment-aligned (i, j) blocks.
  * **Segment reductions.**  URD/TRD sample histograms, hit-ratio curves
    (``build_hit_ratio_functions``: one composite-key sort for all
    tenants, stacked breakpoint arrays), Alg.-3 write ratios (re-touch
    writes per tenant = one ``bincount``) and URD-based sizes all come
    from the same pass — no per-tenant Python loop anywhere.
  * **SHARDS end-to-end.**  With ``sample_rate`` set (a float, or
    ``"auto"`` for the target-sample-count tuner) the tape is spatially
    filtered *before* counting — hash salts are seed-stabilized per
    (tenant, window) via ``shards_salt`` — distances are scaled by 1/rate,
    curve heights use the Horvitz–Thompson estimator, and per-tenant
    expected-error bars (~1/sqrt(kept)) are reported.  Write ratios are
    estimated on the sampled sub-trace: spatial sampling keeps every access
    of a kept address, so the re-touch classification is exact per address
    and the ratio is unbiased.
  * **Precomputed distances.**  The batch replay engine already counts the
    window's stack distances; ``precomputed_trd`` forwards those raw TRD
    arrays so the exact path never re-counts what ``simulate_many`` just
    measured.

Exactness: on the exact path (``sample_rate=None``) every curve, URD size
and write ratio is bit-identical to the per-tenant seed code — property
tested in ``tests/test_monitor_scale.py``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.batch_sim import (_accel_default, _stack_distances_host,
                                  padded_segment_layout)
from repro.core.mrc import BatchedHitRatioFunctions, build_hit_ratio_functions
from repro.core.reuse_distance import (auto_sample_rate, shards_keep_mask,
                                       shards_salt)
from repro.core.stage_profile import _pstage
from repro.core.trace import Trace, validate_trace

__all__ = ["MonitorResult", "analyze_windows"]


@dataclasses.dataclass(frozen=True)
class MonitorResult:
    """Per-tenant Analyzer outputs for one Δt window, batched.

    curves: stacked hit-ratio step functions (sequence of
      ``HitRatioFunction`` views; feed directly to the partitioners).
    urd_sizes: int64[N] — ``calculateURDbasedSize`` per tenant (at the
      requested percentile; sampled path: from the scaled distances).
    write_ratios: float64[N] — Alg. 3 ``(WAW + WAR) / n`` per tenant
      (sampled path: unbiased estimate from the kept sub-trace).
    sample_rates: float64[N] — effective SHARDS rate per tenant (1.0 exact).
    expected_errors: float64[N] — expected absolute curve error
      (~1/sqrt(kept accesses)); 0.0 on the exact path.
    kind: "urd" | "trd".
    """

    curves: BatchedHitRatioFunctions
    urd_sizes: np.ndarray
    write_ratios: np.ndarray
    sample_rates: np.ndarray
    expected_errors: np.ndarray
    kind: str


def _segment_links(addrs: np.ndarray, tid: np.ndarray,
                   bounds: np.ndarray,
                   layout=None) -> tuple[np.ndarray, np.ndarray]:
    """prev/next occurrence links on a multi-tenant tape, severed at
    segment boundaries; ``nxt`` clamped to the owning segment's end.

    Runs on the same segment-aligned padded layout as the counting pass:
    ``(addr + 1) << pb | local_position`` keys are scattered onto the
    padded tape (pads carry key 0, sorting below every real entry and
    severing runs automatically) and each width group is one in-place SIMD
    row sort — adjacent equal-address entries of a row are then exactly
    the occurrence pairs.  No global ``argsort``: the value sort plus a
    handful of O(m) passes replaces it.  Falls back to the composite-key
    argsort for negative or enormous address spaces.
    """
    m = addrs.shape[0]
    prev = np.full(m, -1, dtype=np.int64)
    nxt = np.repeat(bounds[1:], np.diff(bounds))     # default: segment end
    if m == 0:
        return prev, nxt
    lo = int(addrs.min(initial=0))
    amax = int(addrs.max(initial=0))
    src, tpos, base_src, base_pad, widths, total, seg_starts = \
        layout if layout is not None else padded_segment_layout(bounds)
    pb = int(widths[0] - 1).bit_length()             # local-position bits
    vb = (amax - min(lo, 0) + 1).bit_length()        # address field bits
    if lo < 0 or vb + pb > 62:
        # composite key would overflow: legacy sort path
        big = amax + 1 - min(lo, 0)
        n_seg = int(tid[-1]) + 1
        same = np.zeros(m, dtype=bool)
        if lo < 0 or n_seg * big >= 2**62:
            order = np.lexsort((addrs, tid))
            sa, st = addrs[order], tid[order]
            same[1:] = (sa[1:] == sa[:-1]) & (st[1:] == st[:-1])
        else:
            key = tid * big + addrs
            order = np.argsort(key, kind="stable")
            sk = key[order]                  # one gather serves the compare
            same[1:] = sk[1:] == sk[:-1]
        prev[order[1:]] = np.where(same[1:], order[:-1], -1)
        nxt_full = np.full(m, m, dtype=np.int64)
        nxt_full[order[:-1]] = np.where(same[1:], order[1:], m)
        return prev, np.minimum(nxt_full, nxt)
    kdt = np.int32 if vb + pb <= 31 else np.int64
    gk = np.zeros(total, dtype=kdt)
    loc = (tpos - base_pad).astype(kdt)
    av = (addrs if src is None else addrs[src]).astype(kdt)
    gk[tpos] = ((av + kdt(1)) << pb) | loc
    # one in-place SIMD row sort per distinct width (contiguous, aligned)
    csw = np.concatenate([[0], np.cumsum(widths)]).astype(np.int64)
    heads = np.flatnonzero(
        np.concatenate([[True], widths[1:] != widths[:-1]]))
    for h0, h1 in zip(heads, np.append(heads[1:], widths.size)):
        glo, ghi = int(csw[h0]), int(csw[int(h1)])
        w = int(widths[h0])
        gk[glo:ghi].reshape(-1, w).sort(axis=1)
    H = gk >> pb                                     # 0 at pads
    # adjacent equal addresses inside a row = occurrence pairs; rows are
    # severed explicitly, pads sever themselves (H == 0 < every real)
    pair = np.empty(total, dtype=bool)
    pair[0] = False
    np.equal(H[1:], H[:-1], out=pair[1:])
    pair[1:] &= H[1:] > 0
    pair[csw[:-1]] = False                           # row starts
    # decode original tape positions of the sorted entries (pads decode to
    # their row's start; harmless — they never appear in a pair)
    P = (gk & kdt((1 << pb) - 1)).astype(np.int64)
    P += np.repeat(seg_starts, widths)
    iv = np.flatnonzero(pair)                        # pair = (iv - 1, iv)
    prev[P[iv]] = P[iv - 1]
    nxt[P[iv - 1]] = P[iv]
    return prev, nxt


def _sd_pass(prev: np.ndarray, nxt_c: np.ndarray, backend: str,
             bounds: np.ndarray | None = None,
             layout=None, profile=None) -> np.ndarray:
    """One width-bounded stack-distance counting pass over the whole tape.

    ``bounds`` carries the per-tenant segment offsets so both backends can
    use the segment-aligned padded layout (host: width-bounded merge tree;
    accel: width-restricted kernel grids) instead of paying the full
    global merge depth; ``layout`` is the tape's precomputed
    ``padded_segment_layout`` (shared with the link construction).
    ``profile`` records the accel route's per-width-launch host syncs.
    """
    if backend == "auto":
        backend = "accel" if _accel_default() else "host"
    if backend == "accel":
        from repro.kernels.cache_sim.ops import stack_distances_segments_accel
        return stack_distances_segments_accel(prev, nxt_c, bounds=bounds,
                                              layout=layout, profile=profile)
    return _stack_distances_host(prev, nxt_c, bounds=bounds, layout=layout)


def _urd_sizes(dist: np.ndarray, tid: np.ndarray, n_tenants: int,
               bounds: np.ndarray, percentile: float,
               curves: BatchedHitRatioFunctions) -> np.ndarray:
    """Batched ``urd_cache_blocks`` (max sample + 1, or percentile)."""
    if percentile >= 100.0:
        # max sample + 1 == the curve's largest breakpoint, already stacked
        return curves.max_useful_sizes.astype(np.int64).copy()
    out = np.zeros(n_tenants, dtype=np.int64)
    for i in range(n_tenants):                   # rare config; no recount
        seg = dist[bounds[i]:bounds[i + 1]]
        s = seg[seg >= 0]
        if s.size:
            out[i] = int(np.percentile(s, percentile)) + 1
    return out


def analyze_windows(traces: list[Trace], kind: str = "urd",
                    percentile: float = 100.0,
                    sample_rate: float | str | None = None,
                    window_seed: int = 0,
                    sample_target: int = 4096, sample_floor: int = 256,
                    precomputed_trd: list[np.ndarray | None] | None = None,
                    tenant_ids: list[int] | None = None,
                    backend: str = "auto", pipeline: str = "host",
                    profile=None, validate: bool = False,
                    fault_hook=None) -> MonitorResult:
    """Analyze every tenant's Δt window in one fused pass (see module doc).

    ``precomputed_trd[i]`` (host exact path only) carries tenant i's raw
    window-internal TRD sample array from the batch replay engine; missing
    entries are counted here.  ``tenant_ids`` stabilizes the per-tenant
    SHARDS salts under tenant retirement (defaults to positional ids).
    ``pipeline="device"`` routes the window through the fused device
    program (one jit, one host sync — requires ``percentile == 100``);
    ``pipeline="sharded"`` through its ``shard_map`` twin over the
    default control-plane mesh (same requirement, one sync per mesh);
    ``profile`` (a ``stage_profile.StageProfile``) records per-stage
    times and host syncs on any pipeline.

    ``validate=True`` checks every tape against the ingest contract first
    and raises ``TraceError`` with (tenant, window) coordinates on a
    malformed one (``window_seed`` doubles as the window coordinate) —
    direct callers get one clear error instead of a cryptic numpy/lax
    failure deep in the counting pass.  ``fault_hook`` (internal, fault
    injection) is invoked once at the pipeline's dispatch boundary: on
    the host path right before the counting/curve stage, on the device
    path immediately before the fused program launch.
    """
    if kind not in ("trd", "urd"):
        raise ValueError(f"kind must be 'trd' or 'urd', got {kind!r}")
    if pipeline not in ("host", "device", "sharded"):
        raise ValueError(f"pipeline must be 'host', 'device' or 'sharded', "
                         f"got {pipeline!r}")
    if pipeline != "host" and percentile < 100.0:
        raise ValueError(f"pipeline={pipeline!r} computes URD sizes from "
                         "the curve store (percentile=100); use the host "
                         "pipeline for percentile < 100")
    n = len(traces)
    lens = np.array([len(t) for t in traces], dtype=np.int64)
    bounds = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    m = int(bounds[-1])
    ids = np.asarray(tenant_ids if tenant_ids is not None else range(n),
                     dtype=np.int64)
    if validate:
        for i, t in enumerate(traces):
            validate_trace(t, tenant=int(ids[i]) if i < ids.size else i,
                           window=window_seed)

    if sample_rate is None:
        # ------------------------------------------------------ exact path
        is_read = (np.concatenate([t.is_read for t in traces]) if m
                   else np.zeros(0, bool))
        tid = np.repeat(np.arange(n, dtype=np.int64), lens)
        if pipeline in ("device", "sharded"):
            # one fused program (per mesh when sharded), one sync;
            # recounts even precomputed windows (deterministically equal
            # — see module doc)
            addrs = (np.concatenate([t.addrs for t in traces]) if m
                     else np.zeros(0, np.int64))
            if pipeline == "sharded":
                from repro.core.shard_pipeline import monitor_window_sharded
                curves, urd, wr, _ = monitor_window_sharded(
                    addrs, is_read, bounds, lens, kind=kind,
                    profile=profile, launch_hook=fault_hook)
            else:
                from repro.core.device_pipeline import monitor_window_device
                curves, urd, wr, _ = monitor_window_device(
                    addrs, is_read, bounds, lens, kind=kind,
                    profile=profile, launch_hook=fault_hook)
            return MonitorResult(curves, urd, wr, np.ones(n),
                                 np.zeros(n), kind)
        if fault_hook is not None:
            fault_hook()
        pre = precomputed_trd or []
        dist = np.full(m, -1, dtype=np.int64)
        need = []
        for i in range(n):
            raw = pre[i] if i < len(pre) else None
            if raw is not None:
                dist[bounds[i]:bounds[i + 1]] = raw
            elif lens[i] > 0:
                need.append(i)
        if need:
            # only the tenants without precomputed distances hit the
            # counting pass (no tape is built at all when every window
            # came through the batch replay engine)
            if len(need) == n:
                sel = np.ones(m, dtype=bool)
            else:
                sel = np.zeros(m, dtype=bool)
                for i in need:
                    sel[bounds[i]:bounds[i + 1]] = True
            addrs = np.concatenate([t.addrs for t in traces])
            sub_addr = addrs[sel]
            sub_tid = tid[sel]
            sub_lens = np.bincount(sub_tid, minlength=n)[need]
            sub_bounds = np.concatenate([[0], np.cumsum(sub_lens)])
            # compact tenant ids so segment ends line up on the sub-tape
            remap = np.zeros(n, dtype=np.int64)
            remap[need] = np.arange(len(need))
            sub_bounds = sub_bounds.astype(np.int64)
            with _pstage(profile, "links"):
                layout = padded_segment_layout(sub_bounds)
                prev, nxt_c = _segment_links(sub_addr, remap[sub_tid],
                                             sub_bounds, layout)
            with _pstage(profile, "count"):
                dist[sel] = _sd_pass(prev, nxt_c, backend, sub_bounds,
                                     layout, profile=profile)
        with _pstage(profile, "curve"):
            hot = dist >= 0
            wr = (np.bincount(tid[hot & ~is_read], minlength=n)
                  / np.maximum(lens, 1))
            smask = (hot & is_read) if kind == "urd" else hot
            if kind == "urd" and percentile < 100.0:
                dist = np.where(smask, dist, -1)  # rare: per-segment slices
            curves = build_hit_ratio_functions(dist, tid, n, lens,
                                               mask=smask)
            urd = _urd_sizes(dist, tid, n, bounds, percentile, curves)
        if profile is not None:
            profile.end_window()
        return MonitorResult(curves, urd, wr, np.ones(n),
                             np.zeros(n), kind)

    # -------------------------------------------------------- sampled path
    if sample_rate == "auto":
        rates = np.array([auto_sample_rate(int(nl), sample_target,
                                           sample_floor) for nl in lens])
    else:
        r = float(sample_rate)
        if not (0 < r <= 1):
            raise ValueError("sample_rate must be in (0, 1] or 'auto'")
        rates = np.full(n, r)
    # spatial filter per tenant (seed-stabilized salt per (tenant, window));
    # only the kept sub-tape is ever concatenated — the Monitor's ingest
    # never materializes a full-window tape on the sampled path
    keeps = [shards_keep_mask(t.addrs, float(rates[i]),
                              shards_salt(window_seed, int(ids[i])))
             for i, t in enumerate(traces)]
    kept = np.array([int(k.sum()) for k in keeps], dtype=np.int64)
    sub_bounds = np.concatenate([[0], np.cumsum(kept)]).astype(np.int64)
    if int(kept.sum()):
        addrs_s = np.concatenate(
            [t.addrs[k] for t, k in zip(traces, keeps)])
        read_s = np.concatenate(
            [t.is_read[k] for t, k in zip(traces, keeps)])
    else:
        addrs_s = np.zeros(0, np.int64)
        read_s = np.zeros(0, bool)
    tid_s = np.repeat(np.arange(n, dtype=np.int64), kept)
    if pipeline in ("device", "sharded"):
        # the fused program scales distances, builds the HT curves and the
        # write ratios on device; cold accesses of the kept sub-tape (its
        # distinct addresses) come back for the error bars
        if pipeline == "sharded":
            from repro.core.shard_pipeline import monitor_window_sharded
            curves, urd, wr, distinct = monitor_window_sharded(
                addrs_s, read_s, sub_bounds, lens, rates=rates, kind=kind,
                profile=profile, launch_hook=fault_hook)
        else:
            from repro.core.device_pipeline import monitor_window_device
            curves, urd, wr, distinct = monitor_window_device(
                addrs_s, read_s, sub_bounds, lens, rates=rates, kind=kind,
                profile=profile, launch_hook=fault_hook)
    else:
        if fault_hook is not None:
            fault_hook()
        with _pstage(profile, "links"):
            layout = padded_segment_layout(sub_bounds)
            prev, nxt_c = _segment_links(addrs_s, tid_s, sub_bounds, layout)
        with _pstage(profile, "count"):
            sd = _sd_pass(prev, nxt_c, backend, sub_bounds, layout,
                          profile=profile)
        with _pstage(profile, "curve"):
            rate_s = rates[tid_s]
            dist = np.where(sd >= 0, np.round(sd / np.maximum(rate_s, 1e-300)
                                              ).astype(np.int64), -1)
            hot_w = (dist >= 0) & ~read_s
            wr = np.bincount(tid_s[hot_w], minlength=n) / np.maximum(kept, 1)
            if kind == "urd":
                dist = np.where(read_s, dist, -1)
            curves = build_hit_ratio_functions(dist, tid_s, n, lens,
                                               rates=rates)
            urd = _urd_sizes(dist, tid_s, n, sub_bounds, percentile, curves)
            # error bars scale with the kept *distinct* addresses (= cold
            # accesses of the sub-tape): curve noise is binomial over
            # surviving addresses
            distinct = np.bincount(tid_s[prev < 0], minlength=n)
        if profile is not None:
            profile.end_window()
    errors = np.where(rates < 1.0,
                      np.minimum(1.0,
                                 1.0 / np.sqrt(np.maximum(distinct, 1))),
                      0.0)
    return MonitorResult(curves, urd, wr, rates, errors, kind)
