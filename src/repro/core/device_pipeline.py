"""Device-resident Δt window pipeline: count → reduce → curve → partition
fused into one jitted program, with double-buffered window ingest.

The fused host monitor (``core.monitor``) still round-trips numpy between
its stages: the counting pass syncs per padded-width launch, the curve
build, write ratios and the breakpoint-walk partitioner all run on host
arrays.  This module keeps the whole window decision on device:

  * **Ingest** (the only host work): the window tape is laid out through
    ``batch_sim.padded_segment_layout`` (power-of-two padded,
    self-aligned segments), occurrence links are built and scattered onto
    the padded tape (``padded_tape_links``), and everything is shipped
    with ``jax.device_put`` — asynchronously, so window t+1's transfer
    overlaps window t's on-device analysis (``DeviceWindowPipeline
    .run_stream``).
  * **One jitted program per window shape bucket** (the static key is the
    tape's ``width_groups_of`` structure + tenant count + mode flags, so
    retraces are bounded by distinct padded-width *structures*):
      - SD counting via ``ops.segment_counts_device`` (Pallas kernel on
        TPU, the ``cache_sim_segments_tree`` merge-sort-tree oracle
        elsewhere),
      - device-side segment reduction of the URD/TRD distances into a
        **stacked-breakpoint curve store** (a device twin of
        ``mrc.BatchedHitRatioFunctions``: per-row sort + run-length
        reduction; tenant i's breakpoints live at
        ``[row_start[i], row_start[i] + k[i])`` of the padded tape),
      - Alg.-3 write ratios via a device bincount,
      - the ``method="fast"`` envelope-scan ``greedy_allocate`` ported to
        ``lax`` primitives (row-local ``lax.cummin`` prefix-min envelope,
        one stable ``lax.sort`` merge, prefix-sum budget cut — the same
        grant order as the host walk, partial grant included).
    Zero host syncs inside the window: the single sync is the final
    result fetch (asserted by ``StageProfile``).
  * **Bit parity.**  Off TPU the program runs in float64/int64
    (``jax.enable_x64`` scoped to this pipeline only), and
    every per-tenant output — curve edges *and* heights, URD sizes, write
    ratios, allocations — is bit-identical to the host path; tier-1
    therefore exercises the full pipeline everywhere.  On TPU the program
    runs in f32/int32: allocations may differ only where f32 density
    rounding flips a tie (documented tolerance: compare decisions by
    aggregate latency), and scaled SHARDS distances must stay below 2^31.
    The aggregate-latency scalar is reduction-order sensitive in either
    mode (jnp sums sequentially, numpy pairwise) — compare it
    approximately; sizes and curves exactly.

``monitor_window_device`` backs ``analyze_windows(pipeline="device")``
(monitor outputs only); ``DeviceWindowPipeline`` fuses the partition
stage in as well and exposes the double-buffered ``run_stream``;
``greedy_walk_device`` reuses the jitted walk for standalone
``greedy_allocate(method="device")`` calls on host curve stores.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.batch_sim import padded_segment_layout, padded_tape_links
from repro.core.mrc import BatchedHitRatioFunctions
from repro.core.stage_profile import StageProfile, _pstage
from repro.kernels.cache_sim.ops import (_on_tpu, segment_counts_device,
                                         width_groups_of)

__all__ = ["StageProfile", "WindowIngest", "WindowDecision",
           "DeviceWindowPipeline", "greedy_walk_device", "ingest_window",
           "monitor_window_device", "transfer_sanitizer"]


# ------------------------------------------------------------ dtype plumbing
def _f64_default() -> bool:
    # off-TPU the pipeline runs in x64 for bit parity with the numpy host
    # path; on TPU it runs in the native f32/int32 (documented tolerance)
    return not _on_tpu()


def _x64(f64: bool):
    if f64:
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def transfer_sanitizer(enabled: bool = True):
    """Runtime teeth for the zero-hidden-sync window contract.

    Entered around a window's dispatch + fetch, ``jax.transfer_guard
    ("disallow")`` makes every *implicit* transfer raise — a stray
    ``.item()``, ``float()`` or numpy coercion on a device value anywhere
    under the window program becomes an immediate ``XlaRuntimeError``
    instead of a silent sync the ``StageProfile`` counter can only count
    after the fact.  Explicit ``jax.device_put`` / ``jax.device_get``
    stay exempt, which is exactly the contract: ingest transfers in via
    ``device_put``, and the window's one permitted sync — the decision
    fetch in ``_fetch`` — goes out via ``device_get``.  On the sharded
    pipeline the same contract holds **per mesh**: the stacked per-shard
    ingest is one explicit (async) ``device_put`` across the whole mesh
    and the replicated decision comes back in one ``device_get``, so a
    window still costs ≤ 1 host sync no matter how many shards the mesh
    holds (asserted by the shard suite via ``StageProfile``).
    Complements the static RL001 pass (tools/repro_lint), which cannot
    see through dynamic dispatch.
    """
    if not enabled:
        return contextlib.nullcontext()
    return jax.transfer_guard("disallow")


def _np_dtypes(f64: bool):
    return (np.int64, np.float64) if f64 else (np.int32, np.float32)


# ------------------------------------------------------------------- ingest
@dataclasses.dataclass
class WindowIngest:
    """One window's device-resident tape + host-side metadata.

    ``dev`` holds the device arrays (transferred asynchronously);
    ``key`` is the static jit bucket: retraces happen per distinct
    ``(width structure, n_tenants, sampled, kind, use_kernel, f64)``.
    """

    key: tuple
    dev: dict
    n: int
    total: int
    f64: bool
    row_start: np.ndarray      # int64[n] curve-store row base per tenant
    n_acc: np.ndarray          # int64[n] curve denominators (full lens)
    cold: np.ndarray           # int64[n] cold accesses (= kept distinct)


def ingest_window(addrs: np.ndarray, is_read: np.ndarray,
                  bounds: np.ndarray, n_accesses: np.ndarray, *,
                  rates: np.ndarray | None = None, kind: str = "urd",
                  use_kernel: bool | None = None, f64: bool | None = None,
                  profile: StageProfile | None = None
                  ) -> WindowIngest | None:
    """Host half of the pipeline: layout + links + async device transfer.

    ``bounds`` are the per-tenant segment offsets of the (possibly
    SHARDS-filtered) tape; ``n_accesses`` the *full* window lengths (the
    curve denominators).  Returns ``None`` for an all-empty window (the
    callers short-circuit to the trivial host result).
    """
    from repro.core.monitor import _segment_links
    bounds = np.asarray(bounds, np.int64)
    n = bounds.shape[0] - 1
    if use_kernel is None:
        use_kernel = _on_tpu()
    if f64 is None:
        f64 = _f64_default()
    idt, fdt = _np_dtypes(f64)
    with _pstage(profile, "ingest"):
        lens_sub = np.diff(bounds)
        tid = np.repeat(np.arange(n, dtype=np.int64), lens_sub)
        layout = padded_segment_layout(bounds)
        src, tpos, base_src, base_pad, widths, total, seg_starts = layout
        if n == 0 or total == 0:
            return None
        if not f64 and int(total) * (int(total) + 2) >= 2**31 \
                and not use_kernel:
            raise ValueError(
                "device pipeline: f64=False limits the merge-sort-tree "
                f"counting oracle to tapes with total*(total+2) < 2^31 "
                f"(got total={int(total)}); use f64=True or the TPU kernel")
        prev, nxt_c = _segment_links(addrs, tid, bounds, layout)
        gprev, gnxt, gocc = padded_tape_links(prev, nxt_c, layout)
        src_eff = (src if src is not None
                   else np.arange(addrs.shape[0], dtype=np.int64))
        gread = np.zeros(total, bool)
        gread[tpos] = is_read[src_eff]
        wg = width_groups_of(widths)
        row_base = np.concatenate([[0], np.cumsum(widths)[:-1]]
                                  ).astype(np.int64)
        # non-empty segments only; 'right' lands on the owning tenant even
        # when empty tenants duplicate the bound value
        row_tids = (np.searchsorted(bounds, seg_starts, side="right")
                    - 1).astype(np.int64)
        row_start = np.zeros(n, np.int64)
        row_start[row_tids] = row_base
        n_acc = np.maximum(np.asarray(n_accesses, np.int64), 1)
        cold = np.bincount(tid[prev < 0], minlength=n).astype(np.int64)
        host = {
            "gprev": gprev.astype(np.int32),
            "gnxt": gnxt.astype(np.int32),
            "gocc": gocc.astype(np.int32),
            "gread": gread,
            "gtid": np.repeat(row_tids, widths).astype(np.int32),
            "grank": (np.arange(total, dtype=np.int64)
                      - np.repeat(row_base, widths)).astype(np.int32),
            "row_tids": row_tids.astype(np.int32),
            "row_start": row_start.astype(idt),
            "n_acc": n_acc.astype(idt),
            "wr_den": np.maximum(lens_sub, 1).astype(idt),
            "rates": (np.ones(n, fdt) if rates is None
                      else np.asarray(rates, fdt)),
        }
        key = (wg, n, rates is not None, kind, bool(use_kernel), bool(f64))
        with _x64(f64):
            dev = jax.device_put(host)      # async: overlaps prior analysis
    return WindowIngest(key, dev, n, int(total), bool(f64),
                        row_start, n_acc, cold)


# ------------------------------------------------- traceable stage bodies
def _make_eval(n: int, f64: bool):
    """h_i(sizes_i) from the padded device curve store (host ``evaluate``
    semantics: the 0-head plateau below the first breakpoint, 0 at
    sizes <= 0)."""
    idt = jnp.int64 if f64 else jnp.int32
    fdt = jnp.float64 if f64 else jnp.float32

    def eval_at(edges_p, hgt_p, kcnt, gtid, grank, row_start, sizes):
        has_u = grank < kcnt[gtid]
        le = has_u & (edges_p <= sizes[gtid])
        kq = jnp.zeros(n, idt).at[gtid].add(le.astype(idt))
        h = hgt_p[row_start + jnp.maximum(kq - 1, 0)]
        return jnp.where((kq > 0) & (sizes > 0), h, fdt(0.0))

    return eval_at


def _make_walk(wg: tuple, n: int, total: int, f64: bool):
    """The ``method="fast"`` envelope-scan breakpoint walk on the padded
    device curve store — the host walk's grant order, in ``lax``.

    Chains (a tenant's steps strictly above its current size) live inside
    self-aligned rows, so the chain-stop cumsum and the prefix-min density
    envelope are row-local scans; one stable 3-key ``lax.sort``
    (``-envelope, tenant, rank``) reproduces ``np.lexsort``'s merge of
    all chains, a prefix sum over Δc finds the budget cut, and the first
    un-granted step receives the host walk's partial grant.
    """
    idt = jnp.int64 if f64 else jnp.int32
    fdt = jnp.float64 if f64 else jnp.float32

    def row_scan(x, fn):
        parts = [fn(x[lo:hi].reshape((hi - lo) // w, w)).reshape(-1)
                 for w, lo, hi in wg]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def walk(edges_p, hgt_p, kcnt, gtid, grank, row_start,
             sizes0, budget, w_t, gain):
        has_u = grank < kcnt[gtid]
        le0 = has_u & (edges_p <= sizes0[gtid])
        k0 = jnp.zeros(n, idt).at[gtid].add(le0.astype(idt))
        step = has_u & (grank >= k0[gtid])
        first = step & (grank == k0[gtid])
        h0 = jnp.where((k0 > 0) & (sizes0 > 0),
                       hgt_p[row_start + jnp.maximum(k0 - 1, 0)], fdt(0.0))
        hprev = jnp.concatenate([jnp.zeros(1, fdt), hgt_p[:-1]])
        eprev = jnp.concatenate([jnp.zeros(1, idt), edges_p[:-1]])
        dh = hgt_p - jnp.where(first, h0[gtid], hprev)
        dc = edges_p - jnp.where(first, sizes0[gtid], eprev)
        # chain-stop at the first non-improving step (host `valid`)
        bad = (step & (dh <= 0)).astype(idt)
        cbad = row_scan(bad, lambda x: jnp.cumsum(x, axis=1))
        valid = step & (cbad == 0)
        dens = (w_t[gtid] * dh * gain) / dc.astype(fdt)
        env = row_scan(jnp.where(valid, dens, jnp.inf),
                       lambda x: lax.cummin(x, axis=1))
        neg_e = jnp.where(valid, -env, jnp.inf).astype(fdt)
        dc_z = jnp.where(valid, dc, 0)
        # host order: lexsort((rank, tenant, -envelope)); invalid slots
        # carry +inf and sort past every valid step
        _, tid_s, _, dc_s, nxt_s = lax.sort(
            (neg_e, gtid, grank, dc_z, edges_p), num_keys=3, is_stable=True)
        cum = jnp.cumsum(dc_s)
        granted = (dc_s > 0) & (cum <= budget)
        sizes1 = sizes0.at[tid_s].max(jnp.where(granted, nxt_s, 0))
        ngrant = jnp.sum(granted.astype(idt))
        nvalid = jnp.sum(valid.astype(idt))
        spent = jnp.where(ngrant > 0,
                          cum[jnp.clip(ngrant - 1, 0, total - 1)], 0)
        rem = budget - spent
        nxt_i = jnp.clip(ngrant, 0, total - 1)
        part = jnp.where((rem > 0) & (ngrant < nvalid), rem, 0)
        return sizes1.at[tid_s[nxt_i]].add(part.astype(idt))

    return walk


_PROGRAMS: dict[tuple, dict] = {}


def _programs(key: tuple) -> dict:
    """Build (and cache) the jitted window programs for one shape bucket."""
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    wg, n, sampled, kind, use_kernel, f64 = key
    total = wg[-1][2]
    idt = jnp.int64 if f64 else jnp.int32
    fdt = jnp.float64 if f64 else jnp.float32
    sent = (1 << 62) if f64 else (1 << 30)   # above every real sample + 1
    rows_per = [(hi - lo) // w for w, lo, hi in wg]
    rb = np.concatenate([[0], np.cumsum(rows_per)]).astype(int)
    eval_at = _make_eval(n, f64)
    walk = _make_walk(wg, n, total, f64)

    def count_stage(d):
        counts = segment_counts_device(d["gprev"], d["gnxt"], d["gocc"], wg,
                                       use_kernel=use_kernel)
        hot = d["gprev"] >= 0
        if sampled:
            r = jnp.maximum(d["rates"][d["gtid"]], 1e-300)
            return jnp.where(hot, jnp.round(counts.astype(fdt) / r
                                            ).astype(idt), -1)
        return jnp.where(hot, counts.astype(idt), -1)

    def curve_stage(d, dist):
        smask = dist >= 0
        if kind == "urd":
            smask = smask & d["gread"]
        sv = jnp.where(smask, dist + 1, sent)
        edges_p = jnp.zeros(total, idt)
        cum_p = jnp.zeros(total, idt)
        kcnt = jnp.zeros(n, idt)
        urd = jnp.zeros(n, idt)
        for gi, (w, lo, hi) in enumerate(wg):
            rows = (hi - lo) // w
            s = jnp.sort(sv[lo:hi].reshape(rows, w), axis=1)
            val = s != sent
            sl = jnp.concatenate(
                [jnp.full((rows, 1), -1, s.dtype), s[:, :-1]], axis=1)
            sr = jnp.concatenate(
                [s[:, 1:], jnp.full((rows, 1), -1, s.dtype)], axis=1)
            new = val & (s != sl)               # first of a run = unique
            last = val & (s != sr)              # run end carries the cumsum
            rank = jnp.cumsum(new.astype(idt), axis=1) - 1
            iota = lax.broadcasted_iota(idt, (rows, w), 1)
            rowi = lax.broadcasted_iota(idt, (rows, w), 0)
            dst = jnp.where(last, lo + rowi * w + rank, total)
            edges_p = edges_p.at[dst.ravel()].set(s.ravel().astype(idt),
                                                  mode="drop")
            cum_p = cum_p.at[dst.ravel()].set((iota + 1).ravel(),
                                              mode="drop")
            rt = d["row_tids"][int(rb[gi]):int(rb[gi + 1])]
            kcnt = kcnt.at[rt].set(jnp.sum(new.astype(idt), axis=1))
            urd = urd.at[rt].set(jnp.max(jnp.where(val, s, 0),
                                         axis=1).astype(idt))
        # plateau heights: same int/int division (or HT estimator) as the
        # host build, computed where the run-ends landed
        if sampled:
            den = d["n_acc"] * d["rates"]
            hgt_p = jnp.minimum(cum_p / den[d["gtid"]], 1.0)
        else:
            hgt_p = cum_p / d["n_acc"][d["gtid"]]
        return edges_p, hgt_p.astype(fdt), kcnt, urd

    def wr_stage(d, dist):
        wflag = ((dist >= 0) & (~d["gread"])).astype(idt)
        wcnt = jnp.zeros(n, idt).at[d["gtid"]].add(wflag)
        return wcnt / d["wr_den"]

    def partition_stage(d, edges_p, hgt_p, kcnt, urd, p):
        capacity, c_min = p["capacity"], p["c_min"]
        w_t, t_fast, t_slow = p["weights"], p["t_fast"], p["t_slow"]
        c_min_arr = jnp.minimum(urd, c_min)
        feasible = jnp.sum(urd) <= capacity
        b0 = capacity - jnp.sum(c_min_arr)
        tot_min = jnp.maximum(jnp.sum(c_min_arr), 1)
        scaled = jnp.floor((c_min_arr * capacity).astype(fdt)
                           / tot_min.astype(fdt)).astype(idt)
        s0 = jnp.where(b0 < 0, scaled, c_min_arr)
        budget = capacity - jnp.sum(s0)
        walked = walk(edges_p, hgt_p, kcnt, d["gtid"], d["grank"],
                      d["row_start"], s0, budget, w_t, t_slow - t_fast)
        sizes = jnp.where(feasible, urd, walked)
        h_at = eval_at(edges_p, hgt_p, kcnt, d["gtid"], d["grank"],
                       d["row_start"], sizes)
        lat = jnp.sum(w_t * (h_at * t_fast + (1.0 - h_at) * t_slow))
        return sizes, h_at, lat, feasible

    def monitor_core(d):
        dist = count_stage(d)
        edges_p, hgt_p, kcnt, urd = curve_stage(d, dist)
        return edges_p, hgt_p, kcnt, urd, wr_stage(d, dist)

    def decision_core(d, p):
        dist = count_stage(d)
        edges_p, hgt_p, kcnt, urd = curve_stage(d, dist)
        wr = wr_stage(d, dist)
        sizes, h_at, lat, feasible = partition_stage(
            d, edges_p, hgt_p, kcnt, urd, p)
        return edges_p, hgt_p, kcnt, urd, wr, sizes, h_at, lat, feasible

    # donated scratch: each window's tape is consumed exactly once, so on
    # TPU the ingest buffers are recycled in place (CPU would only warn)
    dk = dict(donate_argnums=(0,)) if _on_tpu() else {}
    progs = {
        "monitor": jax.jit(monitor_core, **dk),
        "decision": jax.jit(decision_core, **dk),
        "count": jax.jit(count_stage),
        "curve": jax.jit(curve_stage),
        "wr": jax.jit(wr_stage),
        "partition": jax.jit(partition_stage),
        # unjitted stage bodies: the sharded pipeline re-traces exactly
        # these closures inside its shard_map body (core.shard_pipeline),
        # so per-shard counting/curve/partition stays one implementation
        "stages": {"count": count_stage, "curve": curve_stage,
                   "wr": wr_stage, "partition": partition_stage},
    }
    _PROGRAMS[key] = progs
    return progs


# --------------------------------------------------------------- dispatch
def _dispatch_monitor(ing: WindowIngest, profile: StageProfile | None,
                      sanitize: bool = False):
    progs = _programs(ing.key)
    with transfer_sanitizer(sanitize), _x64(ing.f64):
        if profile is not None and profile.staged:
            with profile.stage("count"):
                dist = progs["count"](ing.dev)
                jax.block_until_ready(dist)
                profile.sync()
            with profile.stage("curve"):
                cur = progs["curve"](ing.dev, dist)
                jax.block_until_ready(cur)
                profile.sync()
            with profile.stage("write_ratio"):
                wr = progs["wr"](ing.dev, dist)
                jax.block_until_ready(wr)
                profile.sync()
            return (*cur, wr)
        with _pstage(profile, "dispatch"):
            return progs["monitor"](ing.dev)


def _fetch(ing: WindowIngest, out, profile: StageProfile | None,
           sanitize: bool = False):
    """The window's single host sync: block on the program, copy out.

    The copy is an *explicit* ``jax.device_get`` — the one transfer the
    ``transfer_sanitizer`` guard permits, so under ``sanitize`` any other
    device->host escape in the window raises while this fetch stays legal.
    """
    with transfer_sanitizer(sanitize), _x64(ing.f64):
        with _pstage(profile, "fetch"):
            jax.block_until_ready(out)
            if profile is not None and not profile.staged:
                profile.sync()
        return jax.device_get(list(out))


def _trivial_monitor(n: int, n_accesses: np.ndarray):
    """Host-identical outputs for an all-empty window (no device work)."""
    k = np.zeros(n, np.int64)
    curves = BatchedHitRatioFunctions.from_padded(
        np.zeros(0, np.int64), np.zeros(0, np.float64), k,
        np.zeros(n, np.int64), n_accesses)
    return (curves, np.zeros(n, np.int64), np.zeros(n, np.float64),
            np.zeros(n, np.int64))


def monitor_window_device(addrs: np.ndarray, is_read: np.ndarray,
                          bounds: np.ndarray, n_accesses: np.ndarray, *,
                          rates: np.ndarray | None = None,
                          kind: str = "urd",
                          use_kernel: bool | None = None,
                          f64: bool | None = None,
                          profile: StageProfile | None = None,
                          launch_hook=None,
                          transfer_sanitize: bool = False):
    """Monitor outputs for one window, computed on device.

    Returns ``(curves, urd_sizes, write_ratios, cold_counts)`` —
    ``analyze_windows(pipeline="device")``'s backend.  One host sync (the
    fetch); bit-identical to the host monitor in f64 mode.  ``launch_hook``
    (fault injection) is invoked right before the fused program dispatch —
    after ingest, at the real launch boundary.  ``transfer_sanitize``
    (default off, bit-identical when on) runs dispatch + fetch under the
    ``transfer_sanitizer`` guard: any hidden host sync raises.
    """
    n = int(np.asarray(bounds).shape[0]) - 1
    n_acc = np.maximum(np.asarray(n_accesses, np.int64), 1)
    ing = ingest_window(addrs, is_read, bounds, n_accesses, rates=rates,
                        kind=kind, use_kernel=use_kernel, f64=f64,
                        profile=profile)
    if profile is not None:
        profile.end_window()
    if launch_hook is not None:
        launch_hook()
    if ing is None:
        return _trivial_monitor(n, n_acc)
    out = _dispatch_monitor(ing, profile, sanitize=transfer_sanitize)
    edges_p, hgt_p, kcnt, urd, wr = _fetch(ing, out, profile,
                                           sanitize=transfer_sanitize)
    curves = BatchedHitRatioFunctions.from_padded(
        edges_p, hgt_p, kcnt, ing.row_start, ing.n_acc)
    return (curves, np.asarray(urd, np.int64), np.asarray(wr, np.float64),
            ing.cold)


# --------------------------------------------------- fused decision pipeline
@dataclasses.dataclass(frozen=True)
class WindowDecision:
    """One Δt window's full control-plane decision (device-computed).

    ``latency`` is the Eq.-2 objective at ``sizes`` — reduction-order
    approximate vs ``aggregate_latency`` (see module doc); everything
    else is bit-identical to the host path in f64 mode.
    """

    sizes: np.ndarray
    write_ratios: np.ndarray
    urd_sizes: np.ndarray
    hit_ratios: np.ndarray
    latency: float
    feasible: bool
    curves: BatchedHitRatioFunctions


class DeviceWindowPipeline:
    """End-to-end fused window decisions with double-buffered ingest.

    ``run(traces)`` analyzes + partitions one window in a single device
    program; ``run_stream(windows)`` overlaps window t+1's host-side
    ingest and async transfer with window t's on-device analysis, paying
    one host sync per window (the decision fetch).
    """

    def __init__(self, capacity: int, t_fast: float = 1.0,
                 t_slow: float = 20.0, c_min: int = 0, kind: str = "urd",
                 weights: np.ndarray | None = None,
                 use_kernel: bool | None = None, f64: bool | None = None,
                 transfer_sanitize: bool = False, mesh=None):
        self.capacity = int(capacity)
        self.t_fast, self.t_slow = float(t_fast), float(t_slow)
        self.c_min = int(c_min)
        self.kind = kind
        self.weights = None if weights is None else np.asarray(weights,
                                                               np.float64)
        self.use_kernel = use_kernel
        self.f64 = _f64_default() if f64 is None else bool(f64)
        # default-off, bit-identical when on: window dispatch + fetch run
        # under jax.transfer_guard("disallow") so any hidden host sync
        # raises; the decision fetch stays legal (explicit device_get)
        self.transfer_sanitize = bool(transfer_sanitize)
        # default-off (None = this single-device pipeline, byte-identical
        # to pre-mesh behavior); a 1-D ("shards",) mesh routes every
        # window through the shard_map twin (core.shard_pipeline) with
        # per-shard async ingest and the budget cut replicated
        self.mesh = mesh

    # ------------------------------------------------------------ plumbing
    def _params(self, n: int) -> dict:
        idt, fdt = _np_dtypes(self.f64)
        w = np.ones(n) if self.weights is None else self.weights
        return {"capacity": idt(self.capacity), "c_min": idt(self.c_min),
                "weights": np.asarray(w, fdt), "t_fast": fdt(self.t_fast),
                "t_slow": fdt(self.t_slow)}

    def ingest(self, traces, profile: StageProfile | None = None):
        """Host prep + async transfer for one window of tenant traces."""
        n = len(traces)
        lens = np.array([len(t) for t in traces], dtype=np.int64)
        bounds = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
        if int(bounds[-1]):
            addrs = np.concatenate([t.addrs for t in traces])
            is_read = np.concatenate([t.is_read for t in traces])
        else:
            addrs = np.zeros(0, np.int64)
            is_read = np.zeros(0, bool)
        if self.mesh is not None:
            from repro.core.shard_pipeline import ingest_window_sharded
            ing = ingest_window_sharded(
                addrs, is_read, bounds, lens, mesh=self.mesh,
                kind=self.kind, use_kernel=self.use_kernel, f64=self.f64,
                profile=profile)
        else:
            ing = ingest_window(addrs, is_read, bounds, lens,
                                kind=self.kind, use_kernel=self.use_kernel,
                                f64=self.f64, profile=profile)
        return ing, n, np.maximum(lens, 1)

    def _dispatch(self, ing: WindowIngest,
                  profile: StageProfile | None = None):
        if self.mesh is not None:
            from repro.core.shard_pipeline import dispatch_decision_sharded
            return dispatch_decision_sharded(
                ing, self._params(ing.n), profile,
                sanitize=self.transfer_sanitize)
        progs = _programs(ing.key)
        p = self._params(ing.n)
        with transfer_sanitizer(self.transfer_sanitize), _x64(ing.f64):
            if self.transfer_sanitize:
                # under the guard the numpy params must cross explicitly
                # (inside the x64 scope so dtypes match the implicit path)
                p = jax.device_put(p)
            if profile is not None and profile.staged:
                with profile.stage("count"):
                    dist = progs["count"](ing.dev)
                    jax.block_until_ready(dist)
                    profile.sync()
                with profile.stage("curve"):
                    cur = progs["curve"](ing.dev, dist)
                    jax.block_until_ready(cur)
                    profile.sync()
                with profile.stage("write_ratio"):
                    wr = progs["wr"](ing.dev, dist)
                    jax.block_until_ready(wr)
                    profile.sync()
                with profile.stage("partition"):
                    part = progs["partition"](ing.dev, *cur[:4], p)
                    jax.block_until_ready(part)
                    profile.sync()
                return (*cur, wr, *part)
            with _pstage(profile, "dispatch"):
                return progs["decision"](ing.dev, p)

    def _trivial(self, n: int, n_acc: np.ndarray) -> WindowDecision:
        curves, urd, wr, _ = _trivial_monitor(n, n_acc)
        w = np.ones(n) if self.weights is None else self.weights
        lat = float(np.sum(w * self.t_slow))
        return WindowDecision(np.zeros(n, np.int64), wr, urd,
                              np.zeros(n, np.float64), lat, True, curves)

    def _finish(self, ing: WindowIngest, out,
                profile: StageProfile | None = None) -> WindowDecision:
        (edges_p, hgt_p, kcnt, urd, wr, sizes, h_at, lat, feas) = \
            _fetch(ing, out, profile, sanitize=self.transfer_sanitize)
        curves = BatchedHitRatioFunctions.from_padded(
            edges_p, hgt_p, kcnt, ing.row_start, ing.n_acc)
        if profile is not None:
            profile.end_window()
        return WindowDecision(np.asarray(sizes, np.int64),
                              np.asarray(wr, np.float64),
                              np.asarray(urd, np.int64),
                              np.asarray(h_at, np.float64),
                              float(lat), bool(feas), curves)

    # -------------------------------------------------------------- driving
    def run(self, traces, profile: StageProfile | None = None
            ) -> WindowDecision:
        ing, n, n_acc = self.ingest(traces, profile)
        if ing is None:
            if profile is not None:
                profile.end_window()
            return self._trivial(n, n_acc)
        out = self._dispatch(ing, profile)
        return self._finish(ing, out, profile)

    def run_stream(self, windows, profile: StageProfile | None = None
                   ) -> list[WindowDecision]:
        """Double-buffered window stream: ingest t+1 overlaps analysis t.

        Per iteration the *previous* window's program is already running
        on device; the next window's host-side layout/link work and its
        async ``device_put`` proceed under it, and only then is the
        previous decision fetched (the one sync).
        """
        results: list[WindowDecision] = []
        pending = None                  # (ingest, in-flight outputs)
        for traces in windows:
            ing, n, n_acc = self.ingest(traces, profile)
            nxt = None
            if ing is not None:
                nxt = (ing, self._dispatch(ing, profile))
            if pending is not None:
                results.append(self._finish(*pending, profile))
            if ing is None:
                if profile is not None:
                    profile.end_window()
                results.append(self._trivial(n, n_acc))
            pending = nxt
        if pending is not None:
            results.append(self._finish(*pending, profile))
        return results


# ------------------------------------------------- standalone device walk
_WALK_PROGRAMS: dict[tuple, object] = {}


def _walk_program(n: int, k_pad: int, f64: bool):
    key = (n, k_pad, f64)
    if key not in _WALK_PROGRAMS:
        wg = ((k_pad, 0, n * k_pad),)
        _WALK_PROGRAMS[key] = jax.jit(_make_walk(wg, n, n * k_pad, f64))
    return _WALK_PROGRAMS[key]


def greedy_walk_device(b: BatchedHitRatioFunctions, sizes: np.ndarray,
                       budget: int, w: np.ndarray, gain: float,
                       f64: bool | None = None) -> np.ndarray:
    """``partitioner._greedy_walk_fast`` on device (one jitted program).

    Pads the host curve store (0-heads stripped) to a uniform
    power-of-two breakpoint count per tenant and runs the jitted
    envelope-scan walk — ``greedy_allocate(method="device")``'s backend.
    Bit-identical grant order to the host walk in f64 mode.
    """
    if f64 is None:
        f64 = _f64_default()
    idt, fdt = _np_dtypes(f64)
    n = len(b)
    sizes = np.asarray(sizes, np.int64)
    if budget <= 0 or n == 0:
        return sizes
    k = np.maximum(np.diff(b.offsets) - 1, 0)        # drop the 0-heads
    kmax = int(k.max(initial=0))
    if kmax == 0:
        return sizes
    k_pad = 1 << (kmax - 1).bit_length()
    total = n * k_pad
    edges_p = np.zeros(total, np.int64)
    hgt_p = np.zeros(total, np.float64)
    tot_k = int(k.sum())
    if tot_k:
        rank = (np.arange(tot_k, dtype=np.int64)
                - np.repeat(np.cumsum(k) - k, k))
        src = np.repeat(b.offsets[:-1] + 1, k) + rank
        dst = np.repeat(np.arange(n, dtype=np.int64) * k_pad, k) + rank
        edges_p[dst] = b.edges[src]
        hgt_p[dst] = b.heights[src]
    walk = _walk_program(n, k_pad, bool(f64))
    with _x64(bool(f64)):
        out = walk(jnp.asarray(edges_p.astype(idt)),
                   jnp.asarray(hgt_p.astype(fdt)),
                   jnp.asarray(k.astype(idt)),
                   jnp.asarray(np.repeat(np.arange(n, dtype=np.int32),
                                         k_pad)),
                   jnp.asarray(np.tile(np.arange(k_pad, dtype=np.int32),
                                       n)),
                   jnp.asarray((np.arange(n, dtype=np.int64)
                                * k_pad).astype(idt)),
                   jnp.asarray(sizes.astype(idt)), idt(budget),
                   jnp.asarray(np.asarray(w, fdt)), fdt(gain))
        out = np.asarray(out)
    return out.astype(np.int64)
