"""Sharded Δt window pipeline: the fused device program over a 1-D mesh.

``core.device_pipeline`` keeps a whole window decision on one device;
this module partitions the padded, width-sorted segment tape across a
1-D ``("shards",)`` device mesh **by whole tenant-segments** and runs
exactly the same per-window jitted stages under ``shard_map``:

  * **Assignment** (``shard_assignment``): greedy width-balanced (LPT)
    placement of segments, walked in the tape's global descending-width
    order so each shard's sub-tape is again a descending sequence of
    power-of-two rows — prefix sums of descending pow2 widths are
    multiples of every following width, so every row stays self-aligned
    on its shard and the boundary-severing proof (links clamped at
    segment ends, pad/cross-segment dominance contributions cancel —
    see ``core.monitor``) applies *per shard*: counting needs no
    cross-device links.  The greedy max-shard load never exceeds 2× the
    optimal (load ≤ mean + w_max ≤ 2·max(mean, w_max)); pinned as a
    hypothesis invariant in the shard suite.
  * **Uniform stacked ingest** (``ingest_window_sharded``): shard_map
    needs one static per-shard structure, so each distinct width's row
    count is padded to its max across shards; surplus rows carry the
    ``padded_tape_links`` pad sentinels and a *trash tenant slot* ``n``
    (per-tenant arrays run length ``n+1``; the slot is dropped after the
    cross-shard reduction, so all-pad rows can never alias a real
    tenant's curve).  The whole ``[n_shards, S]`` tree ships in a single
    async ``jax.device_put`` with ``NamedSharding(mesh, P("shards"))``
    leaves — the per-shard async transfer that ``run_stream``'s double
    buffering overlaps with the previous window's analysis.
  * **One jitted program per shape bucket**: inside ``shard_map`` each
    shard runs the *identical* stage closures the single-device program
    jits (``device_pipeline._programs(...)["stages"]``) — SD counting,
    device curve build (SHARDS scaling included), write counts — on its
    own resident tape chunk; only the per-tenant summaries cross shards
    (integer ``lax.psum`` of breakpoint/URD/write counts — exact, since
    every tenant lives wholly on one shard and foreign shards contribute
    zeros) plus one ``lax.all_gather`` of the device-resident curve
    store (the envelope-walk input).  The budget cut — the existing
    envelope-scan walk + partition stage over the concatenated store —
    then runs once, replicated, at jit level, so the grant order and
    allocations are **bit-identical** to the fused host path (the walk
    is layout-order free: one total-order 3-key sort, row-local scans).
  * **Transfer contract**: ≤ 1 host sync per window *per mesh* — ingest
    is one explicit ``device_put``, the decision fetch one explicit
    ``device_get`` — enforced under ``transfer_sanitizer`` and asserted
    by the shard suite via ``StageProfile``.

``monitor_window_sharded`` backs ``analyze_windows(pipeline="sharded")``
and the manager's new top ladder rung (sharded → device → host → solo);
``DeviceWindowPipeline(mesh=...)`` routes its fused decisions (and
``run_stream``) through here.  Default-off everywhere: without a mesh /
with ``pipeline != "sharded"`` nothing in this module ever runs.  On CPU
hosts the harness forces ``XLA_FLAGS=--xla_force_host_platform_device_
count=8`` so tests and CI exercise real multi-device semantics.
"""
from __future__ import annotations

import dataclasses
import heapq

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.batch_sim import padded_segment_layout, padded_tape_links
from repro.core.device_pipeline import (_f64_default, _fetch, _np_dtypes,
                                        _programs, _trivial_monitor, _x64,
                                        transfer_sanitizer)
from repro.core.mrc import BatchedHitRatioFunctions
from repro.core.stage_profile import StageProfile, _pstage
from repro.kernels.cache_sim.ops import _on_tpu

__all__ = ["ShardIngest", "ShardLayout", "dispatch_decision_sharded",
           "ingest_window_sharded", "monitor_window_sharded",
           "shard_assignment", "uniform_shard_layout"]

_AXIS = "shards"
_TAPE_KEYS = ("gprev", "gnxt", "gocc", "gread", "gtid", "grank", "row_tids")
_REP_KEYS = ("rates", "n_acc", "wr_den")


# ----------------------------------------------------------- shard placement
def shard_assignment(widths: np.ndarray, n_shards: int) -> np.ndarray:
    """Greedy width-balanced (LPT) shard per padded row.

    ``widths`` are the layout's padded row widths in descending order;
    each row goes to the currently lightest shard (ties → lowest index),
    so every shard's row subsequence stays descending (self-alignment)
    and ``max_load <= mean + w_max <= 2 * max(mean, w_max)`` — within 2×
    of the optimal max-shard width.
    """
    n_shards = int(n_shards)
    assign = np.empty(widths.shape[0], dtype=np.int64)
    heap = [(0, s) for s in range(n_shards)]    # (load, shard); ties → low s
    heapq.heapify(heap)
    for r, w in enumerate(widths):
        load, s = heapq.heappop(heap)
        assign[r] = s
        heapq.heappush(heap, (load + int(w), s))
    return assign


@dataclasses.dataclass(frozen=True)
class ShardLayout:
    """The uniform per-shard tape structure (identical on every shard).

    ``shard_wg`` is the per-shard ``width_groups_of``-style structure
    (every distinct width padded to its max row count over shards — the
    static shape shard_map requires); ``entry_base``/``row_index`` map
    each *global* layout row to its local slot on its assigned shard.
    """

    uwidths: np.ndarray        # distinct pow2 widths, descending
    rcap: np.ndarray           # rows per width in the uniform layout
    size: int                  # per-shard padded tape length S
    rows: int                  # per-shard row count R
    shard_wg: tuple            # ((w, lo, hi), ...) over [0, S)
    entry_base: np.ndarray     # int64[g] local entry offset per global row
    row_index: np.ndarray      # int64[g] local row index per global row


def uniform_shard_layout(widths: np.ndarray, assign: np.ndarray,
                         n_shards: int) -> ShardLayout:
    """Place every assigned row into the uniform per-shard structure."""
    widths = np.asarray(widths, np.int64)
    neg_u, inv = np.unique(-widths, return_inverse=True)
    uw = (-neg_u).astype(np.int64)               # descending distinct widths
    per = np.zeros((int(n_shards), uw.size), np.int64)
    np.add.at(per, (assign, inv), 1)
    rcap = per.max(axis=0)
    blk_entry = np.concatenate([[0], np.cumsum(rcap * uw)[:-1]]
                               ).astype(np.int64)
    blk_row = np.concatenate([[0], np.cumsum(rcap)[:-1]]).astype(np.int64)
    # arrival order per (shard, width) — rows walked in global descending
    # order, so the k-th arrival takes the block's k-th slot
    key = assign * uw.size + inv
    order = np.argsort(key, kind="stable")
    sk = key[order]
    starts = np.flatnonzero(np.concatenate([[True], sk[1:] != sk[:-1]]))
    runs = np.diff(np.append(starts, sk.size))
    seq = np.empty(sk.size, np.int64)
    seq[order] = np.arange(sk.size, dtype=np.int64) - np.repeat(starts, runs)
    shard_wg = tuple((int(w), int(lo), int(lo + int(c) * int(w)))
                     for w, lo, c in zip(uw, blk_entry, rcap))
    return ShardLayout(uw, rcap, int(np.sum(rcap * uw)), int(rcap.sum()),
                       shard_wg, blk_entry[inv] + seq * widths,
                       blk_row[inv] + seq)


# ------------------------------------------------------------------- ingest
@dataclasses.dataclass
class ShardIngest:
    """One window's mesh-resident stacked tape + host-side metadata.

    Mirrors ``device_pipeline.WindowIngest``; ``dev`` holds three trees —
    ``tape`` ([n_shards, S] leaves, sharded over the mesh), ``rep``
    (replicated length-``n+1`` per-tenant inputs with the trash slot) and
    ``geo`` (replicated concatenated-store coordinates for the budget
    cut).  ``row_start`` is already in concatenated-store coordinates so
    the host curve reassembly is the same ``from_padded`` gather.
    """

    key: tuple
    dev: dict
    n: int
    total: int                 # concatenated store length n_shards * S
    f64: bool
    row_start: np.ndarray      # int64[n] concatenated-store row base
    n_acc: np.ndarray
    cold: np.ndarray
    mesh: object
    n_shards: int
    shard_size: int


def ingest_window_sharded(addrs: np.ndarray, is_read: np.ndarray,
                          bounds: np.ndarray, n_accesses: np.ndarray, *,
                          mesh, rates: np.ndarray | None = None,
                          kind: str = "urd", use_kernel: bool | None = None,
                          f64: bool | None = None,
                          profile: StageProfile | None = None
                          ) -> ShardIngest | None:
    """Host half of the sharded pipeline: layout + links + shard placement
    + one async mesh-wide ``device_put`` of the stacked tape.

    Same contract as ``device_pipeline.ingest_window`` (returns ``None``
    for an all-empty window); the extra work is the greedy assignment and
    the scatter of every row into its shard-local slot (links shift by a
    per-row constant — they are clamped within the row, so relative
    comparisons, and therefore counts, are unchanged).
    """
    from repro.core.monitor import _segment_links
    bounds = np.asarray(bounds, np.int64)
    n = bounds.shape[0] - 1
    if use_kernel is None:
        use_kernel = _on_tpu()
    if f64 is None:
        f64 = _f64_default()
    idt, fdt = _np_dtypes(f64)
    n_shards = int(mesh.devices.size)
    with _pstage(profile, "ingest"):
        lens_sub = np.diff(bounds)
        tid = np.repeat(np.arange(n, dtype=np.int64), lens_sub)
        layout = padded_segment_layout(bounds)
        src, tpos, base_src, base_pad, widths, total, seg_starts = layout
        if n == 0 or total == 0:
            return None
        assign = shard_assignment(widths, n_shards)
        lay = uniform_shard_layout(widths, assign, n_shards)
        S = lay.size
        if not f64 and S * (S + 2) >= 2**31 and not use_kernel:
            raise ValueError(
                "sharded pipeline: f64=False limits the merge-sort-tree "
                f"counting oracle to shard tapes with S*(S+2) < 2^31 "
                f"(got S={S}); use f64=True or the TPU kernel")
        prev, nxt_c = _segment_links(addrs, tid, bounds, layout)
        gprev, gnxt, gocc = padded_tape_links(prev, nxt_c, layout)
        src_eff = (src if src is not None
                   else np.arange(addrs.shape[0], dtype=np.int64))
        gread = np.zeros(total, bool)
        gread[tpos] = is_read[src_eff]
        row_base = np.concatenate([[0], np.cumsum(widths)[:-1]]
                                  ).astype(np.int64)
        row_tids = (np.searchsorted(bounds, seg_starts, side="right")
                    - 1).astype(np.int64)
        n_acc = np.maximum(np.asarray(n_accesses, np.int64), 1)
        cold = np.bincount(tid[prev < 0], minlength=n).astype(np.int64)
        # templates: surplus (all-pad) rows carry the padded_tape_links
        # sentinels and the trash tenant slot n
        u_widths = np.repeat(lay.uwidths, lay.rcap)
        u_base = np.concatenate([[0], np.cumsum(u_widths)[:-1]]
                                ).astype(np.int64)
        tape = {
            "gprev": np.full((n_shards, S), -1, np.int32),
            "gnxt": np.tile(np.arange(S, dtype=np.int32), (n_shards, 1)),
            "gocc": np.zeros((n_shards, S), np.int32),
            "gread": np.zeros((n_shards, S), bool),
            "gtid": np.full((n_shards, S), n, np.int32),
            "grank": np.tile((np.arange(S, dtype=np.int64)
                              - np.repeat(u_base, u_widths)
                              ).astype(np.int32), (n_shards, 1)),
            "row_tids": np.full((n_shards, lay.rows), n, np.int32),
        }
        # scatter real rows: links are row-internal, so one constant shift
        # per row relocates them exactly; grank is shift-invariant and the
        # template already matches
        rows_e = np.repeat(np.arange(widths.size, dtype=np.int64), widths)
        shift_e = (lay.entry_base - row_base)[rows_e]
        sh_e = assign[rows_e]
        dst_e = np.arange(total, dtype=np.int64) + shift_e
        tape["gprev"][sh_e, dst_e] = np.where(gprev >= 0, gprev + shift_e,
                                              -1).astype(np.int32)
        tape["gnxt"][sh_e, dst_e] = (gnxt + shift_e).astype(np.int32)
        tape["gocc"][sh_e, dst_e] = gocc.astype(np.int32)
        tape["gread"][sh_e, dst_e] = gread
        tape["gtid"][sh_e, dst_e] = np.repeat(row_tids,
                                              widths).astype(np.int32)
        tape["row_tids"][assign, lay.row_index] = row_tids.astype(np.int32)
        rates_t = (np.ones(n, fdt) if rates is None
                   else np.asarray(rates, fdt))
        rep = {
            "rates": np.concatenate([rates_t, np.ones(1, fdt)]),
            "n_acc": np.concatenate([n_acc, [1]]).astype(idt),
            "wr_den": np.concatenate([np.maximum(lens_sub, 1),
                                      [1]]).astype(idt),
        }
        row_start_cat = np.zeros(n + 1, np.int64)
        row_start_cat[row_tids] = assign * S + lay.entry_base
        geo = {
            "gtid": np.ascontiguousarray(tape["gtid"].reshape(-1)),
            "grank": np.ascontiguousarray(tape["grank"].reshape(-1)),
            "row_start": row_start_cat.astype(idt),
        }
        key = (lay.shard_wg, n_shards, n, rates is not None, kind,
               bool(use_kernel), bool(f64), mesh)
        shardings = ({k: NamedSharding(mesh, P(_AXIS)) for k in tape},
                     {k: NamedSharding(mesh, P()) for k in rep},
                     {k: NamedSharding(mesh, P()) for k in geo})
        with _x64(f64):
            # one async mesh-wide transfer: window t+1's stacked put
            # overlaps window t's on-device analysis under run_stream
            dev_tape, dev_rep, dev_geo = jax.device_put((tape, rep, geo),
                                                        shardings)
    return ShardIngest(key, {"tape": dev_tape, "rep": dev_rep,
                             "geo": dev_geo}, n, n_shards * S, bool(f64),
                       row_start_cat[:n].copy(), n_acc, cold, mesh,
                       n_shards, S)


# ----------------------------------------------------------------- programs
_SHARD_PROGRAMS: dict[tuple, dict] = {}


def _shard_programs(key: tuple) -> dict:
    """Build (and cache) the sharded window programs for one shape bucket.

    Per-shard work re-traces the single-device stage closures
    (``device_pipeline._programs(...)["stages"]``) inside the shard_map
    body; only integer per-tenant summaries are ``psum``-reduced (exact —
    each tenant is whole on one shard, foreign shards add zeros) and the
    curve store ``all_gather``-ed for the single replicated budget cut.
    """
    if key in _SHARD_PROGRAMS:
        return _SHARD_PROGRAMS[key]
    shard_wg, n_shards, n, sampled, kind, use_kernel, f64, mesh = key
    S = shard_wg[-1][2]
    n1 = n + 1
    idt = jnp.int64 if f64 else jnp.int32
    per = _programs((shard_wg, n1, sampled, kind, use_kernel,
                     f64))["stages"]
    # the replicated partition walks the concatenated store: the shard
    # structure repeated per mesh position (all_gather order)
    wg_cat = tuple((w, s * S + lo, s * S + hi)
                   for s in range(n_shards) for (w, lo, hi) in shard_wg)
    part = _programs((wg_cat, n1, sampled, kind, use_kernel,
                      f64))["stages"]["partition"]
    tape_specs = {k: P(_AXIS) for k in _TAPE_KEYS}
    rep_specs = {k: P() for k in _REP_KEYS}

    def shard_body(tape, rep):
        d = {k: v[0] for k, v in tape.items()}      # drop the block axis
        d.update(rep)
        dist = per["count"](d)
        edges_p, hgt_p, kcnt, urd = per["curve"](d, dist)
        wflag = ((dist >= 0) & (~d["gread"])).astype(idt)
        wcnt = jnp.zeros(n1, idt).at[d["gtid"]].add(wflag)
        # integer summaries reduce exactly; the curve store stays device-
        # resident and only concatenates for the replicated walk
        return (lax.all_gather(edges_p, _AXIS).reshape(-1),
                lax.all_gather(hgt_p, _AXIS).reshape(-1),
                lax.psum(kcnt, _AXIS), lax.psum(urd, _AXIS),
                lax.psum(wcnt, _AXIS))

    smap = jax.shard_map(shard_body, mesh=mesh,
                         in_specs=(tape_specs, rep_specs),
                         out_specs=(P(),) * 5, check_vma=False)

    def monitor_core(tape, rep):
        edges_c, hgt_c, kcnt, urd, wcnt = smap(tape, rep)
        wr = wcnt / rep["wr_den"]                   # one division, exact
        return edges_c, hgt_c, kcnt[:n], urd[:n], wr[:n]

    def decision_core(tape, rep, geo, p):
        edges_c, hgt_c, kcnt, urd, wcnt = smap(tape, rep)
        wr = wcnt / rep["wr_den"]
        # the single replicated step: budget cut + envelope walk over the
        # gathered store — bit-identical grant order to the host walk
        sizes, h_at, lat, feas = part(geo, edges_c, hgt_c, kcnt, urd, p)
        return (edges_c, hgt_c, kcnt[:n], urd[:n], wr[:n],
                sizes[:n], h_at[:n], lat, feas)

    progs = {"monitor": jax.jit(monitor_core),
             "decision": jax.jit(decision_core)}
    _SHARD_PROGRAMS[key] = progs
    return progs


# --------------------------------------------------------------- dispatch
def _dispatch_monitor_sharded(ing: ShardIngest,
                              profile: StageProfile | None,
                              sanitize: bool = False):
    progs = _shard_programs(ing.key)
    with transfer_sanitizer(sanitize), _x64(ing.f64):
        with _pstage(profile, "dispatch"):
            return progs["monitor"](ing.dev["tape"], ing.dev["rep"])


def dispatch_decision_sharded(ing: ShardIngest, params: dict,
                              profile: StageProfile | None = None,
                              sanitize: bool = False):
    """Launch the fused sharded decision (DeviceWindowPipeline backend).

    ``params`` are the single-device ``_params`` dict; the weights gain
    the trash slot (weight 0, so the pad tenant never contributes to the
    latency objective).  Always fused — the sharded program has no staged
    per-launch mode (``StageProfile.staged`` is ignored here).
    """
    progs = _shard_programs(ing.key)
    p = dict(params)
    w = np.asarray(params["weights"])
    p["weights"] = np.concatenate([w, np.zeros(1, w.dtype)])
    with transfer_sanitizer(sanitize), _x64(ing.f64):
        if sanitize:
            # under the guard the numpy params must cross explicitly —
            # replicated over the mesh, or the launch would need a
            # (guarded) device-to-device broadcast
            p = jax.device_put(p, NamedSharding(ing.mesh, P()))
        with _pstage(profile, "dispatch"):
            return progs["decision"](ing.dev["tape"], ing.dev["rep"],
                                     ing.dev["geo"], p)


def monitor_window_sharded(addrs: np.ndarray, is_read: np.ndarray,
                           bounds: np.ndarray, n_accesses: np.ndarray, *,
                           mesh=None, rates: np.ndarray | None = None,
                           kind: str = "urd",
                           use_kernel: bool | None = None,
                           f64: bool | None = None,
                           profile: StageProfile | None = None,
                           launch_hook=None,
                           transfer_sanitize: bool = False):
    """Monitor outputs for one window, computed across the mesh.

    ``analyze_windows(pipeline="sharded")``'s backend; same signature
    and return contract as ``monitor_window_device`` plus ``mesh``
    (default: ``distributed.sharding.control_plane_mesh()`` over every
    local device).  One host sync per window per mesh (the fetch);
    bit-identical to the host monitor in f64 mode at any shard count.
    """
    if mesh is None:
        from repro.distributed.sharding import control_plane_mesh
        mesh = control_plane_mesh()
    n = int(np.asarray(bounds).shape[0]) - 1
    n_acc = np.maximum(np.asarray(n_accesses, np.int64), 1)
    ing = ingest_window_sharded(addrs, is_read, bounds, n_accesses,
                                mesh=mesh, rates=rates, kind=kind,
                                use_kernel=use_kernel, f64=f64,
                                profile=profile)
    if profile is not None:
        profile.end_window()
    if launch_hook is not None:
        launch_hook()
    if ing is None:
        return _trivial_monitor(n, n_acc)
    out = _dispatch_monitor_sharded(ing, profile, sanitize=transfer_sanitize)
    edges_c, hgt_c, kcnt, urd, wr = _fetch(ing, out, profile,
                                           sanitize=transfer_sanitize)
    curves = BatchedHitRatioFunctions.from_padded(
        edges_c, hgt_c, kcnt, ing.row_start, ing.n_acc)
    return (curves, np.asarray(urd, np.int64), np.asarray(wr, np.float64),
            ing.cold)
