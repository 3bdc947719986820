"""Monitor → Analyzer → Actuator loop (paper Fig. 8, Alg. 1/3/4).

``ECICacheManager`` is the hypervisor-level controller:

  * ``Monitor``  — accumulates per-tenant (addr, r/w) events for the current
    Δt window (the paper's modified-blktrace).
  * ``Analyzer`` — at window boundaries computes URD (or TRD for baselines),
    builds H_i(c), estimates URD-based sizes, checks feasibility, and — when
    infeasible — runs the Eq.-2 partitioner; also assigns write policies
    (Alg. 3).
  * ``Actuator`` — applies the decisions: resizes per-tenant LRU partitions
    (evicting LRU-first on shrink) and switches write policies; keeps the
    Map Table (block residency) implicitly through the per-tenant caches.

The same class drives both the trace-replay benchmarks and the live paged-KV
serving engine (see ``repro.cache.tiered`` which feeds events back here).

Failure domains & the graceful-degradation ladder
-------------------------------------------------
With a ``FaultPlan`` attached (``faults=``) — or ``fault_tolerant=True`` —
the manager survives control-plane and tier failures instead of crashing or
actuating garbage.  Everything below is **default-off and bit-identical
when off** (the checked-in goldens enforce this):

  * **Monitor ladder.**  Each analyze walks the rungs
    ``device → fused host → per-tenant`` : the configured pipeline runs
    first with up to ``retry_limit`` retries per rung (exponential backoff
    ``backoff_base * 2**attempt`` seconds, 0 = no sleep), a failed device
    rung steps down to the fused host pass (``device_stepdowns``), a failed
    host rung to per-tenant solo passes (``host_stepdowns``) where a single
    poisoned tenant can no longer take the whole deployment's analyze down
    (failed solo tenants are individually quarantined at their
    last-known-good size/policy).  If every rung fails, the manager
    re-applies the **last-known-good decision** (``lkg_decisions``).
  * **Decision guard.**  Every decision — degraded or not — is validated
    against ``repro.core.guard`` hard invariants (Σsizes ≤ capacity, c_min
    floors, finite curves/latency, policies ∈ {WB, WT, RO}).  A tolerant
    manager retries a *sampled* analyze once exactly
    (``sampled_exact_retries``) and otherwise quarantines the decision
    (``guard_quarantines``) behind the last-known-good allocation; an
    intolerant manager counts it (``guard_violations_actuated``) so silent
    garbage still surfaces in ``summary()``.
  * **Ingest validation.**  Malformed tapes raise ``TraceError`` with
    (tenant, window) coordinates; a tolerant ``run_window`` quarantines the
    offending tenant-window (empty tape, held at last-known-good —
    ``poisoned_windows``) instead of raising.  Straggler tapes
    (``FaultPlan`` ``"straggler"``) hold the tenant out of this window's
    analyze and fold the deferred tape into the next one
    (``straggler_windows``).
  * **Tier loss + write-policy demotion (the paper-faithful part).**
    ``fail_tier(level)`` / ``note_tier_loss`` drop the level's residents —
    lost dirty blocks are counted in ``dirty_loss`` (the reliability cost
    the paper's Alg. 3 restricts WB to bound) — and every WB tenant on the
    failed level is demoted to ``demote_policy`` (default WT: hits without
    dirty-loss exposure) for the outage **plus ``demote_cooldown`` analyzes
    after recovery**, after which Alg. 3 reassigns policies normally.
    While a level is down its partition budget is 0 and the partitioner
    degrades to ``greedy_allocate`` (the box-projected PGD solver cannot
    express an empty budget).  Reconvergence: decisions depend only on the
    current window's tape and the restored capacities, so a recovered
    manager matches the no-fault run within
    ``K = demote_cooldown + 2`` windows of the last fault — gated in
    ``benchmarks/bench_faults.py`` and the chaos suite.

Every degradation is recorded as a ``DegradeEvent`` in the shared
``events`` deque (alongside ``ReconfigEvent``) and counted once in
``summary()``.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable

import numpy as np

from repro.core.batch_sim import simulate_many
from repro.core.characterize import PhaseDetector, characterize_windows
from repro.core.faults import FaultPlan, InjectedFault
from repro.core.guard import validate_decision
from repro.core.monitor import MonitorResult, analyze_windows
from repro.core.mrc import HitRatioFunction
from repro.core.partitioner import (PartitionResult, greedy_allocate,
                                    pgd_solve, two_level_solve)
from repro.core.simulator import LRUCache, SimResult, simulate
from repro.core.stage_profile import StageProfile, _pstage
from repro.core.trace import (Trace, TraceError, validate_trace,
                              validate_trace_arrays)
from repro.core.write_policy import WritePolicy

__all__ = ["TenantState", "AnalyzerDecision", "ReconfigEvent",
           "DegradeEvent", "ECICacheManager"]


@dataclasses.dataclass
class TenantState:
    name: str
    cache: LRUCache
    policy: WritePolicy = WritePolicy.WB        # paper: WB initially
    h_fn: HitRatioFunction | None = None
    urd_size: int = 0
    window_addrs: list[np.ndarray] = dataclasses.field(default_factory=list)
    window_reads: list[np.ndarray] = dataclasses.field(default_factory=list)
    result: SimResult = dataclasses.field(default_factory=SimResult)
    active: bool = True                         # finished tenants are excluded
    # second hierarchy level (ETICA): host-DRAM partition + its policy
    cache2: LRUCache = dataclasses.field(
        default_factory=lambda: LRUCache(0))
    policy2: WritePolicy = WritePolicy.WB

    def window_trace(self) -> Trace:
        if not self.window_addrs:
            return Trace(np.zeros(0, np.int64), np.zeros(0, bool), self.name)
        return Trace(np.concatenate(self.window_addrs),
                     np.concatenate(self.window_reads), self.name)

    def clear_window(self) -> None:
        self.window_addrs.clear()
        self.window_reads.clear()


@dataclasses.dataclass(frozen=True)
class ReconfigEvent:
    """Why the Analyzer ran (event-driven mode telemetry).

    reason: "phase" (detector score crossed ``hi``), "write_ratio"
    (Alg.-3 threshold crossing), "interval" (the fixed-Δt fallback
    clock), "join" / "retire" (tenant churn).  ``tenant`` is the manager
    index, -1 for deployment-wide triggers.
    """

    window: int
    tenant: int
    reason: str
    score: float = 0.0


@dataclasses.dataclass(frozen=True)
class DegradeEvent:
    """One graceful-degradation action (fault-tolerance telemetry).

    Lives in the same ``events`` deque as ``ReconfigEvent`` (same
    ``window``/``tenant``/``reason`` consumer contract).  reason:
    "tier_loss" / "tier_recover" (``level``, ``blocks`` = lost dirty
    blocks), "stepdown" (``rung`` = the rung that failed), "straggler",
    "poisoned" (quarantined tenant-window), "tenant_quarantine" (solo
    analyze failed), "guard_quarantine", "monitor_outage" (all rungs
    failed → last-known-good).  ``tenant`` is -1 for deployment-wide
    events.
    """

    window: int
    tenant: int
    reason: str
    level: int = 0
    blocks: int = 0
    rung: str = ""


@dataclasses.dataclass(frozen=True)
class AnalyzerDecision:
    sizes: np.ndarray
    policies: list[WritePolicy]
    feasible: bool
    partition: PartitionResult
    # per-level extension (all None/zeros for a single-level manager)
    sizes2: np.ndarray | None = None
    policies2: list[WritePolicy] | None = None
    partition2: PartitionResult | None = None
    # event-driven mode: what triggered this analyze (empty on fixed-Δt)
    trigger: tuple[ReconfigEvent, ...] = ()
    # fault tolerance (all defaults on the healthy path): ``quarantined``
    # marks a last-known-good fallback (sizes are the *current*
    # allocations, not a fresh solve), ``guard`` the guard violations that
    # were detected (non-empty + not quarantined = actuated violation,
    # intolerant managers only), ``degraded`` the degradation reason,
    # ``held`` tenants excluded from this analyze (kept at current
    # size/policy), ``deferred`` tenants whose window tape the Actuator
    # must NOT clear (stragglers: it joins the next analyze).
    quarantined: bool = False
    guard: tuple[str, ...] = ()
    degraded: str = ""
    held: tuple[int, ...] = ()
    deferred: tuple[int, ...] = ()


class ECICacheManager:
    """Dynamic per-tenant cache sizing (URD) + write-policy assignment.

    Parameters mirror the paper's setup: ``capacity`` in blocks, ``c_min``
    initial/minimum per-tenant blocks (paper: 1000), ``w_threshold`` for
    Alg. 3 (paper sweeps 0.2–0.9, default 0.5), ``t_fast``/``t_slow`` the
    SSD/HDD (here HBM/host-tier) service times.

    ``rd_kind='trd'`` + ``adaptive_policy=False`` turns this manager into the
    **Centaur** baseline (TRD sizing, WB everywhere) — see ``baselines.py``.

    ``engine`` selects the window-replay path: ``"batch"`` (default) replays
    every tenant's window at once through the vectorized stack-distance
    engine (``repro.core.batch_sim``, exact — the Analyzer additionally
    reuses its counting pass for the reuse distances), ``"lru"`` the
    stateful per-access interpreter.  Both produce identical results.

    ``capacity2 > 0`` turns the managed partitions into ETICA-style
    two-level hierarchies: each tenant owns an L1 (HBM) *and* an L2
    (host-DRAM) LRU partition, the Analyzer sizes both levels
    (``two_level_solve``: the level-2 Eq. 2 runs on the residual hit-ratio
    curves with service time ``t_fast2``) and assigns a per-level write
    policy (Alg. 3 at ``w_threshold`` for L1, the stricter ``w_threshold2``
    for L2 — a clean L2 flushes dirty victims at demotion).  With the
    default ``capacity2 == 0`` everything reduces bit-identically to the
    single-level scheme.

    ``sample_rate`` selects the Monitor's SHARDS spatial sampling: ``None``
    (exact), a float rate, or ``"auto"`` (per-tenant rate tuned to
    ``sample_target`` kept accesses, floored at ``sample_floor`` — see
    ``auto_sample_rate``).  Deployments with at least
    ``auto_sample_tenants`` tenants default to ``"auto"`` when
    ``sample_rate`` is left ``None`` — at thousand-tenant scale the control
    plane monitors on sampled traces by default, with per-tenant error bars
    reported by the monitor; smaller setups (every paper-figure
    reproduction) stay exact and bit-identical.  Either way the whole
    Analyzer runs through the fused batched monitor
    (``repro.core.monitor.analyze_windows``): one stack-distance pass and
    batched curve/write-ratio reductions for all tenants, no per-tenant
    Python loop.

    ``history_limit`` bounds the retained ``AnalyzerDecision`` list (a
    long-running serving deployment analyzes every Δt forever; unbounded
    history is a leak).  ``None`` keeps everything.  The same limit bounds
    the ``events`` reconfiguration log.

    ``phase_detect=True`` turns on ReCA-style event-driven
    reconfiguration (default **off**; with it off every code path is
    bit-identical to the fixed-Δt manager): each replayed window is
    characterized (``repro.core.characterize``, reusing the batch
    engine's window reuse distances so the feature pass adds no second
    pass over the trace) and a hysteresis ``PhaseDetector`` scores every
    tenant.  The Analyzer then runs only when (a) a tenant changes phase,
    (b) a tenant's Alg.-3 write ratio crosses ``w_threshold`` (the policy
    flip must not wait for the clock), (c) a tenant joins or retires, or
    (d) ``reconfig_interval`` windows have accumulated since the last
    analyze (the fixed-Δt fallback clock; 1 analyzes every window).
    Windows between analyzes accumulate in the Monitor, so a triggered
    analyze sees the full access history since the last decision.  Every
    trigger is recorded as a ``ReconfigEvent`` in ``events`` (bounded by
    ``history_limit``) and on the resulting decision's ``trigger`` field.
    ``phase_hi``/``phase_lo``/``phase_ema`` parameterize the detector's
    hysteresis thresholds and baseline EMA.

    ``faults``/``fault_tolerant`` arm the graceful-degradation machinery
    (default off, bit-identical when off — see the module docstring for
    the full failure-domain model).  Ladder order is
    ``device → fused host → per-tenant``; each non-terminal rung gets
    ``retry_limit`` retries with ``backoff_base * 2**attempt`` seconds of
    backoff (0 = no sleep, capped at 1 s).  On a tier loss every WB tenant
    of that level demotes to ``demote_policy`` (default WT) for the outage
    plus ``demote_cooldown`` further analyzes after recovery — the paper's
    reliability rationale: WB buffers dirty data that a cache-device crash
    loses (counted in ``dirty_loss``), so a tier with a fresh failure
    history must serve writes through a clean policy until trust is
    re-established.

    ``profile`` (a ``StageProfile``, default off) records every window's
    stages, counters and host syncs, and puts each stage on the
    profiler's host plane (see ``run_window``).
    """

    def __init__(self, capacity: int, tenant_names: list[str],
                 c_min: int = 1000, w_threshold: float = 0.5,
                 t_fast: float = 1.0, t_slow: float = 20.0,
                 t_write_bypass: float | None = None, flush_cost: float = 0.0,
                 rd_kind: str = "urd",
                 # the adaptive write policy IS the paper's ECI scheme
                 # (Alg. 2) — shipping it on is the reproduction contract;
                 # the off-path is the Centaur/static baselines, pinned
                 # bit-identical in test_baselines.
                 adaptive_policy: bool = True,  # repro-lint: disable=RL003
                 sample_rate: float | str | None = None,
                 initial_blocks: int | None = None,
                 percentile: float = 100.0,
                 partition_fn: Callable = pgd_solve,
                 engine: str = "batch",
                 capacity2: int = 0, t_fast2: float | None = None,
                 w_threshold2: float = 0.3,
                 history_limit: int | None = 256,
                 sample_target: int = 4096, sample_floor: int = 256,
                 auto_sample_tenants: int = 256,
                 phase_detect: bool = False, reconfig_interval: int = 1,
                 phase_hi: float = 0.25, phase_lo: float = 0.10,
                 phase_ema: float = 0.5, pipeline: str = "host",
                 faults: FaultPlan | None = None,
                 fault_tolerant: bool | None = None,
                 retry_limit: int = 2, backoff_base: float = 0.0,
                 demote_cooldown: int = 2,
                 demote_policy: WritePolicy | str = WritePolicy.WT,
                 profile: StageProfile | None = None):
        if engine not in ("batch", "lru"):
            raise ValueError(f"engine must be 'batch' or 'lru', got {engine!r}")
        if pipeline not in ("host", "device", "sharded"):
            raise ValueError(f"pipeline must be 'host', 'device' or "
                             f"'sharded', got {pipeline!r}")
        if pipeline != "host" and percentile < 100.0:
            raise ValueError(f"pipeline={pipeline!r} computes URD sizes "
                             "from the curve store (percentile=100); use "
                             "pipeline='host' for percentile < 100")
        self.capacity = int(capacity)
        self.capacity2 = int(capacity2)
        self.c_min = int(c_min)
        self.w_threshold = float(w_threshold)
        self.w_threshold2 = float(w_threshold2)
        self.t_fast, self.t_slow = float(t_fast), float(t_slow)
        self.t_fast2 = (3.0 * t_fast if t_fast2 is None else float(t_fast2))
        self.t_write_bypass = (1.2 * t_fast if t_write_bypass is None
                               else float(t_write_bypass))
        self.flush_cost = float(flush_cost)
        self.rd_kind = rd_kind
        self.adaptive_policy = adaptive_policy
        self.sample_rate = sample_rate
        self.sample_target = int(sample_target)
        self.sample_floor = int(sample_floor)
        self.auto_sample_tenants = int(auto_sample_tenants)
        self.percentile = percentile
        self.partition_fn = partition_fn
        self.engine = engine
        # "device" routes each analyze through the fused device window
        # program (core.device_pipeline), "sharded" through its mesh twin
        # (core.shard_pipeline); both need percentile == 100
        self.pipeline = pipeline
        init = int(initial_blocks if initial_blocks is not None else c_min)
        self.tenants = [TenantState(n, LRUCache(init)) for n in tenant_names]
        self.history: collections.deque[AnalyzerDecision] = \
            collections.deque(maxlen=history_limit)
        self.windows_analyzed = 0       # also salts the SHARDS hash per window
        self.tenant_windows = 0         # replayed tenant-windows (denominator)
        # event-driven reconfiguration (ReCA-style; default off = exact
        # pre-existing fixed-Δt behavior, analyze every window)
        self.reconfig_interval = max(int(reconfig_interval), 1)
        self.detector = (PhaseDetector(
            hi=phase_hi, lo=phase_lo, ema=phase_ema,
            w_threshold=(w_threshold if adaptive_policy else None))
            if phase_detect else None)
        self.events: collections.deque[ReconfigEvent] = \
            collections.deque(maxlen=history_limit)
        self.reconfig_events = 0        # total events ever (deque is bounded)
        self.windows_run = 0            # run_window calls (≥ windows_analyzed)
        self._pending_windows = 0       # replayed but not yet analyzed
        self._prev_sets: dict[int, np.ndarray] = {}   # drift continuity
        self._joined: list[int] = []    # tenants added since last window
        # interpreter-fallback tenant-windows: since the two-level RO
        # eviction-token replay this counts only genuinely degenerate
        # windows (empty two-level windows / warm L2 behind a dead level);
        # CI asserts it stays 0 on the standard two-level bench mixes
        self.ro_fallback_windows = 0
        # ---------------- fault tolerance (see the module docstring) ------
        # ``faults`` injects; ``fault_tolerant`` arms the ladder/guard/
        # quarantine machinery (defaults on exactly when a plan is
        # attached).  With both off every path above is bit-identical to
        # the pre-fault-tolerance manager.
        self.faults = faults
        self.fault_tolerant = (faults is not None if fault_tolerant is None
                               else bool(fault_tolerant))
        self.retry_limit = max(int(retry_limit), 0)
        self.backoff_base = float(backoff_base)
        self.demote_cooldown = max(int(demote_cooldown), 0)
        self.demote_policy = (WritePolicy(demote_policy)
                              if isinstance(demote_policy, str)
                              else demote_policy)
        self._down_levels: set[int] = set()       # levels currently failed
        self._tier_restore_at: dict[int, int] = {}  # level -> restore window
        # (tenant, level) -> analyze-count when the demotion expires
        # (None = still down: expiry is stamped at recovery)
        self._demoted_until: dict[tuple[int, int], int | None] = {}
        self._held: set[int] = set()              # held out of this analyze
        self._defer_clear: set[int] = set()       # straggler tapes to keep
        self._cur_window = 0                      # window under analysis
        self._accumulated: set[int] = set()       # multi-window tapes
        self._lkg: AnalyzerDecision | None = None
        # unified degrade counters (each increments exactly once per event;
        # surfaced in summary())
        self.dirty_loss = 0
        self.tier_failures = 0
        self.guard_quarantines = 0
        self.guard_violations_observed = 0
        self.guard_violations_actuated = 0
        self.sharded_stepdowns = 0
        self.device_stepdowns = 0
        self.host_stepdowns = 0
        self.tenant_quarantines = 0
        self.lkg_decisions = 0
        self.sampled_exact_retries = 0
        self.poisoned_windows = 0
        self.straggler_windows = 0
        self.degrade_events = 0
        # stage times, counters and host syncs of every window (None: off)
        self.profile = profile

    # ------------------------------------------------------------- Monitor
    def record(self, tenant: int, addrs: np.ndarray, is_read: np.ndarray) -> None:
        """Ingest one tenant's window events (validated: raises
        ``TraceError`` with (tenant, window) coordinates on a malformed
        tape — a tolerant ``run_window`` quarantines instead)."""
        validate_trace_arrays(addrs, is_read, tenant=tenant,
                              window=self.windows_run)
        t = self.tenants[tenant]
        t.window_addrs.append(np.asarray(addrs, np.int64))
        t.window_reads.append(np.asarray(is_read, bool))

    def add_tenant(self, name: str,
                   initial_blocks: int | None = None) -> int:
        """Tenant churn: a workload joins mid-run.  Returns its index.

        The next ``run_window`` records a ``"join"`` reconfiguration
        event; in event-driven mode that forces an analyze so the
        newcomer is sized from its first window.  Existing tenants'
        SHARDS salts and detector baselines are untouched (ids are
        positional and a join only appends).
        """
        init = int(initial_blocks if initial_blocks is not None
                   else self.c_min)
        self.tenants.append(TenantState(name, LRUCache(init)))
        i = len(self.tenants) - 1
        self._joined.append(i)
        return i

    def retire_tenant(self, tenant: int) -> None:
        """Workload finished: release its partitions (paper §6.3)."""
        t = self.tenants[tenant]
        t.active = False
        t.cache.resize(0)
        t.cache2.resize(0)
        self._prev_sets.pop(tenant, None)
        if self.detector is not None:
            self.detector.forget(tenant)

    # ------------------------------------------------------------ Analyzer
    def effective_sample_rate(self) -> float | str | None:
        """Resolve the Monitor's sampling mode for the current deployment."""
        if self.sample_rate is None \
                and len(self.tenants) >= self.auto_sample_tenants:
            return "auto"
        return self.sample_rate

    def _record_degrade(self, ev: DegradeEvent) -> None:
        self.events.append(ev)
        self.degrade_events += 1

    def _launch_hook(self, win: int, rung: str, attempt: int):
        """Fault-injection hook for one monitor launch (None = no plan)."""
        if self.faults is None or not self.faults.enabled:
            return None

        def hook() -> None:
            if self.faults.launch_should_fail(win, rung, attempt):
                raise InjectedFault(
                    f"injected {rung} launch failure "
                    f"(window={win}, attempt={attempt})")
        return hook

    def _monitor_kwargs(self, act: list[int]) -> dict:
        return dict(kind=self.rd_kind, percentile=self.percentile,
                    sample_rate=self.effective_sample_rate(),
                    window_seed=self.windows_analyzed,
                    sample_target=self.sample_target,
                    sample_floor=self.sample_floor, tenant_ids=act)

    def _per_tenant_monitor(self, act: list[int], traces: list[Trace],
                            kw: dict, win: int
                            ) -> tuple[MonitorResult, list[int]]:
        """Bottom ladder rung: solo analyze per tenant, so one bad tenant
        can no longer take the whole deployment's analyze down.  Failed
        tenants are quarantined (held at last-known-good)."""
        curves, urds, wrs, rates, errs, ok = [], [], [], [], [], []
        for trace, i in zip(traces, act):
            try:
                m = analyze_windows([trace], **{**kw, "tenant_ids": [i]},
                                    pipeline="host",
                                    fault_hook=self._launch_hook(
                                        win, "tenant", 0),
                                    profile=self.profile)
            except Exception:
                self._held.add(i)
                self.tenant_quarantines += 1
                self._record_degrade(
                    DegradeEvent(win, i, "tenant_quarantine"))
                continue
            ok.append(i)
            curves.append(m.curves[0])
            urds.append(int(m.urd_sizes[0]))
            wrs.append(float(m.write_ratios[0]))
            rates.append(float(m.sample_rates[0]))
            errs.append(float(m.expected_errors[0]))
        mon = MonitorResult(curves, np.asarray(urds, np.int64),
                            np.asarray(wrs, np.float64),
                            np.asarray(rates, np.float64),
                            np.asarray(errs, np.float64), self.rd_kind)
        return mon, ok

    def _monitor_ladder(self, act: list[int],
                        window_trd: dict[int, np.ndarray]
                        ) -> tuple[MonitorResult | None, list[int], str]:
        """Run the monitor pass down the degradation ladder.

        Returns ``(result, analyzed_tenants, rung)``; ``result`` is None
        only when every rung failed (total outage → last-known-good).
        Without fault tolerance this is exactly the single fused call."""
        rate = self.effective_sample_rate()
        pipe = self.pipeline
        traces = [self.tenants[i].window_trace() for i in act]
        # the device program recounts on device, so precomputed TRD arrays
        # are only forwarded to the host pipeline; a deferred (straggler)
        # tape spans multiple windows, invalidating its single-window
        # precomputed distances
        pre = ([None if i in self._accumulated else window_trd.get(i)
                for i in act]
               if rate is None and pipe == "host" else None)
        kw = self._monitor_kwargs(act)
        if not self.fault_tolerant:
            mon = analyze_windows(traces, precomputed_trd=pre,
                                  pipeline=pipe, profile=self.profile, **kw)
            self.windows_analyzed += 1
            return mon, act, pipe
        win = self._cur_window
        # top of the ladder: sharded mesh → single device → fused host →
        # per-tenant solo; a per-shard launch failure inside the mesh
        # program surfaces at the window dispatch and steps the whole
        # window down one rung (counted per rung in summary())
        rungs = ({"sharded": ["sharded", "device"],
                  "device": ["device"]}.get(pipe, []) + ["host", "tenant"])
        for rung in rungs:
            attempts = (self.retry_limit + 1) if rung != "tenant" else 1
            for attempt in range(attempts):
                try:
                    if rung == "tenant":
                        mon, ok = self._per_tenant_monitor(
                            act, traces, kw, win)
                        if not ok and act:
                            # every solo analyze died too: total outage
                            return None, act, ""
                        self.windows_analyzed += 1
                        return mon, ok, rung
                    mon = analyze_windows(
                        traces,
                        precomputed_trd=(pre if rung == "host" else None),
                        pipeline=(rung if rung in ("sharded", "device")
                                  else "host"),
                        fault_hook=self._launch_hook(win, rung, attempt),
                        profile=self.profile, **kw)
                    self.windows_analyzed += 1
                    return mon, act, rung
                except TraceError:
                    raise          # ingest bugs are not launch failures
                except Exception:
                    if self.backoff_base > 0 and attempt + 1 < attempts:
                        time.sleep(min(self.backoff_base * (2 ** attempt),
                                       1.0))
            if rung == "sharded":
                self.sharded_stepdowns += 1
            elif rung == "device":
                self.device_stepdowns += 1
            elif rung == "host":
                self.host_stepdowns += 1
            self._record_degrade(DegradeEvent(win, -1, "stepdown",
                                              rung=rung))
        return None, act, ""

    def _fallback_decision(self, trigger: tuple[ReconfigEvent, ...],
                           reason: str,
                           violations: tuple[str, ...] = ()
                           ) -> AnalyzerDecision:
        """Last-known-good: keep every tenant at its current size/policy."""
        self.lkg_decisions += 1
        sizes = self.allocated_sizes()
        sizes2 = self.allocated_sizes2()
        n_act = sum(t.active for t in self.tenants)
        part = PartitionResult(
            sizes[[i for i, t in enumerate(self.tenants) if t.active]],
            False, 0.0, np.zeros(n_act))
        decision = AnalyzerDecision(
            sizes, [t.policy for t in self.tenants], False, part,
            sizes2=sizes2, policies2=[t.policy2 for t in self.tenants],
            partition2=None, trigger=tuple(trigger), quarantined=True,
            guard=tuple(violations), degraded=reason,
            deferred=tuple(sorted(self._defer_clear)))
        self._record_degrade(DegradeEvent(self._cur_window, -1, reason))
        self.history.append(decision)
        return decision

    def _apply_demotions(self) -> None:
        """Hold WB tenants of a failed(-and-recovering) tier on the demoted
        policy until ``demote_cooldown`` analyzes after recovery."""
        if not self._demoted_until:
            return
        for (i, lv), until in list(self._demoted_until.items()):
            if until is not None and self.windows_analyzed >= until:
                del self._demoted_until[(i, lv)]
                continue
            t = self.tenants[i]
            if lv == 1 and t.policy is WritePolicy.WB:
                t.policy = self.demote_policy
            elif lv == 2 and t.policy2 is WritePolicy.WB:
                t.policy2 = self.demote_policy

    def _build_decision(self, mon: MonitorResult, act: list[int],
                        held: set[int],
                        trigger: tuple[ReconfigEvent, ...]
                        ) -> tuple[AnalyzerDecision, np.ndarray, int]:
        """Alg. 3 + Eq. 2 over one monitor result.  Returns the decision,
        the guard floors and the partitioned L1 budget."""
        for k, i in enumerate(act):
            t = self.tenants[i]
            t.h_fn = mon.curves[k]
            t.urd_size = int(mon.urd_sizes[k])
            if self.adaptive_policy:
                # Alg. 3 writeRatio = (WAW + WAR)/n: write re-touches are
                # exactly the writes with a TRD sample
                wr = float(mon.write_ratios[k])
                t.policy = (WritePolicy.RO if wr >= self.w_threshold
                            else WritePolicy.WB)
                if self.capacity2 > 0:
                    # per-level Alg. 3: the larger endurance-sensitive L2
                    # switches to the clean policy at a stricter threshold
                    t.policy2 = (WritePolicy.RO if wr >= self.w_threshold2
                                 else WritePolicy.WB)
        self._apply_demotions()

        down1 = 1 in self._down_levels
        down2 = 2 in self._down_levels
        cap1, cap2 = self.capacity, self.capacity2
        pfn = self.partition_fn
        if self.fault_tolerant and (down1 or down2 or held):
            # held tenants keep their current partitions: solve the rest
            # against the residual budget; a down level's budget is 0
            held_sz = sum(int(self.tenants[i].cache.capacity) for i in held)
            held_sz2 = sum(int(self.tenants[i].cache2.capacity)
                           for i in held)
            cap1 = 0 if down1 else max(self.capacity - held_sz, 0)
            cap2 = 0 if down2 else max(self.capacity2 - held_sz2, 0)
            if down1 or down2 or cap1 <= 0:
                # degraded mode: the discrete greedy handles an empty
                # budget exactly (the PGD box projection cannot go below
                # its floors)
                pfn = greedy_allocate
        with _pstage(self.profile, "partition"):
            part, part2 = two_level_solve(
                mon.curves, cap1, cap2, self.t_fast,
                self.t_fast2, self.t_slow, c_min=self.c_min,
                partition_fn=pfn)

        n_ten = len(self.tenants)
        sizes_full = np.zeros(n_ten, dtype=np.int64)
        sizes2_full = np.zeros(n_ten, dtype=np.int64)
        floors = np.zeros(n_ten, dtype=np.int64)
        k = 0
        for i, t in enumerate(self.tenants):
            if not t.active:
                continue
            if i in held:
                sizes_full[i] = t.cache.capacity
                sizes2_full[i] = t.cache2.capacity
                continue
            sizes_full[i] = part.sizes[k]
            if part2 is not None:
                sizes2_full[i] = part2.sizes[k]
            if not down1:
                floors[i] = min(self.c_min, t.urd_size)
            k += 1
        decision = AnalyzerDecision(sizes_full,
                                    [t.policy for t in self.tenants],
                                    part.feasible, part,
                                    sizes2=sizes2_full,
                                    policies2=[t.policy2
                                               for t in self.tenants],
                                    partition2=part2,
                                    trigger=tuple(trigger),
                                    held=tuple(sorted(held)),
                                    deferred=tuple(sorted(
                                        self._defer_clear & held)))
        return decision, floors, cap1

    def analyze(self, window_trd: dict[int, np.ndarray] | None = None,
                trigger: tuple[ReconfigEvent, ...] = ()
                ) -> AnalyzerDecision:
        """Alg. 1 / Alg. 4: run at every Δt window boundary.

        All active tenants are analyzed in one fused pass
        (``analyze_windows``): one stack-distance counting pass over the
        concatenated window tape, batched curve construction, batched
        Alg.-3 write ratios — optionally SHARDS-sampled (see the class
        docstring).  ``window_trd`` optionally carries per-tenant raw TRD
        sample arrays already computed by the batch engine's counting pass
        (identical to ``reuse_distances(trace, "trd").distances``); the
        exact path reuses them instead of re-counting.

        Fault tolerance (see the module docstring): the monitor pass walks
        the degradation ladder, the resulting decision is guard-validated,
        and a violating or unobtainable decision degrades to the
        last-known-good allocation instead of crashing or actuating
        garbage.
        """
        window_trd = window_trd or {}
        held = {i for i in self._held if self.tenants[i].active}
        act = [i for i, t in enumerate(self.tenants)
               if t.active and i not in held]
        # guard rollback point: a quarantined decision must not leak the
        # corrupted pass's Alg.-3 policy flips
        pol_snap = [(t.policy, t.policy2) for t in self.tenants]
        with _pstage(self.profile, "monitor"):
            mon, act, rung = self._monitor_ladder(act, window_trd)
        try:
            if mon is None:
                return self._fallback_decision(trigger, "monitor_outage")
            held = {i for i in self._held if self.tenants[i].active}
            if self.faults is not None:
                self.faults.corrupt_monitor(mon, act, self._cur_window)
            decision, floors, budget = self._build_decision(
                mon, act, held, trigger)
            report = validate_decision(decision, self.capacity,
                                       self.capacity2, floors=floors,
                                       floor_budget=budget)
            if not report.ok:
                self.guard_violations_observed += len(report.violations)
                if self.fault_tolerant:
                    retried = False
                    if any(float(r) < 1.0 for r in mon.sample_rates):
                        # a sampled pass can violate by estimation noise:
                        # retry once exactly before giving up on the window
                        self.sampled_exact_retries += 1
                        retried = True
                        try:
                            kw = {**self._monitor_kwargs(act),
                                  "sample_rate": None}
                            with _pstage(self.profile, "monitor"):
                                mon2 = analyze_windows(
                                    [self.tenants[i].window_trace()
                                     for i in act],
                                    pipeline="host", profile=self.profile,
                                    **kw)
                            self.windows_analyzed += 1
                            if self.faults is not None:
                                self.faults.corrupt_monitor(
                                    mon2, act, self._cur_window)
                            decision, floors, budget = self._build_decision(
                                mon2, act, held, trigger)
                            report = validate_decision(
                                decision, self.capacity, self.capacity2,
                                floors=floors, floor_budget=budget)
                        except Exception:
                            report = None
                    if report is None or not report.ok:
                        self.guard_quarantines += 1
                        vio = (() if report is None
                               else report.violations)
                        if retried and report is not None:
                            self.guard_violations_observed += \
                                len(report.violations)
                        for t, (p, p2) in zip(self.tenants, pol_snap):
                            t.policy, t.policy2 = p, p2
                        return self._fallback_decision(
                            trigger, "guard_quarantine", vio)
                else:
                    # intolerant: the violation WILL be actuated — count it
                    # so garbage never ships silently
                    decision = dataclasses.replace(
                        decision, guard=report.violations)
            if report is not None and report.ok:
                self._lkg = decision
            self.history.append(decision)
            return decision
        finally:
            self._held = set()
            self._defer_clear = set()

    # ------------------------------------------------------------ Actuator
    def actuate(self, decision: AnalyzerDecision) -> None:
        if decision.guard and not decision.quarantined:
            # an intolerant manager ships the violating decision; count it
            # exactly once so garbage never actuates silently
            self.guard_violations_actuated += 1
        sizes2 = (decision.sizes2 if decision.sizes2 is not None
                  else np.zeros(len(self.tenants), np.int64))
        defer = set(decision.deferred)
        for i, (t, size, size2) in enumerate(
                zip(self.tenants, decision.sizes, sizes2)):
            if t.active:
                t.cache.resize(int(size))
                if self.capacity2 > 0 or t.cache2.capacity > 0:
                    t.cache2.resize(int(size2))
                if i not in defer:
                    t.clear_window()
        # deferred (straggler) tapes now span >1 window: their precomputed
        # single-window distances are invalid at the next analyze
        self._accumulated = defer

    # ------------------------------------------------- tier failure domain
    def fail_tier(self, level: int, duration: int | None = None) -> int:
        """Cache device of hierarchy ``level`` (1 = L1/HBM, 2 = L2/host)
        crashes: drop every tenant's residents on that level, account the
        lost dirty blocks (``dirty_loss``), demote WB tenants (see
        ``note_tier_loss``).  ``duration`` (trace-replay mode) restores
        the tier automatically after that many windows; ``None`` waits for
        an explicit ``note_tier_recovery``.  Returns the dirty-block
        count."""
        dirty = 0
        for t in self.tenants:
            cache = t.cache if level == 1 else t.cache2
            if len(cache):
                _, d = cache.state_arrays()
                if d is not None:
                    dirty += int(np.asarray(d).sum())
            cache.resize(0)
        self.note_tier_loss(level, dirty)
        if duration is not None:
            self._tier_restore_at[level] = \
                self.windows_run + max(int(duration), 1)
        return dirty

    def note_tier_loss(self, level: int, dirty_blocks: int = 0) -> None:
        """Register a tier failure (serving path: ``TieredKVCache`` calls
        this after dropping its own residents).  Marks the level down —
        its partition budget is 0 until recovery — and demotes every WB
        tenant on it to ``demote_policy`` (paper §3: WB's dirty blocks are
        exactly what a cache-device crash loses; a tenant on a tier that
        just failed must not keep buffering dirty data)."""
        self.tier_failures += 1
        self.dirty_loss += int(dirty_blocks)
        self._down_levels.add(int(level))
        for i, t in enumerate(self.tenants):
            if not t.active:
                continue
            pol = t.policy if level == 1 else t.policy2
            if pol is WritePolicy.WB:
                # expiry is stamped at recovery (None = still down)
                self._demoted_until.setdefault((i, int(level)), None)
                if level == 1:
                    t.policy = self.demote_policy
                else:
                    t.policy2 = self.demote_policy
        self._record_degrade(DegradeEvent(
            self.windows_run, -1, "tier_loss", level=int(level),
            blocks=int(dirty_blocks)))

    def note_tier_recovery(self, level: int) -> None:
        """The failed tier is back: restore its budget and start the
        WB-demotion cooldown clock (``demote_cooldown`` analyzes)."""
        level = int(level)
        if level not in self._down_levels:
            return
        self._down_levels.discard(level)
        self._tier_restore_at.pop(level, None)
        until = self.windows_analyzed + 1 + self.demote_cooldown
        for key, u in list(self._demoted_until.items()):
            if key[1] == level and u is None:
                self._demoted_until[key] = until
        self._record_degrade(DegradeEvent(
            self.windows_run, -1, "tier_recover", level=level))

    def tier_is_down(self, level: int) -> bool:
        return int(level) in self._down_levels

    # --------------------------------------------------------- trace replay
    def _accumulate(self, t: TenantState, res: SimResult) -> None:
        agg = t.result
        agg.reads += res.reads; agg.read_hits += res.read_hits
        agg.writes += res.writes; agg.write_hits += res.write_hits
        agg.cache_writes += res.cache_writes
        agg.total_latency += res.total_latency
        agg.read_hits_l2 += res.read_hits_l2
        agg.write_hits_l2 += res.write_hits_l2
        agg.cache_writes_l2 += res.cache_writes_l2
        agg.fallback += res.fallback
        agg.capacity = t.cache.capacity
        agg.capacity2 = t.cache2.capacity
        agg.policy = t.policy.value
        agg.policy2 = t.policy2.value

    def _record_events(self, events: list[ReconfigEvent]) -> None:
        self.events.extend(events)
        self.reconfig_events += len(events)

    def _drain_joined(self, window: int) -> list[ReconfigEvent]:
        """Pending ``add_tenant`` joins -> churn events (not yet recorded)."""
        evs = [ReconfigEvent(window, i, "join") for i in self._joined]
        self._joined.clear()
        return evs

    def _fault_preamble(self, traces: list[Trace | None],
                        win: int) -> list[Trace | None]:
        """Apply the window's scheduled faults and quarantine bad tapes.

        Runs only on a tolerant (or fault-injected) manager: restores
        tiers whose outage expired, injects tape corruption / tier losses
        / stragglers from the plan, and validates every incoming tape —
        a malformed one is quarantined (replaced by an empty tape, tenant
        held at last-known-good) instead of raising."""
        for lv, at in list(self._tier_restore_at.items()):
            if win >= at:
                self.note_tier_recovery(lv)
        self._held = set()
        self._defer_clear = set()
        if self.faults is not None and self.faults.enabled:
            traces = self.faults.corrupt_traces(traces, win)
            for spec in self.faults.at(win, "tier_loss"):
                if spec.level not in self._down_levels:
                    self.fail_tier(spec.level, duration=spec.duration)
            for i in sorted(self.faults.stragglers(win)):
                if 0 <= i < len(traces) and traces[i] is not None \
                        and self.tenants[i].active:
                    self._held.add(i)
                    self._defer_clear.add(i)
                    self.straggler_windows += 1
                    self._record_degrade(DegradeEvent(win, i, "straggler"))
        if self.fault_tolerant:
            for i, tr in enumerate(traces):
                if tr is None:
                    continue
                try:
                    validate_trace(tr, tenant=i, window=win)
                except TraceError:
                    traces[i] = Trace(np.zeros(0, np.int64),
                                      np.zeros(0, bool), tr.name)
                    self._held.add(i)
                    self.poisoned_windows += 1
                    self._record_degrade(DegradeEvent(win, i, "poisoned"))
        return traces

    def run_window(self, traces: list[Trace | None],
                   engine: str | None = None) -> None:
        """Replay one Δt window for every tenant, then analyze + actuate.

        ``traces[i] is None`` marks tenant i as finished.  With
        ``phase_detect`` on, the analyze/actuate half runs only when the
        phase detector, a churn event, or the ``reconfig_interval`` clock
        triggers it (see the class docstring); the replay half always
        runs.

        With a ``profile`` the window is stage ``window``, with stages
        ``replay`` (``simulate_many``'s ``replay_*`` stages inside),
        ``monitor`` and ``partition`` in it; everything else of the window
        is ``window``'s self time.
        """
        with _pstage(self.profile, "window"):
            self._run_window(traces, engine)
        if self.profile is not None:
            self.profile.end_window()

    def _run_window(self, traces: list[Trace | None],
                    engine: str | None) -> None:
        engine = self.engine if engine is None else engine
        win = self.windows_run
        self._cur_window = win
        if self.fault_tolerant or self.faults is not None:
            traces = self._fault_preamble(list(traces), win)
        events = self._drain_joined(win)
        for i, tr in enumerate(traces):
            if tr is None and self.tenants[i].active:
                self.retire_tenant(i)
                events.append(ReconfigEvent(win, i, "retire"))

        idx = [i for i, tr in enumerate(traces) if tr is not None]
        for i in idx:
            self.record(i, traces[i].addrs, traces[i].is_read)

        window_trd: dict[int, np.ndarray] | None = None
        if engine == "batch":
            with _pstage(self.profile, "replay"):
                results, rds = simulate_many(
                    [traces[i] for i in idx],
                    policies=[self.tenants[i].policy for i in idx],
                    t_fast=self.t_fast, t_slow=self.t_slow,
                    t_write_bypass=self.t_write_bypass,
                    flush_cost=self.flush_cost,
                    caches=[self.tenants[i].cache for i in idx],
                    policies2=[self.tenants[i].policy2 for i in idx],
                    caches2=[self.tenants[i].cache2 for i in idx],
                    t_fast2=self.t_fast2,
                    return_window_rd=True, profile=self.profile)
            window_trd = {i: rd for i, rd in zip(idx, rds) if rd is not None}
            for i, res in zip(idx, results):
                self._accumulate(self.tenants[i], res)
            self.ro_fallback_windows += sum(r.fallback for r in results)
        else:
            with _pstage(self.profile, "replay"):
                for i in idx:
                    t = self.tenants[i]
                    res = simulate(traces[i], t.cache.capacity, t.policy,
                                   self.t_fast, self.t_slow,
                                   t_write_bypass=self.t_write_bypass,
                                   flush_cost=self.flush_cost, cache=t.cache,
                                   capacity2=t.cache2.capacity,
                                   policy2=t.policy2, t_fast2=self.t_fast2,
                                   cache2=t.cache2)
                    self._accumulate(t, res)
        self.tenant_windows += len(idx)
        self.windows_run += 1

        if self.detector is None:
            # fixed-Δt mode: analyze + actuate every window, exactly the
            # pre-event-driven behavior (churn events are telemetry only)
            self._record_events(events)
            decision = self.analyze(window_trd)
            self.actuate(decision)
            return

        # ---------------------------------------- event-driven mode (ReCA)
        # characterize this window's accesses on the replay engine's
        # window reuse distances (no second pass; see core.characterize)
        feats = characterize_windows(
            [traces[i] for i in idx],
            prev_sets=[self._prev_sets.get(i) for i in idx],
            dists=[None if window_trd is None else window_trd.get(i)
                   for i in idx],
            tenant_ids=idx)
        for k, i in enumerate(idx):
            self._prev_sets[i] = feats.address_sets[k]
        events.extend(ReconfigEvent(win, e.tenant, e.reason, e.score)
                      for e in self.detector.update(feats, win, idx))
        self._pending_windows += 1
        if self._pending_windows >= self.reconfig_interval:
            events.append(ReconfigEvent(win, -1, "interval"))
        if events:
            # a multi-window accumulation invalidates the single-window
            # precomputed distances; the Analyzer re-counts the full span
            wtrd = window_trd if self._pending_windows == 1 else None
            self._record_events(events)
            decision = self.analyze(wtrd, trigger=tuple(events))
            self.actuate(decision)
            self._pending_windows = 0

    # ------------------------------------------------------------- metrics
    def allocated_sizes(self) -> np.ndarray:
        return np.array([t.cache.capacity for t in self.tenants], np.int64)

    def allocated_sizes2(self) -> np.ndarray:
        return np.array([t.cache2.capacity for t in self.tenants], np.int64)

    def summary(self) -> dict[str, float]:
        res = [t.result for t in self.tenants]
        n = sum(r.n for r in res)
        lat = sum(r.total_latency for r in res)
        writes = sum(r.cache_writes for r in res)
        alloc = int(self.allocated_sizes().sum())
        mean_lat = lat / n if n else 0.0
        return {
            "accesses": n,
            "mean_latency": mean_lat,
            "performance": 1.0 / mean_lat if mean_lat else 0.0,
            "cache_writes": writes,
            "allocated_blocks": alloc,
            "perf_per_cost": (1.0 / mean_lat) / alloc if mean_lat and alloc else 0.0,
            "read_hit_ratio": (sum(r.read_hits for r in res)
                               / max(sum(r.reads for r in res), 1)),
            "cache_writes_l2": sum(r.cache_writes_l2 for r in res),
            "allocated_blocks_l2": int(self.allocated_sizes2().sum()),
            "read_hit_ratio_l2": (sum(r.read_hits_l2 for r in res)
                                  / max(sum(r.reads for r in res), 1)),
            # batch-engine telemetry: tenant-windows replayed through the
            # per-access interpreter (degenerate windows only — RO
            # eviction pressure stays vectorized), over all replayed windows
            "ro_fallback_windows": self.ro_fallback_windows,
            "tenant_windows": self.tenant_windows,
            # event-driven telemetry: replayed vs analyzed windows and the
            # cumulative reconfiguration-event count (the `events` deque
            # itself is bounded by history_limit)
            "windows_run": self.windows_run,
            "windows_analyzed": self.windows_analyzed,
            "reconfig_events": self.reconfig_events,
            # unified fallback/degrade telemetry (each counter increments
            # exactly once per event; all 0 on a healthy fault-free run)
            "dirty_loss": self.dirty_loss,
            "tier_failures": self.tier_failures,
            "guard_quarantines": self.guard_quarantines,
            "guard_violations_observed": self.guard_violations_observed,
            "guard_violations_actuated": self.guard_violations_actuated,
            "sharded_stepdowns": self.sharded_stepdowns,
            "device_stepdowns": self.device_stepdowns,
            "host_stepdowns": self.host_stepdowns,
            "tenant_quarantines": self.tenant_quarantines,
            "lkg_decisions": self.lkg_decisions,
            "sampled_exact_retries": self.sampled_exact_retries,
            "poisoned_windows": self.poisoned_windows,
            "straggler_windows": self.straggler_windows,
            "degrade_events": self.degrade_events,
        }
