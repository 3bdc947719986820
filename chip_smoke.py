#!/usr/bin/env python3
"""Bring-up smoke of the ECI-Cache system on a TPU chip.

Drives the system's two main paths once, through the entry points a user
calls, at the sizes users run, and checks each against a plain reference:

  A. Control plane, the paper's host: ``ECICacheManager.run_window`` over
     the 16 MSR Cambridge tenant profiles, 16,384 accesses per
     tenant-window, 4 windows, a cache below the tenants' summed URD
     demand.  Three managers see the same windows: the default one
     (batch replay, host decision pipeline), a ``pipeline="device"`` one
     and an ``engine="lru"`` one.  The per-access interpreter
     (``simulate``) of the last is the replay reference: replay stats
     must match it exactly.  Device decisions must match host decisions
     within the f32 tolerance of ``core.device_pipeline``.  A profiler
     trace of the last window shows that the compiled ``cache_sim`` Pallas
     kernels ran on the chip for replay, for the host monitor and inside
     the device pipeline.
  B. Serving at published widths: ``qwen3_0_6b`` (28 layers, d_model 1024,
     seeded random weights) through ``MultiTenantEngine`` +
     ``TieredKVCache`` + ``ECICacheManager``, 8 requests from 2 tenants that
     share a 128-token system prompt.  The reference is a plain
     ``forward_hidden`` pass over the same tokens: prefill logits and every
     greedy decode token must agree with it within ``LOGIT_TOL``.

``--four-chips`` runs only the sharded control plane
(``analyze_windows(pipeline="sharded")``, ``shard_map`` over the four-device
``control_plane_mesh``) against the single-chip device pipeline on the same
windows, 1,024 tenants x 4,096 accesses, 2 windows, and partitions each
result with the manager's Eq.-2 solver.

    python chip_smoke.py                 # one chip: phases A and B
    python chip_smoke.py --four-chips    # four chips: the sharded phase

Every result line names the device.  The last line of stdout is
``{"ok": true, "device": {...}}``; a failed phase exits non-zero without it,
and so does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Phase A: the paper's host.  The 16 tenants' summed URD demand is about
# 9,200 blocks at this window length, so a 6,144-block cache makes the
# Eq.-2 partitioner bind.  Latency model and floors as in
# benchmarks/common.py.
PHASE_A = dict(n_access=16384, windows=4, capacity=6144, c_min=50,
               initial_blocks=384)
SIM = dict(t_fast=1.0, t_slow=20.0, flush_cost=10.0)
# Phase B: a few requests per tenant, prompts of whole 16-token pages.
PHASE_B = dict(requests=8, tenants=2, system_prompt=128, user_prompt=32,
               new_tokens=8, page_size=16, pool_pages=128, capacity=96)
# --four-chips: the sharded control plane at fleet scale.
PHASE_SHARDED = dict(n_tenants=1024, n_access=4096, windows=2,
                     capacity=1024 * 256, c_min=50)

# bf16 model, f32 logits (std about 0.56 with these random weights): an
# engine logit may differ from the reference by at most this much, and a
# greedy token must be within this much of the reference's best logit.
# Two XLA programs of the same bf16 math at different shapes (the engine's
# page-padded prefill, the reference's batched pass) differed by up to
# 0.042 on a TPU v5e through the 28 layers.
LOGIT_TOL = 0.1
# f32 device curves vs the f64 host build: heights are cum/n divisions
F32_RTOL = 1e-6
# decisions that differ (an f32 tie) must cost the same Eq.-2 latency
LATENCY_RTOL = 1e-4

_REPLAY_INTS = ("reads", "read_hits", "writes", "write_hits", "cache_writes",
                "read_hits_l2", "write_hits_l2", "cache_writes_l2",
                "fallback")
TRACE_DIR = ROOT / ".smoke_trace"
DIAG_DIR = ROOT / "chiprun_out"


def log(msg: str) -> None:
    print(msg, flush=True)


def msr_names(n_tenants: int | None = None) -> list[str]:
    from repro.data.traces import MSR_PROFILES
    base = list(MSR_PROFILES)
    n = len(base) if n_tenants is None else int(n_tenants)
    return [base[i % len(base)] for i in range(n)]


def msr_windows(profiles: list[str], n_access: int, windows: int,
                seed: int) -> list[list]:
    """Seeded synthetic MSR windows, one trace per tenant per window."""
    from repro.data.traces import msr_trace
    return [[msr_trace(nm, n_access, seed=seed + 1000 * w + i)
             for i, nm in enumerate(profiles)] for w in range(windows)]


# ------------------------------------------------------------- references
def check_replay(mgr, ref) -> None:
    """Replay stats of ``mgr`` equal the interpreter manager ``ref``'s."""
    for i, (t, r) in enumerate(zip(mgr.tenants, ref.tenants)):
        for f in _REPLAY_INTS:
            a, b = getattr(t.result, f), getattr(r.result, f)
            if a != b:
                raise AssertionError(f"replay tenant {i} {f}: {a} != {b}")
        a, b = t.result.total_latency, r.result.total_latency
        if abs(a - b) > 1e-9 * max(1.0, abs(b)):
            raise AssertionError(f"replay tenant {i} latency: {a} != {b}")
    if not (mgr.allocated_sizes() == ref.allocated_sizes()).all():
        raise AssertionError("final allocations differ from the reference")


def check_decision(host, dev, host_curves, dev_curves, capacity: int,
                   t_fast: float, t_slow: float) -> bool:
    """Device decision vs host decision for one window.

    Curves: same breakpoints, heights within f32 rounding.  Policies and
    URD sizes: equal.  Sizes: equal, or (an f32 tie flipped) within the
    budget at the same Eq.-2 latency on the host's curves.  Returns
    whether the sizes were identical.
    """
    import numpy as np
    from repro.core.partitioner import aggregate_latency
    for i, (hc, dc) in enumerate(zip(host_curves, dev_curves)):
        if not np.array_equal(hc.edges, dc.edges):
            raise AssertionError(f"tenant {i}: curve breakpoints differ")
        if not np.allclose(dc.heights, hc.heights, rtol=F32_RTOL, atol=0):
            raise AssertionError(f"tenant {i}: curve heights differ beyond "
                                 f"f32 rounding")
    if host.policies != dev.policies:
        raise AssertionError("write policies differ")
    if np.array_equal(host.sizes, dev.sizes):
        return True
    if int(np.sum(dev.sizes)) > capacity:
        raise AssertionError("device decision exceeds the cache")
    lh = aggregate_latency(host_curves, host.sizes, t_fast, t_slow)
    ld = aggregate_latency(host_curves, dev.sizes, t_fast, t_slow)
    if abs(ld - lh) > LATENCY_RTOL * lh:
        raise AssertionError(f"device sizes cost {ld}, host sizes {lh}")
    return False


# ------------------------------------------------------------ kernel proof
# The compiled Pallas kernels each phase-A manager must launch on the chip:
# the batch replay counts the whole window tape in ``cache_sim_scan`` (the
# host monitor reuses its distances); the device pipeline and the host
# monitor of the lru-engine manager count in ``cache_sim_segments_scan``.
KERNELS = {"host": ("cache_sim_scan",),
           "device": ("cache_sim_scan", "cache_sim_segments_scan"),
           "lru": ("cache_sim_segments_scan",)}


def kernel_events(trace_dir: Path, names: tuple[str, ...]) -> dict:
    """Device-plane trace events naming each kernel: ``{name: [count,
    total ms, [[plane, line, event, start ns, duration ns], ...]]}``."""
    from jax.profiler import ProfileData
    found = {k: [0, 0.0, []] for k in names}
    for path in glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    text = " ".join([ev.name] + [str(v) for _, v in
                                                 ev.stats])
                    for k in names:
                        if k in text:
                            found[k][0] += 1
                            found[k][1] += ev.duration_ns / 1e6
                            found[k][2].append([plane.name, line.name,
                                                ev.name, ev.start_ns,
                                                ev.duration_ns])
    return found


def check_kernels(trace_dir: Path, window: int, label: str) -> None:
    """Each manager's traced window launched its ``KERNELS`` on the
    device; the matched events go to ``chiprun_out/`` for the record."""
    record, missing = {}, []
    for name, kernels in KERNELS.items():
        found = kernel_events(trace_dir / name, kernels)
        record[name] = found
        log(f"phaseA kernels window={window} manager={name} events="
            f"{json.dumps({k: v[:2] for k, v in found.items()})} "
            f"device={label}")
        missing += [f"{name}:{k}" for k, v in found.items() if not v[0]]
    DIAG_DIR.mkdir(exist_ok=True)
    (DIAG_DIR / "smoke_kernel_events.json").write_text(json.dumps(record))
    if missing:
        raise AssertionError(f"no device event of {missing} in the trace")


def _traced(trace_dir: Path | None):
    import contextlib
    import jax
    if trace_dir is None:
        return contextlib.nullcontext()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return jax.profiler.trace(str(trace_dir), profiler_options=opts)


# ---------------------------------------------------------------- phase A
def phase_a(n_access: int, windows: int, capacity: int, c_min: int,
            initial_blocks: int, n_tenants: int | None = None,
            seed: int = 0, label: str = "",
            trace_dir: Path | None = None) -> dict:
    """The control plane on the MSR tenants; raises on any mismatch."""
    from repro.core.device_pipeline import StageProfile
    from repro.core.manager import ECICacheManager
    from repro.core.monitor import analyze_windows

    profiles = msr_names(n_tenants)
    t0 = time.perf_counter()
    wins = msr_windows(profiles, n_access, windows, seed)
    log(f"phaseA setup traces={len(profiles)}x{n_access}x{windows} "
        f"gen_s={time.perf_counter() - t0:.3f}")
    kw = dict(capacity=capacity, tenant_names=profiles, c_min=c_min,
              initial_blocks=initial_blocks, **SIM)
    mgrs = {"host": ECICacheManager(**kw),
            "device": ECICacheManager(pipeline="device", **kw),
            "lru": ECICacheManager(engine="lru", **kw)}
    curves = {k: [] for k in mgrs}
    walls = {k: [] for k in mgrs}
    for w, traces in enumerate(wins):
        # in the last window each manager runs under its own profiler
        # session (if any), so each trace holds that manager's kernels only
        traced = trace_dir is not None and w == windows - 1
        for name, mgr in mgrs.items():
            with _traced(trace_dir / name if traced else None):
                t = time.perf_counter()
                mgr.run_window(traces)
                walls[name].append(time.perf_counter() - t)
            curves[name].append([t_.h_fn for t_ in mgr.tenants])
            log(f"phaseA window={w} manager={name} "
                f"wall_s={walls[name][-1]:.6f}"
                f"{' traced' if traced else ''} "
                f"allocated={int(mgr.allocated_sizes().sum())} "
                f"device={label}")
    if trace_dir is not None:
        check_kernels(trace_dir, windows - 1, label)

    # reference 1: batch replay == the per-access interpreter
    check_replay(mgrs["host"], mgrs["lru"])
    # reference 2: device decisions == host decisions (f32 tolerance)
    dec = {k: list(m.history) for k, m in mgrs.items()}
    exact = 0
    for w in range(windows):
        exact += check_decision(dec["host"][w], dec["device"][w],
                                curves["host"][w], curves["device"][w],
                                capacity, SIM["t_fast"], SIM["t_slow"])
        if not all(a == b for a, b in zip(dec["host"][w].sizes,
                                          dec["lru"][w].sizes)):
            raise AssertionError(f"window {w}: lru-engine decision differs")
    summaries = {k: m.summary() for k, m in mgrs.items()}
    for k, s in summaries.items():
        bad = {c: s[c] for c in ("device_stepdowns", "host_stepdowns",
                                 "sharded_stepdowns", "lkg_decisions",
                                 "guard_violations_actuated")
               if s[c]}
        if bad:
            raise AssertionError(f"manager {k} degraded: {bad}")
    feasible = [bool(d.feasible) for d in dec["host"]]
    if any(feasible):
        raise AssertionError(f"the cache did not bind: feasible={feasible}")

    # stage breakdown of the last window, both decision pipelines warm
    # (the staged device programs compile on the first, unrecorded call)
    stages = {}
    for pipe, staged in (("host", False), ("device", True)):
        analyze_windows(wins[-1], pipeline=pipe,
                        profile=StageProfile(staged=staged))
        prof = StageProfile(staged=staged)
        analyze_windows(wins[-1], pipeline=pipe, profile=prof)
        stages[pipe] = {k: round(v, 6) for k, v in prof.times.items()}
        log(f"phaseA stages pipeline={pipe} window={windows - 1} "
            f"times_s={json.dumps(stages[pipe])} syncs={prof.syncs} "
            f"device={label}")
    s = summaries["host"]
    log(f"phaseA result accesses={s['accesses']} "
        f"mean_latency={s['mean_latency']} "
        f"read_hit_ratio={s['read_hit_ratio']} "
        f"replay_vs_lru=exact decisions_exact={exact}/{windows} "
        f"stepdowns=0 guard_violations_actuated=0 device={label}")
    return {"walls": walls, "stages": stages, "exact_decisions": exact,
            "summary": s}


# ---------------------------------------------------------------- phase B
def phase_b(cfg, requests: int, tenants: int, system_prompt: int,
            user_prompt: int, new_tokens: int, page_size: int,
            pool_pages: int, capacity: int, seed: int = 0,
            label: str = "") -> dict:
    """Multi-tenant serving through the ECI-managed pool vs a plain pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.cache import BlockPool, TieredKVCache
    from repro.core.manager import ECICacheManager
    from repro.models import model as M
    from repro.models.attention import build_heads
    from repro.serve.engine import MultiTenantEngine, Request, prefill_with_kv

    t0 = time.perf_counter()
    params = jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    jax.block_until_ready(params)
    _, hkv = build_heads(cfg, 1)
    pool = BlockPool(pool_pages, page_size, cfg.n_layers, hkv, cfg.head_dim,
                     dtype=jnp.dtype(cfg.dtype))
    manager = ECICacheManager(capacity,
                              [f"tenant{i}" for i in range(tenants)],
                              c_min=8, initial_blocks=capacity // tenants)
    tiered = TieredKVCache(pool, manager, window_events=32)
    prompt_len = system_prompt + user_prompt
    engine = MultiTenantEngine(
        cfg, params, tiered, page_size=page_size,
        max_pages_per_seq=-(-(prompt_len + new_tokens) // page_size))
    rng = np.random.default_rng(seed)
    system = [rng.integers(0, cfg.vocab_size, system_prompt)
              for _ in range(tenants)]
    reqs = []
    for i in range(requests):
        prompt = np.concatenate(
            [system[i % tenants],
             rng.integers(0, cfg.vocab_size, user_prompt)]).astype(np.int32)
        reqs.append(Request(tenant=i % tenants, prompt=prompt,
                            max_new_tokens=new_tokens))
        engine.submit(reqs[-1])
    log(f"phaseB setup model={cfg.name} params={n_params} "
        f"dtype={cfg.dtype} pool_pages={pool_pages} "
        f"setup_s={time.perf_counter() - t0:.3f} device={label}")
    t0 = time.perf_counter()
    steps = 0
    while engine.waiting or engine.active:
        engine.step()
        steps += 1
        if steps > new_tokens + 2:
            raise AssertionError("engine did not finish its requests")
    wall = time.perf_counter() - t0
    if len(engine.completed) != requests or any(
            len(r.generated) != new_tokens for r in reqs):
        raise AssertionError("not every request completed its tokens")
    tokens = requests * new_tokens
    pool_sum = tiered.summary()
    log(f"phaseB served requests={requests} tokens={tokens} steps={steps} "
        f"wall_s_incl_compile={wall:.6f} "
        f"hbm_hit_ratio={pool_sum['hbm_hit_ratio']} "
        f"rebalances={manager.windows_analyzed} device={label}")

    # reference: one plain forward pass over prompt + generated tokens
    @jax.jit
    def ref_pass(p, toks, targets):
        h = M.forward_hidden(p, cfg, toks)
        lg = jnp.einsum("bsd,dv->bsv", h, M._lm_head(p, cfg),
                        preferred_element_type=jnp.float32)
        lg = lg[..., :cfg.vocab_size]
        return (lg[:, prompt_len - 1], lg.max(-1),
                jnp.take_along_axis(lg, targets[..., None], -1)[..., 0],
                lg.argmax(-1))

    seq = np.stack([np.concatenate([r.prompt, r.generated[:-1]])
                    for r in reqs]).astype(np.int32)
    tgt = np.stack([np.concatenate([np.zeros(prompt_len - 1, np.int32),
                                    np.asarray(r.generated, np.int32)])
                    for r in reqs])
    ref_last, ref_max, ref_at, ref_arg = jax.device_get(
        ref_pass(params, jnp.asarray(seq), jnp.asarray(tgt)))
    n_pages = -(-prompt_len // page_size)
    prefill_err = 0.0
    for i, r in enumerate(reqs):
        tok = np.pad(r.prompt, (0, n_pages * page_size - prompt_len))
        lg, _, _ = prefill_with_kv(params, cfg, jnp.asarray(tok[None]), 1,
                                   prompt_len - 1)
        lg = np.asarray(lg[0, :cfg.vocab_size], np.float32)
        prefill_err = max(prefill_err,
                          float(np.max(np.abs(lg - ref_last[i]))))
    dec = slice(prompt_len - 1, prompt_len - 1 + new_tokens)
    margin = float(np.max(ref_max[:, dec] - ref_at[:, dec]))
    gen = np.asarray([r.generated for r in reqs])
    agree = int(np.sum(ref_arg[:, dec] == gen))
    log(f"phaseB reference prefill_max_abs_diff={prefill_err} "
        f"greedy_tokens_equal={agree}/{tokens} "
        f"greedy_max_margin={margin} tol={LOGIT_TOL} "
        f"ref_logit_std={float(np.std(ref_last))} device={label}")
    if prefill_err > LOGIT_TOL:
        raise AssertionError(f"prefill logits differ by {prefill_err}")
    if margin > LOGIT_TOL:
        raise AssertionError(f"a greedy token is {margin} below the "
                             f"reference's best logit")
    return {"tokens": tokens, "wall": wall, "prefill_err": prefill_err,
            "margin": margin, "agree": agree}


# ------------------------------------------------------ sharded (4 chips)
def phase_sharded(n_tenants: int, n_access: int, windows: int,
                  capacity: int, c_min: int, seed: int = 0,
                  label: str = "") -> dict:
    """``analyze_windows(pipeline="sharded")`` over every local device vs
    ``pipeline="device"`` on one, then the manager's Eq.-2 partitioner on
    each result; raises on any mismatch."""
    import numpy as np
    from repro.core.device_pipeline import StageProfile
    from repro.core.monitor import analyze_windows
    from repro.core.partitioner import pgd_solve
    from repro.distributed.sharding import control_plane_mesh

    n_shards = int(control_plane_mesh().devices.size)
    if n_shards < 2:
        raise AssertionError("the sharded control plane needs 2+ devices")
    t0 = time.perf_counter()
    wins = msr_windows(msr_names(n_tenants), n_access, windows, seed)
    log(f"sharded setup traces={n_tenants}x{n_access}x{windows} "
        f"gen_s={time.perf_counter() - t0:.3f}")
    exact = 0
    for w, traces in enumerate(wins):
        mon, part = {}, {}
        for pipe in ("device", "sharded"):
            prof = StageProfile()
            t = time.perf_counter()
            mon[pipe] = analyze_windows(traces, pipeline=pipe, profile=prof)
            dt = time.perf_counter() - t
            part[pipe] = pgd_solve(mon[pipe].curves, capacity, SIM["t_fast"],
                                   SIM["t_slow"], c_min=c_min)
            log(f"sharded window={w} pipeline={pipe} monitor_wall_s={dt:.6f}"
                f" times_s={json.dumps(prof.times)} syncs={prof.syncs} "
                f"shards={n_shards if pipe == 'sharded' else 1} "
                f"device={label}")
        a, b = mon["device"], mon["sharded"]
        if not np.array_equal(a.urd_sizes, b.urd_sizes):
            raise AssertionError(f"window {w}: URD sizes differ")
        if not np.allclose(a.write_ratios, b.write_ratios, rtol=F32_RTOL):
            raise AssertionError(f"window {w}: write ratios differ")
        for i in range(n_tenants):
            if not (np.array_equal(a.curves[i].edges, b.curves[i].edges)
                    and np.allclose(a.curves[i].heights,
                                    b.curves[i].heights, rtol=F32_RTOL,
                                    atol=0)):
                raise AssertionError(f"window {w}: tenant {i} curve differs")
        pa, pb = part["device"], part["sharded"]
        if pa.feasible:
            raise AssertionError(f"window {w}: the cache did not bind")
        if np.array_equal(pa.sizes, pb.sizes):
            exact += 1
        elif abs(pa.latency - pb.latency) > LATENCY_RTOL * pa.latency:
            raise AssertionError(f"window {w}: allocations differ")
    log(f"sharded result allocations_exact={exact}/{windows} "
        f"device={label}")
    return {"exact": exact}


# ------------------------------------------------------------------- main
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded control plane on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    label = f"{dev.platform}:{dev.device_kind.replace(' ', '_')}" \
            f"x{len(devices)}"
    log(f"chip_smoke device={label} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            from repro.distributed.sharding import control_plane_mesh
            if len(devices) != 4 or control_plane_mesh(4).devices.size != 4:
                raise AssertionError(f"--four-chips needs 4 devices, "
                                     f"found {len(devices)}")
            phase_sharded(**PHASE_SHARDED, seed=args.seed, label=label)
        else:
            from repro.configs import get_config
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            phase_a(**PHASE_A, seed=args.seed, label=label,
                    trace_dir=TRACE_DIR)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            phase_b(get_config("qwen3_0_6b"), **PHASE_B, seed=args.seed,
                    label=label)
    except Exception:
        traceback.print_exc()
        log(f"chip_smoke FAILED after {time.perf_counter() - t0:.1f}s")
        return 1
    entries = sum(1 for p in Path(cache_dir).rglob("*") if p.is_file()) \
        if Path(cache_dir).is_dir() else 0
    log(f"chip_smoke total_s={time.perf_counter() - t0:.3f} "
        f"compile_cache_entries={entries} device={label}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
