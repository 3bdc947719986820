"""Cache-space partitioners solving the paper's Eq. 2.

    Latency_i(c_i) = h_i(c_i) * T_fast + (1 - h_i(c_i)) * T_slow
    minimize   sum_i w_i * Latency_i(c_i)
    subject to sum_i c_i <= C,    c_min <= c_i <= c_urd_i

The paper solves this with MATLAB ``fmincon`` on the (piecewise-constant)
hit-ratio functions.  We provide:

  * ``greedy_allocate``  — breakpoint greedy: H_i are step functions, so
    latency only improves at breakpoints; repeatedly granting the jump with
    the best latency-reduction *density* (Δlatency / Δblocks) is the classic
    MRC-partitioning procedure (Centaur's convex-hull walk).  Near-optimal:
    exact on the concave hull, with at most a one-breakpoint knapsack
    rounding gap at tight capacities.  Deterministic, no MATLAB.

    The default ``method="fast"`` is a *vectorized breakpoint walk*: every
    tenant's (Δh/Δc density, Δc) steps are materialized as arrays, each
    chain is reduced to its prefix-min density envelope (the order the heap
    consumes a chain: a cheap step blocks its better successors, so a
    chain's effective priority is the running minimum), one argsort merges
    all chains, and a prefix sum over Δc finds the budget cut — O(K log K)
    array work for K breakpoints total, no Python inner loop.  The grant
    order — hence the allocation — is **bit-identical** to the retained
    ``method="heap"`` oracle (property-tested), including the partial grant
    of the first step past the budget.
  * ``pgd_solve``        — projected-gradient descent in JAX on the
    piecewise-linear relaxation of H_i, with a Dykstra-style projection onto
    { sum c <= C } ∩ box.  This is the faithful "fmincon analog"; tests check
    it matches greedy within the relaxation gap.

Both return allocations in *blocks* (pages).  All entry points accept a
plain list of ``HitRatioFunction`` or the fused monitor's
``BatchedHitRatioFunctions`` store (stacked breakpoint arrays; zero-copy
for the vectorized paths).

``two_level_solve`` adds ETICA's second capacity constraint: level 1
(HBM blocks) is sized by the single-level problem, then level 2 (host-DRAM
blocks) solves the *same* Eq. 2 on the residual hit-ratio curves
``h~_i(c) = h_i(c1_i + c)`` with service time ``t_fast2`` — exact because
the exclusive hierarchy's union is one LRU stack (see ``batch_sim``), so
L2 hits are precisely the reuses in ``[c1_i, c1_i + c2_i)``.  With batched
curves the residual shift is vectorized too, so both levels stay on the
fast path.
"""
from __future__ import annotations

import dataclasses
import heapq

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mrc import BatchedHitRatioFunctions, HitRatioFunction

__all__ = ["PartitionResult", "greedy_allocate", "pgd_solve",
           "aggregate_latency", "two_level_solve"]


@dataclasses.dataclass(frozen=True)
class PartitionResult:
    sizes: np.ndarray          # int64[N] allocated blocks per tenant
    feasible: bool             # True iff sum(urd sizes) <= C (paper's term)
    latency: float             # aggregate objective value at `sizes`
    hit_ratios: np.ndarray     # float64[N] at `sizes`


def _hit_ratios_at(hs, sizes: np.ndarray) -> np.ndarray:
    """Vectorized ``[h_i(sizes_i)]`` via the stacked-curve store."""
    return BatchedHitRatioFunctions.from_curves(hs).evaluate(
        np.asarray(sizes))


def aggregate_latency(hs, sizes: np.ndarray,
                      t_fast: float, t_slow: float,
                      weights: np.ndarray | None = None) -> float:
    """Paper Eq. 2 objective at an allocation (vectorized over tenants)."""
    w = np.ones(len(hs)) if weights is None else np.asarray(weights, float)
    hr = _hit_ratios_at(hs, sizes)
    return float(np.sum(w * (hr * t_fast + (1.0 - hr) * t_slow)))


def greedy_allocate(hs, capacity: int,
                    t_fast: float, t_slow: float,
                    c_min: int = 0,
                    weights: np.ndarray | None = None,
                    method: str = "fast") -> PartitionResult:
    """Breakpoint-greedy partitioner (the discrete reference optimizer).

    Feasible case (paper Alg. 1 line 8): if the URD-based sizes all fit,
    allocate them outright.  Otherwise walk breakpoints by best
    Δlatency/Δblocks until capacity is exhausted.  ``method="fast"``
    (default) runs the vectorized breakpoint walk, ``"heap"`` the original
    one-pop-at-a-time loop — both produce bit-identical sizes (the heap is
    retained as the oracle in tests and for the partial-grant semantics
    reference).  ``"device"`` runs the fast walk's jitted ``lax`` port
    (``core.device_pipeline.greedy_walk_device``) — bit-identical to the
    host walk in its f64 mode, used standalone here and inlined by the
    fused device window program.
    """
    if method not in ("fast", "heap", "device"):
        raise ValueError(
            f"method must be 'fast', 'heap' or 'device', got {method!r}")
    n = len(hs)
    w = np.ones(n) if weights is None else np.asarray(weights, float)
    b = BatchedHitRatioFunctions.from_curves(hs)
    urd_sizes = b.max_useful_sizes.astype(np.int64)
    c_min_arr = np.minimum(np.full(n, c_min, dtype=np.int64), urd_sizes)

    if int(urd_sizes.sum()) <= capacity:
        sizes = urd_sizes
        return PartitionResult(
            sizes, True,
            aggregate_latency(b, sizes, t_fast, t_slow, w),
            b.evaluate(sizes))

    sizes = c_min_arr.copy()
    budget = capacity - int(sizes.sum())
    if budget < 0:  # even the minimums do not fit: scale the minimums down
        sizes = np.floor(c_min_arr * capacity / max(c_min_arr.sum(), 1)
                         ).astype(np.int64)
        budget = capacity - int(sizes.sum())

    gain = t_slow - t_fast  # latency saved per unit hit-ratio
    if method == "heap":
        sizes = _greedy_walk_heap(hs, sizes, budget, urd_sizes, w, gain)
    elif method == "device":
        from repro.core.device_pipeline import greedy_walk_device
        sizes = greedy_walk_device(b, sizes, budget, w, gain)
    else:
        sizes = _greedy_walk_fast(b, sizes, budget, w, gain)

    return PartitionResult(
        sizes, False,
        aggregate_latency(b, sizes, t_fast, t_slow, w),
        b.evaluate(sizes))


def _greedy_walk_heap(hs, sizes: np.ndarray, budget: int,
                      urd_sizes: np.ndarray, w: np.ndarray,
                      gain: float) -> np.ndarray:
    """The original heap inner loop: pop the densest next breakpoint,
    grant it, push the tenant's following step.  O(K log K) with Python
    constant factors — retained as the oracle for the fast walk."""
    n = len(hs)
    heap: list[tuple[float, int, int, int, float]] = []

    def push(i: int) -> None:
        nxt, dh = hs[i].marginal_gain(int(sizes[i]))
        dc = nxt - int(sizes[i])
        if dh > 0 and dc > 0 and nxt <= urd_sizes[i]:
            density = w[i] * dh * gain / dc
            heapq.heappush(heap, (-density, i, nxt, dc, dh))

    for i in range(n):
        push(i)
    while heap and budget > 0:
        _, i, nxt, dc, _ = heapq.heappop(heap)
        if nxt - int(sizes[i]) != dc:   # stale entry
            push(i)
            continue
        if dc > budget:                 # partial grant: no hit-ratio step is
            sizes[i] += budget          # crossed, but matches paper's diff
            budget = 0                  # term (maximize allocated space)
            break
        sizes[i] = nxt
        budget -= dc
        push(i)
    return sizes


def _greedy_walk_fast(b: BatchedHitRatioFunctions, sizes: np.ndarray,
                      budget: int, w: np.ndarray, gain: float) -> np.ndarray:
    """Vectorized replay of the heap walk (bit-identical grant order).

    Each tenant's chain of breakpoint steps must be consumed in curve
    order, so a step's effective priority under "always pop the densest
    head" is the prefix-min of densities along its chain; merging the
    chains by (envelope desc, tenant, step) reproduces the heap's pop
    sequence exactly (ties included: on equal density the heap compares
    the tenant index next, and a chain's better-than-envelope successors
    flush immediately after their blocking step either way).  A cumsum
    over Δc then finds the budget cut and the partial-grant step.
    """
    n = len(b)
    if budget <= 0 or n == 0:
        return sizes
    edges, heights, off = b.edges, b.heights, b.offsets
    lens = np.diff(off)
    # first step index per tenant (strictly above its current size)
    k0 = b._composite(sizes) - off[:-1]
    n_steps = np.maximum(lens - k0, 0)
    total = int(n_steps.sum())
    if total == 0:
        return sizes
    st_tid = np.repeat(np.arange(n, dtype=np.int64), n_steps)
    rank = (np.arange(total, dtype=np.int64)
            - np.repeat(np.cumsum(n_steps) - n_steps, n_steps))
    gk = off[st_tid] + k0[st_tid] + rank          # breakpoint per step
    h_cur0 = b.evaluate(sizes)                    # h at the starting sizes
    first = rank == 0
    dh = heights[gk] - np.where(first, h_cur0[st_tid], heights[gk - 1])
    dc = edges[gk] - np.where(first, sizes[st_tid], edges[gk - 1])
    # the heap stops a chain at its first non-improving step
    bad = (dh <= 0).astype(np.int64)
    cbad = np.cumsum(bad)
    seg0 = np.repeat(np.cumsum(n_steps) - n_steps, n_steps)
    valid = (cbad - cbad[seg0] + bad[seg0]) == 0
    if not valid.any():
        return sizes
    st_tid, rank = st_tid[valid], rank[valid]
    nxt_s, dc = edges[gk[valid]], dc[valid]
    d = w[st_tid] * dh[valid] * gain / dc         # heap's density, same ops
    # prefix-min envelope per chain (doubling scan: log K numpy passes)
    nv = d.shape[0]
    idx = np.arange(nv, dtype=np.int64)
    head = np.ones(nv, dtype=bool)
    head[1:] = st_tid[1:] != st_tid[:-1]
    first_idx = np.maximum.accumulate(np.where(head, idx, 0))
    e = d.copy()
    shift = 1
    while shift < nv:
        can = idx - shift >= first_idx
        prev_e = np.concatenate([np.full(shift, np.inf), e[:-shift]])
        e = np.where(can, np.minimum(e, prev_e), e)
        shift *= 2
    order = np.lexsort((rank, st_tid, -e))
    cum = np.cumsum(dc[order])
    n_full = int(np.searchsorted(cum, budget, side="right"))
    granted = order[:n_full]
    np.maximum.at(sizes, st_tid[granted], nxt_s[granted])
    rem = budget - (int(cum[n_full - 1]) if n_full else 0)
    if rem > 0 and n_full < nv:                   # partial-grant tail
        sizes[st_tid[order[n_full]]] += rem
    return sizes


def two_level_solve(hs: list[HitRatioFunction], capacity: int,
                    capacity2: int, t_fast: float, t_fast2: float,
                    t_slow: float, c_min: int = 0,
                    partition_fn=None,
                    weights: np.ndarray | None = None
                    ) -> tuple[PartitionResult, PartitionResult | None]:
    """Eq. 2 with per-level capacities and per-level service times.

    Stage 1 sizes L1 exactly as the single-level problem (``capacity2 == 0``
    therefore reproduces it bit-identically); stage 2 runs the same
    partitioner on the residual curves ``h_i.shifted(c1_i)`` against the
    level-2 budget with gain ``t_slow - t_fast2`` and no per-tenant
    minimum.  Returns ``(level1, level2)``; ``level2`` is ``None`` when
    ``capacity2 <= 0``.
    """
    fn = partition_fn if partition_fn is not None else pgd_solve
    # only forward weights when set: custom partition_fn callables predate
    # the weights kwarg and must keep working unchanged
    kw = {} if weights is None else {"weights": weights}
    p1 = fn(hs, capacity, t_fast, t_slow, c_min=c_min, **kw)
    if capacity2 <= 0:
        return p1, None
    if isinstance(hs, BatchedHitRatioFunctions):
        shifted = hs.shifted(p1.sizes)       # vectorized residual curves
    else:
        shifted = [h.shifted(int(s)) for h, s in zip(hs, p1.sizes)]
    p2 = fn(shifted, capacity2, t_fast2, t_slow, c_min=0, **kw)
    return p1, p2


def _project_capacity_box(c: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                          capacity: float, iters: int = 50) -> jnp.ndarray:
    """Project onto { lo <= c <= hi, sum(c) <= capacity } by bisection on the
    simplex Lagrange multiplier (exact for this polytope)."""
    c0 = jnp.clip(c, lo, hi)

    def over_budget(_c):
        return jnp.sum(_c) > capacity

    def bisect(_c):
        # find tau >= 0 with sum(clip(c - tau, lo, hi)) == capacity
        tau_lo = jnp.zeros(())
        tau_hi = jnp.max(c - lo) + 1.0

        def body(_, carry):
            tlo, thi = carry
            mid = 0.5 * (tlo + thi)
            s = jnp.sum(jnp.clip(c - mid, lo, hi))
            return jnp.where(s > capacity, mid, tlo), jnp.where(s > capacity, thi, mid)

        tlo, thi = jax.lax.fori_loop(0, iters, body, (tau_lo, tau_hi))
        return jnp.clip(c - 0.5 * (tlo + thi), lo, hi)

    return jax.lax.cond(over_budget(c0), bisect, lambda _c: _c, c0)


_TABLE_PTS = 128  # fixed interpolation-table width so jit caches per n


def _pgd_core(n: int, steps: int):
    """Build (and cache) the jitted PGD loop for n tenants."""

    @jax.jit
    def run(xs, ys, lo, hi, cap, w, t_fast, t_slow, lr):
        def interp_h(c):
            return jax.vmap(jnp.interp)(c, xs, ys)

        def objective(c):
            h = interp_h(c)
            return jnp.sum(w * (h * t_fast + (1.0 - h) * t_slow))

        grad_fn = jax.grad(objective)

        def body(_, c):
            g = grad_fn(c)
            c = c - lr * g / (jnp.linalg.norm(g) + 1e-9) * jnp.sqrt(float(n))
            return _project_capacity_box(c, lo, hi, cap)

        c0 = _project_capacity_box(hi * cap / (jnp.sum(hi) + 1e-9), lo, hi, cap)
        return jax.lax.fori_loop(0, steps, body, c0)

    return run


_PGD_CACHE: dict[tuple[int, int], object] = {}


def pgd_solve(hs: list[HitRatioFunction], capacity: int,
              t_fast: float, t_slow: float,
              c_min: int = 0, steps: int = 300, lr: float | None = None,
              weights: np.ndarray | None = None) -> PartitionResult:
    """Projected-gradient solver on the piecewise-linear relaxation (JAX).

    This is the faithful analog of the paper's MATLAB ``fmincon`` call: a
    first-order method on the *smoothed* MRC, with the exact projection onto
    { sum c <= C } ∩ box.  Like fmincon it works on the relaxation, so under
    capacity pressure it spreads the squeeze across tenants rather than
    walking exact breakpoints — reproducing the squeeze behaviour the paper
    reports for Centaur in infeasible states.  ``greedy_allocate`` is the
    exact (beyond-paper) discrete optimizer.
    """
    n = len(hs)
    w = np.ones(n) if weights is None else np.asarray(weights, float)
    urd_sizes = np.array([h.max_useful_size for h in hs], dtype=np.int64)
    if int(urd_sizes.sum()) <= capacity:
        sizes = urd_sizes
        return PartitionResult(
            sizes, True, aggregate_latency(hs, sizes, t_fast, t_slow, w),
            np.array([h(int(s)) for h, s in zip(hs, sizes)]))

    # Fixed-width piecewise-linear tables (resampled) so jit caches per n.
    xs = np.zeros((n, _TABLE_PTS), np.float32)
    ys = np.zeros((n, _TABLE_PTS), np.float32)
    for i, h in enumerate(hs):
        e = h.edges.astype(np.float64); v = h.heights.astype(np.float64)
        grid = np.linspace(0.0, max(float(e[-1]), 1.0), _TABLE_PTS)
        xs[i] = grid
        ys[i] = np.interp(grid, e, v)
    lo = np.minimum(np.full(n, float(c_min)), urd_sizes.astype(np.float32))
    hi = urd_sizes.astype(np.float32)
    if lr is None:
        lr = 0.05 * capacity / n

    key = (n, steps)
    if key not in _PGD_CACHE:
        _PGD_CACHE[key] = _pgd_core(n, steps)
    run = _PGD_CACHE[key]
    c_star = np.asarray(run(jnp.asarray(xs), jnp.asarray(ys),
                            jnp.asarray(lo), jnp.asarray(hi),
                            jnp.float32(capacity), jnp.asarray(w, jnp.float32),
                            jnp.float32(t_fast), jnp.float32(t_slow),
                            jnp.float32(lr)))

    # Snap each tenant down to its nearest breakpoint (never exceeds c*),
    # then spend any leftover with single marginal-density repair steps —
    # still a local method, faithful to the first-order character of fmincon.
    # The snap never goes below the c_min floor when the floors fit: c*
    # already respects them, so the sum stays within the budget.
    sizes = np.zeros(n, dtype=np.int64)
    for i, h in enumerate(hs):
        k = np.searchsorted(h.edges, c_star[i], side="right") - 1
        sizes[i] = int(h.edges[max(k, 0)])
    floors = lo.astype(np.int64)
    if int(floors.sum()) <= capacity:
        sizes = np.maximum(sizes, floors)
    leftover = capacity - int(sizes.sum())
    gain = t_slow - t_fast
    while leftover > 0:
        best, best_i, best_nxt = 0.0, -1, 0
        for i, h in enumerate(hs):
            nxt, dh = h.marginal_gain(int(sizes[i]))
            dc = nxt - int(sizes[i])
            if dh > 0 and 0 < dc <= leftover:
                d = w[i] * dh * gain / dc
                if d > best:
                    best, best_i, best_nxt = d, i, nxt
        if best_i < 0:
            break
        leftover -= best_nxt - int(sizes[best_i])
        sizes[best_i] = best_nxt
    return PartitionResult(
        sizes, False, aggregate_latency(hs, sizes, t_fast, t_slow, w),
        np.array([h(int(s)) for h, s in zip(hs, sizes)]))
