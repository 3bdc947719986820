"""Plain reference of the ECI-Cache control plane, one window at a time.

Written from the semantics the configuration states (paper §4–§5, Alg. 1
and 3, Eq. 2), independent of the code under test: it imports nothing of
the program and takes none of its tables.

  * Replay: one ``OrderedDict`` LRU per tenant, walked access by access.
    Read hit -> ``t_fast``; read miss -> ``t_slow`` and an install (one
    cache write); WB write -> ``t_fast``, install or modify dirty (one
    cache write); WT write -> ``t_write_bypass``, install or refresh clean
    (one cache write); RO write -> ``t_write_bypass``, invalidates a
    cached copy.  Evicting a dirty block charges ``flush_cost``.  Resizing
    drops LRU blocks with no charge.
  * Monitor: reuse distance of a re-touch = distinct addresses strictly
    between it and its previous touch in the window.  URD samples are the
    read re-touches; ``H(c)`` steps up by each sample's share of the
    window's accesses at ``c = d + 1``; the URD size is the largest
    step.  Alg. 3's write ratio is write re-touches over accesses.  With
    SHARDS sampling a tenant keeps the addresses whose salted hash is
    below its rate, distances are scaled by ``1 / rate`` and rounded, and
    heights are divided by the rate and clipped at 1.
  * Partition (Eq. 2): when the URD sizes fit, each tenant gets its URD
    size; otherwise the sizes must fit the cache, keep the ``c_min``
    floors, stay at or below the URD sizes, sit on a breakpoint of the
    curve (or on the floor), and leave no unspent budget that the next
    breakpoint of any tenant would fit into; and their objective,
    ``sum_i Latency_i``, is compared with the exact optimum over the same
    curves (a group knapsack over the capacity).
"""
from __future__ import annotations

import math
from collections import OrderedDict

import numpy as np

_MASK64 = (1 << 64) - 1
# replay counters, in the order the check compares them
COUNTERS = ("reads", "read_hits", "writes", "write_hits", "cache_writes")


# ------------------------------------------------------------------ replay
class Tenant:
    """One tenant's LRU partition (LRU first) and its write policy."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self.od: OrderedDict[int, bool] = OrderedDict()
        self.policy = "wb"

    def resize(self, capacity: int) -> None:
        self.capacity = int(capacity)
        while len(self.od) > self.capacity:
            self.od.popitem(last=False)


def replay(t: Tenant, addrs: np.ndarray, is_read: np.ndarray, p: dict,
           dtype=np.float64) -> dict:
    """Walk one window tape through ``t``; returns its counters.  The
    modelled latency is accumulated access by access in ``dtype``."""
    t_fast, t_slow = p["t_fast"], p["t_slow"]
    t_byp, flush = p["t_write_bypass"], p["flush_cost"]
    od, cap, pol = t.od, t.capacity, t.policy
    reads = read_hits = writes = write_hits = cache_writes = 0
    cost: list[float] = []
    lat = cost.append

    def install(a: int, dirty: bool) -> None:
        if len(od) >= cap:
            _, was_dirty = od.popitem(last=False)
            if was_dirty:
                lat(flush)
        od[a] = dirty

    for a, rd in zip(addrs.tolist(), is_read.tolist()):
        if rd:
            reads += 1
            if a in od:
                read_hits += 1
                od.move_to_end(a)
                lat(t_fast)
            else:
                lat(t_slow)
                if cap > 0:
                    install(a, False)
                    cache_writes += 1
            continue
        writes += 1
        if pol == "ro":
            if a in od:
                write_hits += 1
                del od[a]
            lat(t_byp)
        elif a in od:
            write_hits += 1
            od[a] = pol == "wb"
            od.move_to_end(a)
            cache_writes += 1
            lat(t_fast if pol == "wb" else t_byp)
        elif cap > 0:
            install(a, pol == "wb")
            cache_writes += 1
            lat(t_fast if pol == "wb" else t_byp)
        else:
            lat(t_byp)
    total = np.cumsum(np.asarray(cost, dtype))[-1] if cost else dtype(0)
    return {"reads": reads, "read_hits": read_hits, "writes": writes,
            "write_hits": write_hits, "cache_writes": cache_writes,
            "total_latency": float(total)}


# ----------------------------------------------------------------- monitor
def previous_touch(addrs: np.ndarray) -> np.ndarray:
    """Index of each access's previous touch of the same address, or -1."""
    order = np.argsort(addrs, kind="stable")
    prev = np.full(addrs.shape[0], -1, np.int64)
    same = addrs[order[1:]] == addrs[order[:-1]]
    prev[order[1:][same]] = order[:-1][same]
    return prev


def reuse_distances(addrs: np.ndarray, block: int = 64) -> np.ndarray:
    """Distinct addresses strictly between each re-touch and its previous
    touch; -1 at first touches.

    With ``p = prev[i]``, an access ``j`` in ``(p, i)`` is the first touch
    of its address inside the interval iff ``prev[j] < p``, and every
    ``j <= p`` has ``prev[j] < p``; so the distance is ``#{j < i :
    prev[j] < p} - (p + 1)``.  The count is taken over whole blocks of
    ``j`` by binary search in each block's sorted ``prev``, and over the
    query's own block by a direct comparison.
    """
    n = addrs.shape[0]
    prev = previous_touch(addrs)
    out = np.full(n, -1, np.int64)
    qi = np.flatnonzero(prev >= 0)
    if qi.size == 0:
        return out
    qp = prev[qi]
    qb = qi // block
    count = np.zeros(qi.size, np.int64)
    for b in range(int(qb[-1])):
        lo = int(np.searchsorted(qb, b + 1))        # queries past block b
        srt = np.sort(prev[b * block:(b + 1) * block])
        count[lo:] += np.searchsorted(srt, qp[lo:], side="left")
    base = qb * block
    k = np.arange(block, dtype=np.int64)
    cols = np.minimum(base[:, None] + k[None, :], n - 1)
    inside = (base[:, None] + k[None, :]) < qi[:, None]
    count += np.sum(inside & (prev[cols] < qp[:, None]), axis=1)
    out[qi] = count - qp - 1
    return out


def shards_salt(window: int, tenant: int) -> int:
    """The configuration's SHARDS salt: a splitmix64 mix of (window,
    tenant) into ``[1, 2**31 - 3]``."""
    z = (window * 0x9E3779B97F4A7C15 + tenant * 0xBF58476D1CE4E5B9
         + 0x94D049BB133111EB) & _MASK64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK64
    z ^= z >> 31
    return z % (2**31 - 3) + 1


def shards_rate(n: int, target: int, floor: int) -> float:
    """Per-tenant rate: ``max(target, floor)`` kept accesses expected."""
    return 1.0 if n <= 0 else min(1.0, max(target, floor, 1) / n)


def shards_keep(addrs: np.ndarray, rate: float, salt: int) -> np.ndarray:
    """Keep ``a`` iff ``(a * 2654435761 + salt) mod 2**32 < rate * 2**32``."""
    thr = math.ceil(rate * 2.0**32)
    if thr >= 2**32:
        return np.ones(addrs.shape[0], bool)
    h = ((addrs.astype(np.uint64) % 2**32) * 2654435761 + salt) % 2**32
    return h < thr


def monitor(addrs: np.ndarray, is_read: np.ndarray, rate: float,
            salt: int, dtype=np.float64) -> dict:
    """One tenant-window: curve ``(edges, heights)``, URD size, write
    ratio, under SHARDS sampling at ``rate`` (1 = exact); heights in
    ``dtype``."""
    n = addrs.shape[0]
    if rate < 1.0:
        keep = shards_keep(addrs, rate, salt)
        addrs, is_read = addrs[keep], is_read[keep]
    kept = addrs.shape[0]
    d = reuse_distances(addrs)
    if rate < 1.0:
        d = np.where(d >= 0, np.round(d / rate).astype(np.int64), -1)
    write_ratio = float(np.sum((d >= 0) & ~is_read)) / max(kept, 1)
    samples = d[(d >= 0) & is_read]
    sizes, counts = np.unique(samples + 1, return_counts=True)
    den = dtype(max(n, 1))
    cum = np.cumsum(counts).astype(dtype)
    if rate < 1.0:
        heights = np.minimum(cum / (den * dtype(rate)), dtype(1))
    else:
        heights = cum / den
    edges = np.concatenate([[0], sizes]).astype(np.int64)
    return {"edges": edges,
            "heights": np.concatenate([[0.0], heights.astype(np.float64)]),
            "n": int(den), "urd": int(edges[-1]),
            "write_ratio": write_ratio}


# --------------------------------------------------------------- partition
def partition_faults(sizes: np.ndarray, feasible: bool, curves: list[dict],
                     capacity: int, c_min: int) -> int:
    """How many Eq.-2 conditions the decided ``sizes`` break on the
    reference curves (see the module docstring)."""
    urd = np.array([c["urd"] for c in curves], np.int64)
    fits = int(urd.sum()) <= capacity
    bad = int(bool(feasible) != fits)
    if fits:
        return bad + int(np.sum(sizes != urd))
    floors = np.minimum(c_min, urd)
    bad += int(int(sizes.sum()) > capacity)
    if int(floors.sum()) <= capacity:
        bad += int(np.sum(sizes < floors))
    bad += int(np.sum(sizes > urd))
    left = capacity - int(sizes.sum())
    for s, f, c in zip(sizes.tolist(), floors.tolist(), curves):
        e, h = c["edges"], c["heights"]
        k = int(np.searchsorted(e, s, side="right"))
        bad += int(s != f and (k == 0 or int(e[k - 1]) != s))
        if 0 < k < e.shape[0] and h[k] > h[k - 1] and int(e[k]) - s <= left:
            bad += 1          # a further gaining breakpoint fits the budget
    return bad


def curve_at(c: dict, size: int) -> float:
    """``H(size)`` of a reference curve (a step function)."""
    k = int(np.searchsorted(c["edges"], size, side="right")) - 1
    return float(c["heights"][max(k, 0)])


def optimal_hits(curves: list[dict], capacity: int, c_min: int) -> float:
    """The largest ``sum_i H_i(c_i)`` under Eq. 2's constraints: a
    group knapsack over capacity, each tenant choosing its floor or one
    breakpoint up to its URD size (``H`` only steps up at breakpoints)."""
    best = np.full(capacity + 1, -np.inf)
    best[0] = 0.0
    for c in curves:
        floor = min(c_min, c["urd"])
        sizes = [floor] + [int(e) for e in c["edges"]
                           if floor < int(e) <= c["urd"]]
        nxt = np.full(capacity + 1, -np.inf)
        for s in sizes:
            if s <= capacity:
                nxt[s:] = np.maximum(nxt[s:], best[:capacity + 1 - s]
                                     + curve_at(c, s))
        best = nxt
    return float(best.max())


def partition_gap(sizes: np.ndarray, curves: list[dict], capacity: int,
                  c_min: int, t_fast: float, t_slow: float) -> float:
    """How far the decided sizes' Eq.-2 objective, ``sum_i Latency_i``
    with ``Latency = H * t_fast + (1 - H) * t_slow``, lies above the
    exact optimum, as a share of the optimum.  0 where the URD sizes fit
    (each tenant's size is then checked exactly) or the floors do not."""
    urd = np.array([c["urd"] for c in curves], np.int64)
    floors = np.minimum(c_min, urd)
    if int(urd.sum()) <= capacity or int(floors.sum()) > capacity:
        return 0.0
    n, gain = len(curves), t_slow - t_fast
    got = sum(curve_at(c, int(s)) for c, s in zip(curves, sizes.tolist()))
    best = optimal_hits(curves, capacity, c_min)
    lat_opt = n * t_slow - gain * best
    return (gain * (best - got)) / lat_opt
