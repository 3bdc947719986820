"""The one traffic generator of the benchmark.

A traffic mix is a data file beside this module (``<mix>.json``); this
module reads it and, with the cell's configuration, builds the pool of
window tapes a run cycles through.  The per-tenant tape generator and the
MSR Cambridge profiles below are a verbatim copy of
``repro.data.traces.generate_trace`` and ``MSR_PROFILES`` (synthetic
traces calibrated to the ECI-Cache paper's Fig. 12 request-type mixes and
Table 2), kept here so the yardstick cannot move with the program.
``chipbench/tests/test_traffic.py`` checks that the copy still reproduces
the program's tapes bit for bit.

A mix file holds:

  * ``accesses``: ``[[tenant_count, accesses_per_tenant_window], ...]``,
    tenants taken in index order (their profiles come from the
    configuration);
  * ``tape_pool_windows``: windows generated per run and cycled;
  * ``max_warmup_cycles``: the most pool cycles warm-up may run before
    the manager's state repeats with the pool (``cell.Run``);
  * ``base_seed``: the seed of the base tapes; the run's ``--seed``
    names their blocks (``build_pool``).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import NamedTuple

import numpy as np

HERE = Path(__file__).resolve().parent


class Trace(NamedTuple):
    """One tenant-window tape: block addresses and read flags."""

    addrs: np.ndarray
    is_read: np.ndarray
    name: str = ""


# ---- verbatim copy of repro.data.traces (WorkloadProfile, MSR_PROFILES,
# ---- generate_trace) -------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class WorkloadProfile:
    """Target statistics for one synthetic workload.

    cold_read/cold_write/rar/raw/war/waw: target fractions (sum ~ 1).
    zipf_a: Zipf exponent for re-reference locality (higher = tighter).
    working_set: approximate number of distinct blocks.
    sequential: if True, cold accesses stream (defeats caching, paper Fig. 9a).
    """

    cold_read: float
    cold_write: float
    rar: float
    raw: float
    war: float
    waw: float
    zipf_a: float = 1.2
    working_set: int = 4096
    sequential: bool = False
    # Re-touch depth exponents: rank ~ u**a over most-recent-first pools.
    # Large a -> shallow (recent) re-touches; small a -> deep re-touches.
    # The paper's Eq. 1 case-2 workloads (TRD >> URD) arise when write
    # re-touches are much deeper than read re-touches: a slowly-cycled large
    # write set inflates TRD while the hot read set keeps URD small.
    read_depth_a: float | None = None    # default: zipf_a
    write_depth_a: float = 0.35
    # Hard bound on how deep read re-touches reach into the access pool:
    # bounds URD (and the useful cache size) structurally, while write
    # re-touches range over the whole pool (inflating TRD).  None = unbounded
    # (Eq. 1 case 1: TRD == URD).
    read_reach: int | None = 256

    def normalized(self) -> "WorkloadProfile":
        s = (self.cold_read + self.cold_write + self.rar + self.raw
             + self.war + self.waw)
        return dataclasses.replace(
            self, cold_read=self.cold_read / s, cold_write=self.cold_write / s,
            rar=self.rar / s, raw=self.raw / s, war=self.war / s,
            waw=self.waw / s)


# Request-type mixes approximating paper Fig. 12 (per-workload descriptions in
# §6.4/§6.6: e.g. wdev_0 ~77% WAW + mostly-RAR rest; hm_1 >92% RAR;
# prxy_0/web_0 WAW/WAR-heavy; stg_1/mds_1/prn_1 RAR/RAW-dominant, etc.).
# ``read_reach`` / cold rates are tuned so the TRD/URD size ratios land where
# the paper reports them (stg_1 Centaur ~1000x ECI, rsrch_2 extreme,
# mds_0/proj_0 sizes occasionally equal — App. A).
MSR_PROFILES: dict[str, WorkloadProfile] = {
    "wdev_0":  WorkloadProfile(0.04, 0.12, 0.12, 0.02, 0.00, 0.70,
                               read_reach=128),
    "web_1":   WorkloadProfile(0.10, 0.12, 0.20, 0.05, 0.04, 0.49,
                               read_reach=192),
    "stg_1":   WorkloadProfile(0.06, 0.34, 0.08, 0.06, 0.06, 0.40,
                               working_set=1 << 17, read_reach=96),
    "ts_0":    WorkloadProfile(0.05, 0.15, 0.10, 0.02, 0.05, 0.63,
                               read_reach=160),
    "hm_1":    WorkloadProfile(0.05, 0.03, 0.88, 0.02, 0.00, 0.02,
                               read_reach=384),
    "mds_0":   WorkloadProfile(0.04, 0.12, 0.08, 0.03, 0.05, 0.68,
                               read_reach=256, write_depth_a=0.9),
    "proj_0":  WorkloadProfile(0.03, 0.26, 0.08, 0.03, 0.06, 0.54,
                               read_reach=256, write_depth_a=0.9),
    "prxy_0":  WorkloadProfile(0.02, 0.10, 0.06, 0.04, 0.08, 0.70,
                               read_reach=96),
    "rsrch_0": WorkloadProfile(0.02, 0.12, 0.05, 0.03, 0.09, 0.69,
                               read_reach=96),
    "src1_2":  WorkloadProfile(0.02, 0.12, 0.05, 0.02, 0.10, 0.69,
                               read_reach=96),
    "prn_1":   WorkloadProfile(0.08, 0.12, 0.38, 0.22, 0.05, 0.15,
                               working_set=1 << 16, read_reach=512),
    "src2_0":  WorkloadProfile(0.03, 0.12, 0.06, 0.03, 0.07, 0.69,
                               read_reach=96),
    "web_0":   WorkloadProfile(0.03, 0.10, 0.08, 0.04, 0.10, 0.65,
                               read_reach=128),
    "usr_0":   WorkloadProfile(0.10, 0.15, 0.33, 0.17, 0.08, 0.17,
                               working_set=1 << 16, read_reach=384),
    "rsrch_2": WorkloadProfile(0.02, 0.38, 0.005, 0.005, 0.15, 0.44,
                               sequential=True, read_reach=32),
    "mds_1":   WorkloadProfile(0.06, 0.10, 0.43, 0.25, 0.06, 0.10,
                               working_set=1 << 15, read_reach=320),
}



def generate_trace(profile: WorkloadProfile, n: int, seed: int = 0,
                   name: str = "") -> Trace:
    """Draw an n-access trace matching ``profile``'s target class mix."""
    p = profile.normalized()
    rng = np.random.default_rng(seed)
    addrs = np.empty(n, dtype=np.int64)
    is_read = np.empty(n, dtype=bool)

    read_pool: list[int] = []     # addresses whose last touch was a read
    write_pool: list[int] = []    # addresses whose last touch was a write
    next_cold = 0                 # streaming frontier for cold addresses

    classes = rng.choice(6, size=n, p=[p.cold_read, p.cold_write, p.rar,
                                       p.raw, p.war, p.waw])
    # Zipf ranks for picking *which* previously-touched address to re-use.
    zipf_u = rng.random(n)

    read_a = p.read_depth_a if p.read_depth_a is not None else p.zipf_a

    def pick(pool: list[int], u: float, a: float, reach: int | None) -> int:
        # Zipf-like: rank ~ u**a over most-recent-first ordering, optionally
        # truncated to the most recent ``reach`` entries.
        k = len(pool)
        if reach is not None:
            k = min(k, reach)
        r = int((u ** a) * k)
        return pool[len(pool) - 1 - min(r, k - 1)]

    for i in range(n):
        c = int(classes[i])
        if c >= 2:
            src_read = c in (2, 4)       # RAR/WAR re-touch a last-read addr
            pool = read_pool if src_read else write_pool
            if not pool:                 # nothing to re-touch yet -> cold
                c = 0 if c in (2, 3) else 1
        if c == 0 or c == 1:
            a = next_cold if p.sequential else int(rng.integers(0, 2**31))
            next_cold += 1
            rd = c == 0
        else:
            src_read = c in (2, 4)
            pool = read_pool if src_read else write_pool
            # current access type decides the depth: reads (RAR/RAW) re-touch
            # recent data, writes (WAR/WAW) cycle deep through their set.
            if c in (2, 3):
                a = pick(pool, float(zipf_u[i]), read_a, p.read_reach)
            else:
                a = pick(pool, float(zipf_u[i]), p.write_depth_a, None)
            rd = c in (2, 3)
        addrs[i] = a
        is_read[i] = rd
        # update pools: address moves to the pool of its current access type
        if rd:
            read_pool.append(a)
            if len(read_pool) > p.working_set:
                read_pool.pop(0)
        else:
            write_pool.append(a)
            if len(write_pool) > p.working_set:
                write_pool.pop(0)
    return Trace(addrs, is_read, name)


# ---------------------------------------------------------------- the pool
def load_mix(name: str) -> dict:
    """The traffic mix ``name`` (``traffic/<name>.json``)."""
    return json.loads((HERE / f"{name}.json").read_text())


def tenant_lengths(mix: dict, n_tenants: int) -> list[int]:
    """Accesses per tenant-window, tenant by tenant, from the mix."""
    lens = [int(n) for count, n in mix["accesses"] for _ in range(int(count))]
    if len(lens) != n_tenants:
        raise ValueError(f"mix covers {len(lens)} tenants, the "
                         f"configuration has {n_tenants}")
    return lens


_M64 = (1 << 64) - 1


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser, elementwise on uint64 (wrapping)."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def block_names(addrs: np.ndarray, seed: int, tenant: int) -> np.ndarray:
    """The seed's names for one tenant's blocks.

    The low 32 bits keep the base block number and bits 32-39 carry a
    prefix in 1..255 drawn per block from ``(seed, tenant)``, so the map
    is one to one and the same block has the same name in every window.
    LRU replay, reuse distances and the configuration's SHARDS hash (which
    reads the low 32 bits) are the same under any such renaming: every
    seed does the same work on different blocks.
    """
    if addrs.size and (addrs.min() < 0 or addrs.max() >= 2**32):
        raise ValueError("base block numbers must fit in 32 bits")
    key = np.uint64((seed * 0x9E3779B97F4A7C15 + tenant + 1) & _M64)
    h = _mix64(addrs.astype(np.uint64) ^ _mix64(np.array([key]))[0])
    prefix = (h % np.uint64(255) + np.uint64(1)).astype(np.int64)
    return addrs + (prefix << 32)


def build_pool(profiles: list[str], mix: dict, seed: int) -> list[list[Trace]]:
    """``tape_pool_windows`` windows of one tape per tenant.

    Tenant ``i`` of pool window ``w`` is drawn with ``base_seed + 1000 * w
    + i``; ``seed`` names the blocks (``block_names``).  The program
    compiles its replay for each exact tape length, and the lengths follow
    the reuse structure, so a seed that changed the structure would meet
    new programs in every run; a renaming keeps every seed on the programs
    of the first run.
    """
    lens = tenant_lengths(mix, len(profiles))
    base = int(mix["base_seed"])
    pool = []
    for w in range(int(mix["tape_pool_windows"])):
        win = []
        for i, (p, n) in enumerate(zip(profiles, lens)):
            t = generate_trace(MSR_PROFILES[p], n, base + 1000 * w + i, p)
            win.append(Trace(block_names(t.addrs, seed, i), t.is_read,
                             t.name))
        pool.append(win)
    return pool
