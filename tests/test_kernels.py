"""Pallas kernel sweeps (interpret mode) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cache_sim.kernel import (cache_sim_levels_scan,
                                            cache_sim_scan,
                                            cache_sim_segments_scan,
                                            live_count_scan)
from repro.kernels.cache_sim.ref import (cache_sim_levels_ref,
                                         cache_sim_ref,
                                         cache_sim_segments_ref,
                                         live_counts_ref)
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2_ssd.kernel import mamba2_ssd
from repro.kernels.mamba2_ssd.ref import mamba2_ssd_ref, seg_from_dA
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.urd_scan.kernel import urd_scan
from repro.kernels.urd_scan.ref import urd_scan_ref

# interpret-mode Pallas sweeps are minutes-scale on CPU: tier-1 deselects
# them (`pytest -m slow` opts in; the jnp oracles are covered by the fast
# suite through batch_sim/urd property tests).  The cheap ops.py dispatch
# test below stays un-marked so tier-1 keeps covering the jit wrappers.
slow_sweep = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Sq,Skv,D,causal,window", [
    (1, 2, 128, 128, 64, True, 0),
    (2, 2, 96, 160, 64, True, 0),        # ragged / pad paths
    (1, 1, 256, 256, 128, False, 0),
    (1, 2, 256, 256, 64, True, 96),      # sliding window
    (2, 4, 64, 64, 32, True, 0),         # small head dim
])
@slow_sweep
def test_flash_attention_sweep(B, H, Sq, Skv, D, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, H, Skv, D), dtype)
    v = jax.random.normal(ks[2], (B, H, Skv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_kv=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,D,page,nps,npool", [
    (2, 4, 2, 64, 16, 4, 32),
    (3, 8, 8, 128, 32, 3, 16),
    (1, 8, 2, 64, 8, 6, 64),
])
@slow_sweep
def test_paged_attention_sweep(B, Hq, Hkv, D, page, nps, npool, dtype):
    ks = jax.random.split(KEY, 3)
    rng = np.random.default_rng(0)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (npool, page, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (npool, page, Hkv, D), dtype)
    tables = jnp.asarray(
        rng.integers(0, npool, size=(B, nps)).astype(np.int32))
    lens = jnp.asarray(rng.integers(1, nps * page, size=(B,)
                                    ).astype(np.int32))
    out = paged_attention(q, kp, vp, tables, lens, interpret=True)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("BH,S,P,N,chunk", [
    (2, 128, 32, 16, 32),
    (4, 256, 64, 128, 64),
    (1, 64, 16, 8, 16),
])
@slow_sweep
def test_mamba2_ssd_sweep(BH, S, P, N, chunk):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (BH, S, P), jnp.float32) * 0.5
    B = jax.random.normal(ks[1], (BH, S, N), jnp.float32) * 0.5
    C = jax.random.normal(ks[2], (BH, S, N), jnp.float32) * 0.5
    dA = -jax.random.uniform(ks[3], (BH, S), jnp.float32) * 0.5
    seg = seg_from_dA(dA, chunk)
    out = mamba2_ssd(x, B, C, seg, chunk=chunk, interpret=True)
    ref = mamba2_ssd_ref(x, B, C, dA)
    scale = float(jnp.max(jnp.abs(ref)))
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(ref) / scale, atol=3e-6)


@pytest.mark.parametrize("n,tile", [(64, 16), (100, 32), (512, 128),
                                    (997, 256)])
@slow_sweep
def test_urd_scan_sweep(n, tile):
    rng = np.random.default_rng(n)
    addrs = rng.integers(0, max(4, n // 8), size=n).astype(np.int64)
    from repro.core.trace import Trace, prev_next_occurrence
    prev, nxt = prev_next_occurrence(addrs)
    out = urd_scan(jnp.asarray(prev, jnp.int32), jnp.asarray(nxt, jnp.int32),
                   tile=tile, interpret=True)
    ref = urd_scan_ref(jnp.asarray(prev, jnp.int32),
                       jnp.asarray(nxt, jnp.int32))
    assert jnp.array_equal(out, ref)


@pytest.mark.parametrize("n,tile", [(64, 16), (100, 32), (257, 64)])
@pytest.mark.parametrize("occ_mode", ["all", "reads"])
@slow_sweep
def test_cache_sim_scan_sweep(n, tile, occ_mode):
    """Occupancy-masked stack-distance kernel vs jnp oracle (interpret)."""
    rng = np.random.default_rng(n)
    addrs = rng.integers(0, max(4, n // 6), size=n).astype(np.int64)
    from repro.core.trace import prev_next_occurrence
    prev, nxt = prev_next_occurrence(addrs)
    occ = (np.ones(n, np.int32) if occ_mode == "all"
           else (rng.random(n) < 0.6).astype(np.int32))
    out = cache_sim_scan(jnp.asarray(prev, jnp.int32),
                         jnp.asarray(nxt, jnp.int32),
                         jnp.asarray(occ), tile=tile, interpret=True)
    ref = cache_sim_ref(jnp.asarray(prev, jnp.int32),
                        jnp.asarray(nxt, jnp.int32), jnp.asarray(occ))
    assert jnp.array_equal(out, ref)


@pytest.mark.parametrize("w,tile", [(32, 16), (64, 32),
                                    (16, 64), (16, 128)])  # w < tile
@slow_sweep
def test_cache_sim_segments_scan_sweep(w, tile):
    """Segment-restricted kernel vs the dense segments oracle (interpret).

    The tape is built the way ``padded_segment_layout`` guarantees it:
    one independent segment per ``w``-aligned block, links severed at the
    boundaries (each block's prev/nxt computed in isolation)."""
    rng = np.random.default_rng(w)
    from repro.core.trace import prev_next_occurrence
    prevs, nxts, occs = [], [], []
    for b in range(4):
        addrs = rng.integers(0, max(4, w // 4), size=w).astype(np.int64)
        p, x = prev_next_occurrence(addrs)
        prevs.append(np.where(p >= 0, p + b * w, -1))
        nxts.append(np.minimum(x, w) + b * w)
        occs.append((rng.random(w) < 0.7).astype(np.int32))
    prev, nxt = np.concatenate(prevs), np.concatenate(nxts)
    occ = np.concatenate(occs)
    out = cache_sim_segments_scan(jnp.asarray(prev, jnp.int32),
                                  jnp.asarray(nxt, jnp.int32),
                                  jnp.asarray(occ), seg_width=w,
                                  tile=tile, interpret=True)
    ref = cache_sim_segments_ref(jnp.asarray(prev, jnp.int32),
                                 jnp.asarray(nxt, jnp.int32),
                                 jnp.asarray(occ), w)
    assert jnp.array_equal(out, ref)


@pytest.mark.parametrize("n,tile", [(64, 16), (200, 64)])
@slow_sweep
def test_live_count_scan_sweep(n, tile):
    """RO live-count kernel vs the dense (i, j)-plane oracle (interpret)."""
    rng = np.random.default_rng(n)
    addrs = rng.integers(0, 30, n).astype(np.int64)
    from repro.core.trace import prev_next_occurrence
    _, nxt = prev_next_occurrence(addrs)
    nxt = np.minimum(nxt, n)
    occ = (rng.random(n) < 0.6).astype(np.int32)
    out = live_count_scan(jnp.asarray(nxt, jnp.int32),
                          jnp.asarray(occ), tile=tile, interpret=True)
    ref = live_counts_ref(jnp.asarray(nxt, jnp.int32), jnp.asarray(occ))
    assert jnp.array_equal(out, ref)


@pytest.mark.parametrize("n,tile", [(64, 16), (257, 64)])
@slow_sweep
def test_cache_sim_levels_scan_sweep(n, tile):
    """Two-level residency-mask kernel vs the jnp oracle (interpret)."""
    rng = np.random.default_rng(n)
    addrs = rng.integers(0, max(4, n // 6), size=n).astype(np.int64)
    from repro.core.trace import prev_next_occurrence
    prev, nxt = prev_next_occurrence(addrs)
    occ = (rng.random(n) < 0.7).astype(np.int32)
    cap1 = rng.integers(0, 6, n).astype(np.int32)
    captot = cap1 + rng.integers(0, 6, n).astype(np.int32)
    l1, un = cache_sim_levels_scan(jnp.asarray(prev, jnp.int32),
                                   jnp.asarray(nxt, jnp.int32),
                                   jnp.asarray(occ), jnp.asarray(cap1),
                                   jnp.asarray(captot), tile=tile,
                                   interpret=True)
    r1, ru = cache_sim_levels_ref(jnp.asarray(prev, jnp.int32),
                                  jnp.asarray(nxt, jnp.int32),
                                  jnp.asarray(occ), jnp.asarray(cap1),
                                  jnp.asarray(captot))
    assert jnp.array_equal(l1, r1) and jnp.array_equal(un, ru)


def test_ops_wrappers_dispatch_cpu():
    """ops.py jit wrappers run (reference path) on CPU."""
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.paged_attention.ops import paged_attention_op
    from repro.kernels.mamba2_ssd.ops import mamba2_ssd_op
    from repro.kernels.urd_scan.ops import urd_scan_op
    q = jax.random.normal(KEY, (1, 2, 32, 16))
    o = flash_attention_op(q, q, q)
    assert o.shape == (1, 2, 32, 16)
    q2 = jax.random.normal(KEY, (2, 4, 16))
    kp = jax.random.normal(KEY, (8, 4, 2, 16))
    tb = jnp.zeros((2, 2), jnp.int32)
    ln = jnp.array([3, 5], jnp.int32)
    o2 = paged_attention_op(q2, kp, kp, tb, ln)
    assert o2.shape == (2, 4, 16)
    x = jax.random.normal(KEY, (2, 32, 8))
    Bm = jax.random.normal(KEY, (2, 32, 4))
    dA = -jnp.ones((2, 32)) * 0.1
    o3 = mamba2_ssd_op(x, Bm, Bm, dA, chunk=16)
    assert o3.shape == x.shape
    prev = jnp.array([-1, -1, 0, 1], jnp.int32)
    nxt = jnp.array([2, 3, 4, 4], jnp.int32)
    assert urd_scan_op(prev, nxt).shape == (4,)
