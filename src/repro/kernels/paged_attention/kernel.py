"""Paged decode attention — Pallas TPU kernel with block-table indirection.

This is the serving-side hot path of the ECI-Cache integration: the KV pages
of a request live scattered in the HBM block pool (the paper's "SSD cache"),
located through a per-request block table.  The kernel walks the table with
*scalar prefetch* (``pltpu.PrefetchScalarGridSpec``) so the page index feeds
the BlockSpec ``index_map`` — Pallas issues the HBM→VMEM DMA for page ``i+1``
while page ``i`` is being processed, hiding the gather latency the same way
vLLM's paged attention hides it with warp-level prefetch on GPU (TPU
adaptation: DMA double-buffering replaces warp scheduling).

Grid: (batch, kv_heads, pages_per_seq); the page axis is innermost /
sequential with fp32 running (m, l, acc) scratch for the online softmax over
the q-head group that shares each KV head (GQA).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["paged_attention"]

_NEG_INF = -1e30


def _kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, page_size: int):
    b = pl.program_id(0)
    pi = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]
    page_start = pi * page_size

    @pl.when(page_start < seq_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # [g, d]
        k = k_ref[0, :, 0].astype(jnp.float32)          # [page, d]
        v = v_ref[0, :, 0].astype(jnp.float32)          # [page, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [g, page]
        pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, _NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == n_pages - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, seq_lens: jax.Array, *,
                    scale: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """Decode attention over a paged KV pool.

    q:            [B, Hq, D]      (one new token per sequence)
    k/v_pages:    [n_pool_pages, page_size, Hkv, D]
    block_tables: [B, pages_per_seq] int32 (pool page ids, 0-padded)
    seq_lens:     [B] int32 valid KV length per sequence
    returns       [B, Hq, D]
    """
    B, Hq, D = q.shape
    n_pool, page_size, Hkv, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    g = Hq // Hkv
    if scale is None:
        scale = 1.0 / np.sqrt(D)

    q_g = q.reshape(B, Hkv, g, D)
    kernel = functools.partial(_kernel, scale=scale, page_size=page_size)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, g, D),
                         lambda b, h, pi, tables, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, page_size, 1, D),
                         lambda b, h, pi, tables, lens: (tables[b, pi], 0, h, 0)),
            pl.BlockSpec((1, page_size, 1, D),
                         lambda b, h, pi, tables, lens: (tables[b, pi], 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, D),
                               lambda b, h, pi, tables, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, seq_lens, q_g, k_pages, v_pages)
    return out.reshape(B, Hq, D)
