"""Flash attention (prefill) — Pallas TPU kernel.

Tiling: grid = (batch, q_heads, num_q_blocks, num_kv_blocks) with the KV
block axis innermost (sequential on TPU), so the fp32 running max / sum /
accumulator live in VMEM scratch and persist across KV iterations — the
canonical online-softmax schedule (FlashAttention-2 adapted to the MXU:
[block_q, head_dim] × [head_dim, block_kv] contractions hit the 128×128
systolic array when block sizes are multiples of 128).

Causal + sliding-window masking is applied per tile; fully-masked tiles are
skipped via ``pl.when`` on the block indices (the triangular schedule), so
the causal kernel does ~half the tile work of the dense one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int,
            block_q: int, block_kv: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_kv

    # tile-level reachability: skip tiles fully outside the causal/window band
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window > 0:
        reachable = jnp.logical_and(
            reachable, k_start + block_kv - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 1)
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window > 0:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]                           # [bq, 1]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.named_call, name="flash_attention_pallas")
def _noop(x):
    return x


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: [B, Hq, Sq, D]; k/v: [B, Hq, Skv, D] (caller repeats GQA heads).

    Returns [B, Hq, Sq, Dv].  Sequences are padded to block multiples
    internally; padded KV positions are masked.
    """
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    Dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    block_q = min(block_q, max(Sq, 8))
    block_kv = min(block_kv, max(Skv, 8))
    nq = -(-Sq // block_q)
    nk = -(-Skv // block_kv)
    pq, pk = nq * block_q - Sq, nk * block_kv - Skv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, seq_len=Skv)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, Dv), lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq, :]
