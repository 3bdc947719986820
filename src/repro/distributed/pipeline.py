"""GPipe-style pipeline parallelism over the ``pod`` (DCN) axis.

For cross-pod scale-out an alternative to pure DP is to place layer ranges
(stages) on different pods and stream microbatches through a
``collective_permute`` ring: only stage-boundary activations cross the DCN
link (B_mb × S × d bytes per tick) instead of full gradient reductions.

Implementation: ``shard_map`` over the pipeline axis; each device group
holds its stage's layer slice (params pre-sharded with leading stage dim);
the classic GPipe schedule runs n_micro + n_stages - 1 ticks with bubble
fraction (S-1)/(M+S-1).

Provided as an opt-in feature (DP over ``pod`` is the default):
``pipeline_forward`` is the composable primitive (works under jit, grads
flow through ``ppermute``), exercised by ``tests/test_pipeline.py`` and the
``--tag pp_demo`` dry-run variant.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward", "stack_stages"]


def stack_stages(params, n_stages: int):
    """Split a stacked-layer param tree [L, ...] into [n_stages, L/S, ...]."""
    def one(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])
    return jax.tree.map(one, params)


def pipeline_forward(stage_fn, stage_params, x, *, mesh, axis: str = "pod",
                     n_microbatches: int = 2):
    """Run ``x`` [B, ...] through n_stages sequential stages on the ``axis``
    ring of ``mesh``.

    stage_fn(stage_params_slice, h) -> h : applies one stage's layers.
    stage_params: tree with leading [n_stages, ...] (sharded over ``axis``).
    Returns the final-stage output, valid on every device (broadcast back).
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    B = x.shape[0]
    assert B % n_microbatches == 0
    mb = B // n_microbatches

    other_axes = [a for a in mesh.axis_names if a != axis]

    def per_pod(p_stage, x_local):
        # p_stage: this pod's layer slice (leading stage dim stripped to 1)
        p_my = jax.tree.map(lambda t: t[0], p_stage)
        stage = jax.lax.axis_index(axis)
        ticks = n_microbatches + n_stages - 1
        x_mb = x_local.reshape(n_microbatches, mb, *x_local.shape[1:])

        carry_in = jnp.zeros((mb, *x_local.shape[1:]), x_local.dtype)
        outs = jnp.zeros((n_microbatches, mb, *x_local.shape[1:]),
                         x_local.dtype)

        def tick(t, state):
            carry, outs = state
            # stage 0 injects microbatch t (when available)
            inject = x_mb[jnp.clip(t, 0, n_microbatches - 1)]
            h_in = jnp.where(stage == 0, inject, carry)
            h_out = stage_fn(p_my, h_in)
            # last stage collects microbatch (t - (n_stages - 1))
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
            take = jnp.logical_and(stage == n_stages - 1,
                                   t >= n_stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(take, h_out, outs[out_idx]),
                out_idx, axis=0)
            # forward the activation ring: stage i -> i+1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            carry = jax.lax.ppermute(h_out, axis, perm)
            return carry, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (carry_in, outs))
        # broadcast final-stage results to every pod (psum of masked outs)
        mask = (stage == n_stages - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * mask, axis)
        return outs.reshape(B, *x_local.shape[1:])

    in_specs = (P(axis), P(*[None] * x.ndim))
    out_specs = P(*[None] * x.ndim)
    fn = jax.shard_map(per_pod, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(stage_params, x)
