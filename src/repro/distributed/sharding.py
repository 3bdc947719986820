"""Logical→physical sharding rules for every param/cache/batch tree.

Strategy (DESIGN.md §6): FSDP over ``data`` (every weight also sharded on a
non-TP dim) × TP over ``model`` (heads/ffn/experts/vocab) × DP over
``pod``+``data``; decode KV caches shard their *sequence* axis over
``model`` (flash-decoding split-KV).

Rules are (regex over tree path, dims) — ``dims`` names a mesh axis per
tensor dim or None.  ``spec_for`` drops any axis whose size does not divide
the dim (safety: replication instead of a compile error), so one rule table
serves all 10 architectures.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["param_specs", "batch_specs", "cache_specs", "state_specs",
           "spec_for", "DP", "control_plane_mesh"]


def DP(mesh) -> tuple[str, ...] | str:
    names = mesh.axis_names
    axes = tuple(a for a in ("pod", "data") if a in names)
    return axes if len(axes) > 1 else axes[0]


# (path regex, per-dim mesh axes).  First match wins.  Paths look like
# "layers/attn/wq", "layers/mlp/w_gate", "embed", "shared_attn/attn/wo" …
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed$",                   ("model", "data")),
    (r"lm_head$",                 (None, "model")),   # vocab-only: no per-step FSDP gather of the 6 GB head
    # attention (stacked layers get a leading None automatically)
    (r"attn/wq$",                 ("data", "model")),
    (r"attn/wk$",                 ("data", "model")),
    (r"attn/wv$",                 ("data", "model")),
    (r"attn/wo$",                 ("model", "data")),
    (r"cross/wq$",                ("data", "model")),
    (r"cross/wk$",                ("data", "model")),
    (r"cross/wv$",                ("data", "model")),
    (r"cross/wo$",                ("model", "data")),
    (r"attn/wq_a$",               ("data", "model")),
    (r"attn/wq_b$",               ("data", "model")),
    (r"attn/wkv_a$",              ("data", None)),
    (r"attn/wkv_b$",              ("data", "model")),
    (r"attn/(q_norm|k_norm|q_a_norm|kv_a_norm)$", (None,)),
    # dense / shared-expert FFN
    (r"mlp/w_gate$",              ("data", "model")),
    (r"mlp/w_up$",                ("data", "model")),
    (r"mlp/w_down$",              ("model", "data")),
    (r"mlp/shared_gate$",         ("data", "model")),
    (r"mlp/shared_up$",           ("data", "model")),
    (r"mlp/shared_down$",         ("model", "data")),
    # MoE experts: EP over model, FSDP over data
    (r"mlp/router$",              ("data", None)),
    (r"mlp/w_gate_e|experts",     ("model", "data", None)),
    # mamba2
    (r"mixer/in_proj$",           ("data", "model")),
    (r"mixer/conv_w$",            (None, "model")),
    (r"mixer/conv_b$",            ("model",)),
    (r"mixer/(A_log|D|dt_bias)$", ("model",)),
    (r"mixer/out_proj$",          ("model", "data")),
    (r"mixer/norm$",              ("model",)),
    # norms and everything small: replicate
    (r"(ln1|ln2|ln_x|final_norm|enc_norm|norm)$", None),
]

# MoE expert tensors are 3-D [E, d, ff] under stacked layers -> 4-D.
_MOE_EXPERT = re.compile(r"mlp/w_(gate|up|down)$")


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def spec_for(path_str: str, shape: tuple[int, ...], mesh) -> P:
    """Resolve the rule table for one leaf, with divisibility fallback."""
    stacked = path_str.startswith(("layers/", "enc_layers/"))
    axes_by_name = dict(zip(mesh.axis_names, mesh.devices.shape))

    dims: tuple | None = None
    # distinguish expert tensors (rank 3 + stacking) from dense mlp (rank 2)
    rank = len(shape) - (1 if stacked else 0)
    if _MOE_EXPERT.search(path_str) and rank == 3:
        name = path_str.rsplit("/", 1)[-1]
        if name == "w_down":
            dims = ("model", None, "data")
        else:
            dims = ("model", "data", None)
    else:
        for pat, d in _PARAM_RULES:
            if re.search(pat, path_str):
                dims = d
                break
    if dims is None:
        return P()
    if stacked:
        dims = (None, *dims)
    dims = tuple(dims[:len(shape)]) + (None,) * (len(shape) - len(dims))
    # divisibility fallback: replicate dims the mesh axis cannot divide
    fixed = []
    for size, ax in zip(shape, dims):
        if ax is None:
            fixed.append(None)
        elif size % axes_by_name.get(ax, 1) == 0:
            fixed.append(ax)
        else:
            fixed.append(None)
    return P(*fixed)


def param_specs(params, mesh):
    """Tree of NamedShardings matching a param tree (or its eval_shape)."""
    def one(path, leaf):
        return NamedSharding(mesh, spec_for(_path_str(path), leaf.shape,
                                            mesh))
    return jax.tree_util.tree_map_with_path(one, params)


def _div_ok(n: int, mesh, axes) -> bool:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = int(np.prod([sizes[a] for a in (axes if isinstance(axes, tuple)
                                            else (axes,))]))
    return n % total == 0


def batch_specs(batch, mesh):
    """tokens/labels [B,S] + optional enc_embeds [B,S,d]: DP over batch."""
    dp = DP(mesh)

    def one(path, leaf):
        b = leaf.shape[0]
        axes = dp if _div_ok(b, mesh, dp) else (
            "data" if _div_ok(b, mesh, "data") else None)
        if axes is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(axes, *([None] * (len(leaf.shape) - 1))))
    return jax.tree_util.tree_map_with_path(one, batch)


def cache_specs(cache, mesh):
    """Decode caches: batch→DP when divisible; sequence axis→model.

    Layouts: kv k/v [L,B,S,H,D]; MLA c_kv/k_rope [L,B,S,r]; ssm conv
    [L,B,K,C] / ssm [L,B,H,N,P]; cross_k/v [L,B,Se,H,D]; len [B].
    """
    dp = DP(mesh)

    def one(path, leaf):
        ps = _path_str(path)
        shp = leaf.shape
        if ps in ("len", "enc_len"):
            return NamedSharding(
                mesh, P(dp if _div_ok(shp[0], mesh, dp) else None))
        dims: list = [None] * len(shp)
        if len(shp) >= 2:
            # dim 1 is batch
            if _div_ok(shp[1], mesh, dp):
                dims[1] = dp
            elif _div_ok(shp[1], mesh, "data"):
                dims[1] = "data"
        if ps.startswith(("kv/", "cross_")):
            # [L, B, S, ...]: shard sequence over model (split-KV decode);
            # with batch unshardable (long-context B=1), also spread seq
            # over the data axis.
            seq_axes = ("model",) if dims[1] is not None else ("data", "model")
            cand = tuple(a for a in seq_axes if a in mesh.axis_names)
            if _div_ok(shp[2], mesh, cand):
                dims[2] = cand if len(cand) > 1 else cand[0]
        elif ps.startswith("ssm/"):
            # conv [L,B,K,C]: C→model; ssm [L,B,H,N,P]: H→model
            if ps.endswith("conv") and _div_ok(shp[3], mesh, "model"):
                dims[3] = "model"
            elif ps.endswith("ssm") and _div_ok(shp[2], mesh, "model"):
                dims[2] = "model"
        return NamedSharding(mesh, P(*dims))
    return jax.tree_util.tree_map_with_path(one, cache)


def state_specs(state, mesh):
    """Train state {params, opt{master,mu,nu,count}, step}."""
    pspecs = param_specs(state["params"], mesh)
    return {
        "params": pspecs,
        "opt": {
            "master": pspecs, "mu": pspecs, "nu": pspecs,
            "count": NamedSharding(mesh, P()),
        },
        "step": NamedSharding(mesh, P()),
    }


# ---------------------------------------------------------------- control plane
def control_plane_mesh(n_shards: int | None = None):
    """1-D ``("shards",)`` mesh for the sharded control plane.

    The ECI control-plane shard pipeline (``core.shard_pipeline``)
    partitions the window tape by whole tenant-segments over this axis.
    Uses every local device by default; ``n_shards`` asks for exactly
    that many shards and raises ``ValueError`` when the host has fewer
    devices.  On CPU hosts the test/CI harness forces
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the mesh
    exercises real multi-device semantics everywhere.
    """
    devices = jax.devices()
    k = len(devices) if n_shards is None else int(n_shards)
    if not 1 <= k <= len(devices):
        raise ValueError(f"control_plane_mesh: asked for {k} shards, "
                         f"{len(devices)} devices exist")
    return jax.sharding.Mesh(np.array(devices[:k]), ("shards",))
