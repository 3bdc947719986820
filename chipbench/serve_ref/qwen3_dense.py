"""Plain reference of a dense Qwen3 decoder (Qwen3 Technical Report,
arXiv:2505.09388; the widths of the configuration's ``model`` keys, which
use the names of the published ``config.json``).

Written from the published architecture, independent of the code under
test: it imports nothing of the serving program and takes none of its
arrays.  Per layer, in float32 with every matrix product at
``jax.default_matmul_precision("highest")``:

    x = RMSNorm(h) * w_ln1
    q, k, v = x Wq, x Wk, x Wv      (16 query heads, 8 KV heads of 128)
    q, k = RMSNorm over each head * w_q_norm / w_k_norm   (qk-norm)
    q, k = RoPE(position, theta), rotating the two halves of a head
    h += softmax(q k^T / sqrt(128) + causal mask) v   Wo   (GQA: query
         head j reads KV head j // 2)
    x = RMSNorm(h) * w_ln2
    h += (silu(x Wgate) * (x Wup)) Wdown
    logits = RMSNorm(h) * w_final  E^T   (tied embeddings E)

Attention runs in blocks of query positions; a sequence is padded at its
end to a whole number of blocks (causal: no real position sees a pad).

``fp8=True`` computes the same in float8 (``float8_e4m3fn``), one
precision below the configuration's bf16, as a float8 server would: every
operand of a matrix product (weights by output channel, activations,
queries, keys, values and attention probabilities by row) scaled to the
format's largest finite value, rounded to float8 and scaled back, with
float32 accumulation.  It is the serving cells' control.

**Weights.**  ``weights`` makes them from the run's seed in one jitted
call on the device, in the layout and types a user would load into the
serving program, which the harness hands in as a tree of shapes (names of
leaves as the program's parameter tree has them).  Matrices are truncated
normal (at two standard deviations) of standard deviation
``1/sqrt(fan_in)``; the embedding is normal with standard deviation 0.02,
its rows past ``vocab_size`` (the program's padding) zero; norm weights
are stored as offsets from one (``w = 1 + stored``, the program's form),
normal with standard deviation 0.1.  The reference regenerates them from
the same seed and reads them in float32.

**Costs** (``flops_*``, ``*_bytes``) are the model arithmetic the
benchmark's metrics use; the matrix-product FLOPs follow the program's
``launch/roofline.py`` (2 per parameter per token, 4 x head size per
visible query-key pair per query head).
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256          # query positions per attention block, rows per pad
NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")


def dims(spec: dict) -> dict:
    return {"L": spec["num_hidden_layers"], "d": spec["hidden_size"],
            "hq": spec["num_attention_heads"],
            "hkv": spec["num_key_value_heads"], "hd": spec["head_dim"],
            "ff": spec["intermediate_size"], "V": spec["vocab_size"],
            "theta": float(spec["rope_theta"]),
            "eps": float(spec["rms_norm_eps"])}


# ------------------------------------------------------------------ weights
def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, leaves: tuple, vocab: int) -> list:
    out = []
    for path, shape, dtype in leaves:
        k = _leaf_key(key, path)
        name = path.rsplit("/", 1)[-1]
        if name in NORMS:
            w = 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name == "embed":
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
            w = jnp.where(jnp.arange(shape[0])[:, None] < vocab, w, 0.0)
        else:
            w = (jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                             jnp.float32)
                 / np.sqrt(shape[-2]))
        out.append(w.astype(dtype))
    return out


def weights(seed: int, spec: dict, shapes):
    """The weights for ``seed`` in the tree of ``jax.ShapeDtypeStruct``
    ``shapes`` (the serving program's parameter layout)."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = tuple(("/".join(str(getattr(p, "key", p)) for p in path),
                    tuple(s.shape), jnp.dtype(s.dtype)) for path, s in flat)
    vals = _make(jax.random.PRNGKey(seed), leaves, spec["vocab_size"])
    return jax.tree_util.tree_unflatten(tree, vals)


def as_f32(w):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), w)


# ------------------------------------------------------------------ forward
F8_MAX = 448.0           # float8_e4m3fn's largest finite value


def _q8(x, axis=-1):
    """``x`` rounded to float8 in blocks along ``axis``, each scaled so
    its largest magnitude maps to ``F8_MAX``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * inv      # [T, 1, half]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _layer(h, p, m):
    T = h.shape[0]
    pos = jnp.arange(T)
    q8 = _q8 if m["fp8"] else (lambda x, axis=-1: x)
    x = q8(_rms(h, p["ln1"], m["eps"]))
    a = p["attn"]
    q = (x @ a["wq"]).reshape(T, m["hq"], m["hd"])
    k = (x @ a["wk"]).reshape(T, m["hkv"], m["hd"])
    v = (x @ a["wv"]).reshape(T, m["hkv"], m["hd"])
    q = q8(_rope(_rms(q, a["q_norm"], m["eps"]), pos, m["theta"]))
    k = q8(_rope(_rms(k, a["k_norm"], m["eps"]), pos, m["theta"]))
    v = q8(v)
    rep = m["hq"] // m["hkv"]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / np.sqrt(m["hd"])

    def block(args):
        qb, pb = args                                    # [B, hq, hd], [B]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        s = jnp.where(pos[None, None, :] <= pb[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", q8(jax.nn.softmax(s, -1)), v)

    nb = T // BLOCK
    o = jax.lax.map(block, (q.reshape(nb, BLOCK, m["hq"], m["hd"]),
                            pos.reshape(nb, BLOCK)))
    h = h + q8(o.reshape(T, m["hq"] * m["hd"])) @ a["wo"]
    x = q8(_rms(h, p["ln2"], m["eps"]))
    f = p["mlp"]
    return h + q8(jax.nn.silu(x @ f["w_gate"]) * (x @ f["w_up"])) \
        @ f["w_down"]


@partial(jax.jit, static_argnums=(2,))
def _hidden(w, tokens, mt: tuple):
    m = dict(mt)
    with jax.default_matmul_precision("highest"):
        h = w["embed"][tokens]
        h, _ = jax.lax.scan(lambda c, p: (_layer(c, p, m), None), h,
                            w["layers"])
        return _rms(h, w["final_norm"], m["eps"])


@partial(jax.jit, static_argnums=(2, 3))
def _logits(w, rows, vocab: int, fp8: bool):
    with jax.default_matmul_precision("highest"):
        if fp8:
            rows = _q8(rows)
        return rows @ w["embed"][:vocab].T


def fp8_weights(w32):
    """The float32 weights rounded to float8 by output channel (the
    embedding by row, as the output head reads it); norms stay."""
    def one(path, x):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in NORMS:
            return x
        return _q8(x, axis=-1 if name == "embed" else -2)
    return jax.tree_util.tree_map_with_path(one, w32)


def logits(w32, spec: dict, tokens: np.ndarray, rows: np.ndarray,
           fp8: bool = False) -> np.ndarray:
    """Reference logits ``[len(rows), vocab_size]`` (float32) at positions
    ``rows`` of the sequence ``tokens``; ``w32`` is ``as_f32(weights)``
    (``fp8_weights(as_f32(weights))`` with ``fp8``)."""
    m = dict(dims(spec), fp8=bool(fp8))
    T = len(tokens)
    tp = -(-T // BLOCK) * BLOCK
    toks = np.zeros(tp, np.int32)
    toks[:T] = tokens
    h = _hidden(w32, jnp.asarray(toks), tuple(sorted(m.items())))
    out = []
    for i in range(0, len(rows), BLOCK):
        r = np.zeros(BLOCK, np.int32)
        part = rows[i:i + BLOCK]
        r[:len(part)] = part
        out.append(np.asarray(_logits(w32, h[jnp.asarray(r)], m["V"], fp8))
                   [:len(part)])
    return np.concatenate(out) if out else np.zeros((0, m["V"]), np.float32)


# -------------------------------------------------------------------- costs
def layer_params(spec: dict) -> int:
    """Matrix parameters of the decoder layers (norms left out)."""
    m = dims(spec)
    d, hd = m["d"], m["hd"]
    per = d * m["hq"] * hd * 2 + d * m["hkv"] * hd * 2 + 3 * d * m["ff"]
    return m["L"] * per


def head_params(spec: dict) -> int:
    """The output head (the tied embedding): vocabulary x hidden size."""
    m = dims(spec)
    return m["V"] * m["d"]


def _attn_pair_flops(spec: dict) -> float:
    m = dims(spec)
    return 4.0 * m["hd"] * m["hq"] * m["L"]


def flops_prompt(spec: dict, n: int) -> float:
    """Prefill of an ``n``-token prompt with no prefix reused: every
    position through the layers, causal attention, and the output head at
    the last position only (where the first token comes from)."""
    return (2.0 * layer_params(spec) * n + 2.0 * head_params(spec)
            + _attn_pair_flops(spec) * n * (n + 1) / 2.0)


def flops_decode(spec: dict, ctx: int) -> float:
    """One generated token over ``ctx`` tokens already cached: the layers,
    the output head, and attention over ``ctx + 1`` keys."""
    return (2.0 * (layer_params(spec) + head_params(spec))
            + _attn_pair_flops(spec) * (ctx + 1))


def weight_bytes(spec: dict, bytes_per: int = 2) -> float:
    """Bytes of the matrices a decode step reads once (bf16)."""
    return float(bytes_per * (layer_params(spec) + head_params(spec)))


def kv_bytes_per_token(spec: dict, bytes_per: int = 2) -> float:
    """K and V of one token over every layer (bf16)."""
    m = dims(spec)
    return float(2 * m["L"] * m["hkv"] * m["hd"] * bytes_per)
