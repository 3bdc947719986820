"""Multi-tenant continuous-batching serving engine over the paged pool.

Request lifecycle:
  * prefill — the prompt's KV is computed layer-stacked; the prompt is cut
    into pages; pages whose content key (tenant, prefix-hash) is already in
    the HBM pool are *reused* (read events to ECI-Cache — no recompute
    charge); fresh pages are *admitted* per the tenant's write policy
    (write events).
  * decode — batched single-token steps; attention runs over the pool
    through per-request block tables (the ``paged_attention`` kernel path
    on TPU, its jnp oracle here).  Completed pages become admission events.

The ECI manager observes the event stream; every ``window_events`` it
re-partitions page quotas + write policies across tenants (Actuator =
``BlockPool.enforce_quota``).  The decode path is the "performance" the
paper's hit ratio protects: pages served from the HBM pool avoid the
host-tier fetch penalty.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.block_pool import BlockPool
from repro.cache.tiered import TieredKVCache
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models.attention import build_heads
from repro.models.config import Family, ModelConfig
from repro.models.layers import rms_norm, swiglu, moe_ffn, apply_rope
from repro.models.model import Param

__all__ = ["Request", "MultiTenantEngine", "prefill_with_kv"]

_F32 = jnp.float32


@dataclasses.dataclass
class Request:
    tenant: int
    prompt: np.ndarray                   # int32[S]
    max_new_tokens: int = 16
    rid: int = -1
    generated: list[int] = dataclasses.field(default_factory=list)
    pages: list[int] = dataclasses.field(default_factory=list)   # pool pids
    length: int = 0                      # tokens with KV in the pool
    done: bool = False


def _prefix_key(tenant: int, tokens: np.ndarray) -> tuple:
    return (tenant, hash(tokens.tobytes()))


@partial(jax.jit, static_argnames=("cfg", "tp"))
def prefill_with_kv(params: Param, cfg: ModelConfig, tokens: jax.Array,
                    tp: int = 1, last: jax.Array | int = -1):
    """Forward returning (logits at position ``last``, k [L,B,S,Hkv,D],
    v [L,B,S,Hkv,D]).  ``last`` is the prompt's final real token when the
    tokens are padded to whole pages."""
    from repro.models.model import _attn_mlp_block, _lm_head  # noqa
    hq, hkv = build_heads(cfg, tp)
    B, S = tokens.shape
    h = params["embed"][tokens]
    positions = jnp.arange(S)[None, :]

    def body(carry, p_l):
        hh = carry
        x = rms_norm(hh, p_l["ln1"], cfg.rms_eps)
        k = jnp.einsum("bsd,de->bse", x, p_l["attn"]["wk"],
                       preferred_element_type=_F32).astype(x.dtype)
        v = jnp.einsum("bsd,de->bse", x, p_l["attn"]["wv"],
                       preferred_element_type=_F32).astype(x.dtype)
        k = k.reshape(B, S, hkv, cfg.head_dim)
        v = v.reshape(B, S, hkv, cfg.head_dim)
        if cfg.qk_norm:
            k = rms_norm(k, p_l["attn"]["k_norm"], cfg.rms_eps)
        k = apply_rope(k, positions, cfg.rope_theta)
        hh = _attn_mlp_block(p_l, hh, cfg, tp)
        return hh, (k, v)

    h, (ks, vs) = jax.lax.scan(body, h, params["layers"])
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    logits = jnp.einsum("bd,dv->bv", h[:, last], _lm_head(params, cfg),
                        preferred_element_type=_F32)
    return logits, ks, vs


class MultiTenantEngine:
    """CPU-runnable reference engine (smoke-scale models)."""

    def __init__(self, cfg: ModelConfig, params: Param,
                 tiered: TieredKVCache, page_size: int = 16,
                 max_pages_per_seq: int = 64, tp: int = 1):
        assert cfg.family in (Family.DENSE, Family.MOE), \
            "reference engine covers attention-KV families"
        self.cfg, self.params, self.tp = cfg, params, tp
        self.tiered = tiered
        self.pool: BlockPool = tiered.pool
        self.page = page_size
        self.max_pages = max_pages_per_seq
        self.active: list[Request] = []
        self.waiting: list[Request] = []
        self._rid = 0
        self.completed: list[Request] = []
        self.aborted_restarts = 0    # requests restarted after a pool crash

    # ------------------------------------------------------------ intake
    def submit(self, req: Request) -> None:
        req.rid = self._rid
        self._rid += 1
        self.waiting.append(req)

    # ----------------------------------------------------------- prefill
    def _prefill_one(self, req: Request) -> None:
        cfg, page = self.cfg, self.page
        prompt = np.asarray(req.prompt, np.int32)
        S = len(prompt)
        n_pages = -(-S // page)
        pad = n_pages * page - S
        tok = jnp.asarray(np.pad(prompt, (0, pad))[None, :])
        logits, ks, vs = prefill_with_kv(self.params, cfg, tok, self.tp,
                                         S - 1)

        for pi in range(n_pages):
            key = _prefix_key(req.tenant, prompt[:(pi + 1) * page])
            pid = self.pool.lookup(key)
            if pid is not None:
                self.tiered.access_page(req.tenant, key, fresh=False)
            else:
                self.tiered.access_page(req.tenant, key, fresh=True)
                pid = self.pool.by_key.get(key)
                if pid is not None:
                    sl = slice(pi * page, (pi + 1) * page)
                    self.pool.write_page(ks[:, 0, sl], vs[:, 0, sl], pid)
            if pid is None:
                # bypassed (RO) or over quota: the page logically lives in
                # the host tier; stage it unmanaged (tenant -2) so decode
                # can still attend — latency accounting treats it as a
                # host-tier fetch, and it never counts against quotas.
                pid, _ = self.pool.allocate(-2, None, quota=None)
                if pid is not None:
                    sl = slice(pi * page, (pi + 1) * page)
                    self.pool.write_page(ks[:, 0, sl], vs[:, 0, sl], pid)
            if pid is None:
                raise RuntimeError(
                    f"KV pool exhausted: no page for request {req.rid} "
                    f"(prompt page {pi}); every page is pinned")
            self.pool.pin(pid)
            req.pages.append(pid)
        req.length = S
        req.generated.append(int(jnp.argmax(logits[0, :cfg.vocab_size])))
        self.active.append(req)

    # ------------------------------------------------------------ decode
    def _decode_batch(self) -> None:
        cfg, page = self.cfg, self.page
        reqs = [r for r in self.active if not r.done]
        if not reqs:
            return
        B = len(reqs)
        # ensure every request has a page with room for the next token
        for r in reqs:
            if r.length % page == 0:
                key = (r.tenant, "decode", r.rid, r.length // page)
                self.tiered.access_page(r.tenant, key, fresh=True)
                pid = self.pool.by_key.get(key)
                if pid is None:
                    pid, _ = self.pool.allocate(-2, None, quota=None)
                if pid is None:
                    raise RuntimeError(
                        f"KV pool exhausted: no decode page for request "
                        f"{r.rid}; every page is pinned")
                self.pool.pin(pid)
                r.pages.append(pid)

        tables = np.zeros((B, self.max_pages), np.int32)
        lens = np.zeros((B,), np.int32)
        toks = np.zeros((B,), np.int32)
        for i, r in enumerate(reqs):
            tables[i, :len(r.pages)] = r.pages
            lens[i] = r.length
            toks[i] = r.generated[-1]
        logits, k_new, v_new = _decode_step_jit(
            self.params, self.pool.k_pages, self.pool.v_pages,
            jnp.asarray(toks), jnp.asarray(tables), jnp.asarray(lens),
            self.cfg, self.tp)
        # write the new token's KV into each request's current page
        self.pool.write_tokens(
            k_new, v_new, [r.pages[r.length // page] for r in reqs],
            [r.length % page for r in reqs])
        nxt = np.asarray(jnp.argmax(logits[:, :cfg.vocab_size], axis=-1))
        for i, r in enumerate(reqs):
            r.length += 1
            r.generated.append(int(nxt[i]))
            if len(r.generated) >= r.max_new_tokens:
                r.done = True
                self.completed.append(r)
                for pid in r.pages:           # pages stay cached (prefix
                    self.pool.unpin(pid)      # reuse), but become evictable
        self.active = [r for r in self.active if not r.done]

    # ----------------------------------------------------- tier failures
    def _requeue_active(self) -> None:
        """After an HBM-pool crash every in-flight request's KV is gone:
        abort them and restart from the prompt (prepended to ``waiting``
        so they re-admit first once the tier recovers)."""
        for r in self.active:
            for pid in r.pages:
                self.pool.unpin(pid)       # no-op post-crash; safe anytime
            r.pages = []
            r.generated = []
            r.length = 0
            r.done = False
            self.aborted_restarts += 1
        self.waiting[:0] = self.active
        self.active = []

    # -------------------------------------------------------------- loop
    def step(self) -> None:
        if self.tiered.tier_down(1):
            # admission control: no prefill/decode against a dead pool —
            # in-flight work restarts once, new work queues until recovery
            if self.active:
                self._requeue_active()
            return
        while self.waiting:
            self._prefill_one(self.waiting.pop(0))
        self._decode_batch()

    def run(self, max_steps: int = 256) -> None:
        for _ in range(max_steps):
            if not (self.waiting or self.active):
                break
            self.step()


@partial(jax.jit, static_argnames=("cfg", "tp"))
def _decode_step_jit(params, k_pool, v_pool, toks, tables, lens,
                     cfg: ModelConfig, tp: int):
    """Batched one-token decode over the paged pool (jnp oracle path)."""
    hq, hkv = build_heads(cfg, tp)
    B = toks.shape[0]
    h = params["embed"][toks][:, None, :]
    positions = lens[:, None]

    def body(carry, xs):
        hh = carry
        p_l, k_pg, v_pg = xs
        x = rms_norm(hh, p_l["ln1"], cfg.rms_eps)
        q = jnp.einsum("bsd,de->bse", x, p_l["attn"]["wq"],
                       preferred_element_type=_F32).astype(x.dtype)
        q = q.reshape(B, 1, hq, cfg.head_dim)
        k = jnp.einsum("bsd,de->bse", x, p_l["attn"]["wk"],
                       preferred_element_type=_F32).astype(x.dtype)
        k = k.reshape(B, 1, hkv, cfg.head_dim)
        v = jnp.einsum("bsd,de->bse", x, p_l["attn"]["wv"],
                       preferred_element_type=_F32).astype(x.dtype)
        v = v.reshape(B, 1, hkv, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, p_l["attn"]["q_norm"], cfg.rms_eps)
            k = rms_norm(k, p_l["attn"]["k_norm"], cfg.rms_eps)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        att = paged_attention_ref(q[:, 0], k_pg, v_pg, tables, lens)
        # exact online merge of the in-flight token's KV (not yet pooled)
        att = _merge_self(q[:, 0], k[:, 0], v[:, 0], att, k_pg, tables,
                          lens, cfg.head_dim, hq, hkv)
        a = att.reshape(B, 1, hq * cfg.head_dim)
        a = jnp.einsum("bse,ed->bsd", a, p_l["attn"]["wo"],
                       preferred_element_type=_F32).astype(x.dtype)
        hh = hh + a
        hn = rms_norm(hh, p_l["ln2"], cfg.rms_eps)
        if cfg.family == Family.MOE:
            hh = hh + moe_ffn(hn, p_l["mlp"], cfg, ep=tp)
        else:
            hh = hh + swiglu(hn, p_l["mlp"]["w_gate"], p_l["mlp"]["w_up"],
                             p_l["mlp"]["w_down"])
        return hh, (k[:, 0], v[:, 0])

    h, (k_new, v_new) = jax.lax.scan(
        body, h, (params["layers"], k_pool, v_pool))
    from repro.models.model import _lm_head
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    logits = jnp.einsum("bd,dv->bv", h[:, 0], _lm_head(params, cfg),
                        preferred_element_type=_F32)
    return logits, k_new, v_new


def _merge_self(q, k_self, v_self, att_pool, k_pg, tables, lens,
                head_dim, hq, hkv):
    """Exact online merge of the current token's KV with pooled attention."""
    rep = hq // hkv
    kr = jnp.repeat(k_self, rep, axis=1)
    vr = jnp.repeat(v_self, rep, axis=1)
    scale = 1.0 / np.sqrt(head_dim)
    # recompute pool logits' logsumexp for exact combination
    from repro.kernels.paged_attention.ref import gather_pages
    kp = gather_pages(k_pg, tables)
    kp = jnp.repeat(kp, rep, axis=2)
    s_pool = jnp.einsum("bhd,bkhd->bhk", q.astype(_F32),
                        kp.astype(_F32)) * scale
    mask = jnp.arange(kp.shape[1])[None, None, :] < lens[:, None, None]
    s_pool = jnp.where(mask, s_pool, -1e30)
    lse_pool = jax.nn.logsumexp(s_pool, axis=-1)
    s_self = jnp.einsum("bhd,bhd->bh", q.astype(_F32),
                        kr.astype(_F32)) * scale
    lse_all = jnp.logaddexp(lse_pool, s_self)
    w_pool = jnp.exp(lse_pool - lse_all)[..., None]
    w_self = jnp.exp(s_self - lse_all)[..., None]
    return (att_pool.astype(_F32) * w_pool
            + vr.astype(_F32) * w_self).astype(att_pool.dtype)
