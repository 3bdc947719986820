"""The serving cells on the CPU at the qwen3 smoke preset: the traffic
generator, the run end to end with its check, the control and the planted
faults, a throwaway configuration through the dispatch, and the control
plane's cells through the same dispatch."""
import json
import math

import numpy as np
import pytest

from conftest import BENCH, ROOT

SEED = 2**31 + 4242
SERVING = ["shared-sys512", "noshare-short"]


def smoke_bench(tmp_path, traffic="noshare-short", **mix_over):
    """``BENCHMARK.json`` with a throwaway configuration, ``qwen3-smoke``
    (the qwen3 smoke preset's widths, a small pool), and a cell
    ``smoke.<traffic>`` of it, written as files and found by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "qwen3-0.6b-kv.json").read_text())
    cfg["smoke_arch"] = "qwen3_0_6b"
    cfg["model"].update(num_hidden_layers=2, hidden_size=64,
                        num_attention_heads=4, num_key_value_heads=2,
                        head_dim=16, intermediate_size=128, vocab_size=512,
                        rope_theta=10000.0)
    cfg["pool"]["pages"] = 768
    cfg["manager"].update(capacity=96, initial_blocks=12)
    cfg["tiered"]["window_events"] = 96
    (tmp_path / "smoke.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    mix.update(requests=16, warm_batch=4)
    mix["prompt"] = dict(mix["prompt"], max=48)
    mix["output"] = dict(mix["output"], min=4, max=24)
    mix["arrivals"] = dict(mix["arrivals"], rate=40.0)
    mix.update(mix_over)
    name = f"smoke.{traffic}"
    bench["configs"].append({"name": "qwen3-smoke", "source": "test",
                             "file": str(tmp_path / "smoke.json"),
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": name, "config": "qwen3-smoke",
                               "traffic": traffic, "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "kv-qwen3-noshare" in m.get("workloads", ()):
            m["workloads"] = [*m["workloads"], name]
    return name, bench, mix


@pytest.fixture
def smoke(tmp_path, monkeypatch):
    """A runner of the throwaway cell: ``go(traffic, **run_cell kw)``."""
    import jax
    import run as RUN
    import serve_cell as S
    monkeypatch.setattr(RUN, "setup_jax", lambda: jax)

    def go(traffic="noshare-short", seed=SEED, trace=False, mix=None,
           **kw):
        name, bench, m = smoke_bench(tmp_path, traffic, **(mix or {}))
        monkeypatch.setattr(S, "spec", lambda: bench)
        cell = S.ServeCell(name, bench)
        cell.mix = m
        return S.run_cell(cell, seed, 0.1, trace, harness=RUN,
                          need_chips=False, **kw)
    return go


# ---------------------------------------------------------------- traffic
@pytest.mark.parametrize("mix", SERVING)
def test_schedule_holds_its_distributions(mix):
    import serving as T
    m = dict(T.load_mix(mix), requests=20000)
    s = T.schedule(m)
    for key, got in (("prompt", s.user_len), ("output", s.out_len)):
        spec = m[key]
        assert got.min() >= spec["min"] and got.max() <= spec["max"]
        assert abs(np.median(got) - spec["median"]) <= 0.05 * spec["median"]
    share = np.bincount(s.tenant, minlength=m["tenants"]) / len(s)
    assert np.allclose(share, T.tenant_shares(m), atol=0.01)
    gaps = np.diff(s.due)
    rate, k = m["arrivals"]["rate"], m["arrivals"]["shape"]
    assert abs(gaps.mean() * rate - 1) < 0.05
    assert abs(gaps.std() / gaps.mean() - 1 / math.sqrt(k)) < 0.06
    assert s.due[0] == 0 and np.all(gaps >= 0)


@pytest.mark.parametrize("mix", SERVING)
def test_a_seed_renames_tokens_only(mix):
    import serving as T
    m = T.load_mix(mix)
    s = T.schedule(m)
    a = T.prompts(m, s, 1, 151936, 0)
    b = T.prompts(m, s, 2**31 + 9, 151936, 0)
    sys_len = m["system_prompt"]
    assert [len(x) for x in a] == [len(x) for x in b] \
        == (s.user_len + sys_len).tolist()
    assert all(not np.array_equal(x, y) for x, y in zip(a, b))
    # one system prompt per tenant starts every prompt of that tenant
    for t in range(m["tenants"]):
        heads = {x[:sys_len].tobytes() for x, u in zip(a, s.tenant) if u == t}
        assert len(heads) <= 1
    assert T.prompts(m, s, 1, 151936, 0)[0].tolist() == a[0].tolist()


def test_every_serving_cell_mix_covers_its_tenants():
    import serving as T
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfgs = {c["name"]: json.loads((ROOT / c["file"]).read_text())
            for c in spec["configs"]}
    for w in spec["workloads"]:
        if cfgs[w["config"]].get("kind") == "serving":
            assert T.load_mix(w["traffic"])["tenants"] == \
                cfgs[w["config"]]["tenants"]


def test_warmup_covers_every_prompt_length_and_batch():
    import serving as T
    for mix in SERVING:
        m = T.load_mix(mix)
        w = T.warmup(m, 5, 151936, 16)
        pages = {-(-len(p) // 16) for _, p, _ in w}
        assert pages == set(T.page_counts(m, 16))
        assert sorted(n for _, _, n in w) == list(range(2, len(w) + 2))
        assert len(w) >= m["warm_batch"]


# ------------------------------------------------------------ end to end
def test_serving_run_proves_correct(smoke):
    res = smoke()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 16
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s", "itl_p95_ms"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["served_off"]["value"] == 0


def test_traced_serving_run_reports_spans(smoke):
    # warm-up up to the window's largest batch (all 16 requests at once)
    res = smoke(trace=True, mix={"warm_batch": 16})
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for k in ("prefill_ms", "pool_write_ms", "decode_step_ms"):
        assert m[k]["value"] > 0
    assert m["compiles_in_window.serve"]["value"] == 0
    assert "device_idle_pct.serve" in m
    assert m["setup_programs"]["value"] > 0
    # no TPU planes on the CPU: the device readers find nothing to read
    assert "serve_mfu" not in m and "decode_bw_roofline" not in m
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_a_missing_span_target_fails_the_traced_run(smoke, monkeypatch):
    from repro.cache.block_pool import BlockPool
    monkeypatch.delattr(BlockPool, "write_page")
    with pytest.raises(AttributeError):
        smoke(trace=True)


def test_fp8_reference_control_reads_not_correct(smoke):
    res = smoke(control=True)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("which", ["judged", "not_judged"])
def test_a_dropped_request_reads_not_correct(smoke, monkeypatch, which):
    """A window request whose prefill raises ``KV pool exhausted`` is
    dropped by the run; the check counts it, judged or not."""
    import serve_cell as S
    from repro.serve.engine import MultiTenantEngine
    target = []
    judged_rids, prefill = S.Window.judged_rids, MultiTenantEngine._prefill_one

    def pick(self):
        got = judged_rids(self)
        rest = {r.rid for r in self.requests()} - got
        target.append(max(got) if which == "judged" else min(rest))
        return got

    def exhausted(self, req):
        if target and req.rid == target[0]:
            raise RuntimeError("KV pool exhausted")
        return prefill(self, req)
    monkeypatch.setattr(S.Window, "judged_rids", pick)
    monkeypatch.setattr(MultiTenantEngine, "_prefill_one", exhausted)
    res = smoke()
    assert not res["correct"], res["checks"]
    assert res["failed"] == 1
    assert res["checks"]["served_off"]["value"] == 1


def test_eci_warmup_reaches_every_window_shape():
    """Every (padded width, tapes of that width) a window of E page events
    over T tenants can hold has a set-up window of its own."""
    import serve_cell as S
    for events, tenants in ((512, 8), (96, 8), (100, 3)):
        have = {(1 << (lens[0] - 1).bit_length(), lens.count(lens[0]))
                for lens in S.eci_tapes(events, tenants)}
        w = 2                     # (a one-event tape is the companion)
        while w // 2 < events:
            for c in range(1, tenants + 1):
                if c * (w // 2 + 1) < events:
                    assert (w, c) in have, (events, tenants, w, c)
            w *= 2
        assert all(1 < len(lens) <= tenants and sum(lens) <= events
                   for lens in S.eci_tapes(events, tenants))


def test_stale_page_reads_not_correct(smoke):
    import serve_control
    res = smoke(tables=serve_control.stale_page)
    assert not res["correct"], res["checks"]


def test_an_altered_token_reads_not_correct(smoke, monkeypatch):
    """A token altered where the decode step produces it."""
    from repro.serve.engine import MultiTenantEngine
    orig = MultiTenantEngine._decode_batch

    def altered(self):
        orig(self)
        for r in self.active + self.completed[-1:]:
            if len(r.generated) == 3:
                r.generated[-1] = (r.generated[-1] + 1) % 512
    monkeypatch.setattr(MultiTenantEngine, "_decode_batch", altered)
    res = smoke()
    assert not res["correct"]
    assert res["checks"]["token_gap"]["value"] > \
        res["checks"]["token_gap"]["limit"]


def test_a_pool_left_unchanged_reads_not_correct(smoke, monkeypatch):
    """The prefill's page writes skipped: the pool keeps its old state."""
    from repro.cache.block_pool import BlockPool
    monkeypatch.setattr(BlockPool, "write_page", lambda self, k, v, pid: None)
    res = smoke()
    assert not res["correct"]
    assert res["checks"]["logit_rel_gap"]["value"] > \
        res["checks"]["logit_rel_gap"]["limit"]


def test_shared_prefix_pages_are_freed_under_requests_in_flight(
        smoke, monkeypatch):
    """The program fault that keeps ``kv-qwen3-shared`` out of the
    benchmark: a request that completes unpins the tenant's shared system
    prompt pages while other requests still read them; ECI's quotas then
    evict them and the pool hands them to other keys.  The audit sees a
    page change hands under a request in flight."""
    import serve_control
    import serve_cell as S

    class StepClock:
        """Each reading of the clock advances it 3 ms: arrivals fall
        between the same steps however fast the host runs them (the
        fault shows from 2 to 4 ms a reading)."""
        t = 0.0

        def perf_counter(self):
            self.t += 0.003
            return self.t

        def sleep(self, s):
            self.t += s
    monkeypatch.setattr(S, "time", StepClock())
    audit = serve_control.Audit()
    smoke("shared-sys512", tables=audit,
          mix={"system_prompt": 64, "requests": 24})
    assert audit.first_bad


# ------------------------------------------------- the program's system
def smoke_cell(tmp_path, monkeypatch, model=None, widths=None):
    """The throwaway cell, with ``model`` keys and a ``widths`` map added
    to its configuration file."""
    import serve_cell as S
    name, bench, mix = smoke_bench(tmp_path)
    path = tmp_path / "smoke.json"
    cfg = json.loads(path.read_text())
    cfg["model"].update(model or {})
    if widths is not None:
        cfg["widths"] = widths
    path.write_text(json.dumps(cfg))
    monkeypatch.setattr(S, "spec", lambda: bench)
    cell = S.ServeCell(name, bench)
    cell.mix = mix
    return cell


@pytest.mark.parametrize("where", ["program", "stand_in"])
def test_the_server_is_what_the_builder_returned(tmp_path, monkeypatch,
                                                 where):
    """``Server`` takes its pool, manager, tiered cache and engine from
    the program's ``build_server`` where the program has one, else from
    the stand-in, and builds none of them itself."""
    import repro.launch.serve as L
    import serve_build
    import serve_cell as S
    built = []

    def spy(*args, **kwargs):
        built.append(real(*args, **kwargs))
        return built[-1]
    real = serve_build.stand_in
    if where == "program":
        monkeypatch.setattr(L, "build_server", spy, raising=False)
    else:
        monkeypatch.delattr(L, "build_server", raising=False)
        monkeypatch.setattr(serve_build, "stand_in", spy)
    server = S.Server(smoke_cell(tmp_path, monkeypatch), SEED)
    assert len(built) == 1
    b = built[0]
    assert (server.pool, server.manager, server.tiered, server.engine) \
        == (b.pool, b.manager, b.tiered, b.engine)
    assert server.engine.tiered is b.tiered and b.tiered.pool is b.pool


def test_stand_in_builds_as_the_launcher_does():
    """The stand-in at ``launch/serve.py``'s numbers: the K/V pool of
    shape (layers, pages, page, kv heads, head_dim), and the same tokens
    as the system that launcher builds by hand."""
    import jax
    import jax.numpy as jnp
    import serve_build
    from repro.cache import BlockPool, TieredKVCache
    from repro.configs import get_smoke_config
    from repro.core import ECICacheManager
    from repro.models import model as M
    from repro.serve.engine import MultiTenantEngine, Request
    cfg = get_smoke_config("qwen3_0_6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tenants, pages, page, capacity = 2, 64, 8, 24
    mgr = {"capacity": capacity, "c_min": 8,
           "initial_blocks": capacity // tenants}
    built = serve_build.stand_in(
        cfg, params, tenants=tenants, pool_pages=pages, page_size=page,
        max_pages_per_seq=32, pool_dtype=cfg.dtype, window_events=128,
        manager=mgr)
    assert built.pool.k_pages.shape == built.pool.v_pages.shape \
        == (cfg.n_layers, pages, page, cfg.n_kv_heads, cfg.head_dim)
    assert built.pool.k_pages.dtype == jnp.dtype(cfg.dtype)
    pool = BlockPool(pages, page, cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                     dtype=jnp.dtype(cfg.dtype))
    tiered = TieredKVCache(pool, ECICacheManager(
        capacity, [f"tenant{i}" for i in range(tenants)], c_min=8,
        initial_blocks=capacity // tenants), window_events=128)
    by_hand = MultiTenantEngine(cfg, params, tiered, page_size=page,
                                max_pages_per_seq=32)

    def served(engine):
        rng = np.random.default_rng(0)
        for i in range(4):
            engine.submit(Request(tenant=i % tenants, prompt=rng.integers(
                0, cfg.vocab_size, 12 + 5 * i).astype(np.int32),
                max_new_tokens=4))
        engine.run(max_steps=40)
        return sorted((r.rid, r.generated) for r in engine.completed)
    got = served(built.engine)
    assert len(got) == 4 and got == served(by_hand)


def test_stand_in_refuses_latent_attention():
    import jax
    import serve_build
    from repro.configs import get_smoke_config
    from repro.models import model as M
    cfg = get_smoke_config("minicpm3_4b")
    shapes = jax.eval_shape(lambda: M.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="latent attention"):
        serve_build.stand_in(cfg, shapes, tenants=2, pool_pages=8,
                             page_size=8, max_pages_per_seq=4,
                             pool_dtype="bfloat16", window_events=16,
                             manager={"capacity": 8})


def hooks_only():
    """A server of stand-ins that holds just what ``hook`` wraps."""
    from types import SimpleNamespace as NS
    return NS(engine=NS(_prefill_one=None, _decode_batch=None, active=[]),
              manager=NS(record=None, analyze=None, tenants=[]),
              tiered=NS(rebalance=None, quotas={}), pool=NS())


@pytest.mark.parametrize("call", ["positional", "keywords"])
def test_hook_swaps_tables_by_name(monkeypatch, call):
    """A decode step of another cache layout (one latent pool, tables in
    fourth place): the block-table hook, which ``stale_page`` uses,
    replaces ``tables`` by its name, however the step is called."""
    import repro.serve.engine as E
    import serve_cell as S
    seen = {}

    def latent_step(params, kv_pool, toks, tables, lens, cfg, tp):
        seen.update(pool=kv_pool, tables=tables, lens=lens)
        return ("logits",)
    monkeypatch.setattr(E, "_decode_step_jit", latent_step)
    log = S.Log()
    log.tables = lambda server, log, t: t + 100
    unhook = S.hook(hooks_only(), log)
    try:
        if call == "positional":
            out = E._decode_step_jit("p", "pool", "toks", np.arange(2), 3,
                                     None, 1)
        else:
            out = E._decode_step_jit("p", "pool", "toks", lens=3, cfg=None,
                                     tp=1, tables=np.arange(2))
    finally:
        unhook()
    assert out == ("logits",) and E._decode_step_jit is latent_step
    assert seen["tables"].tolist() == [100, 101]
    assert seen["lens"] == 3 and seen["pool"] == "pool"


def step_without_tables(params, pool, toks, block_tables, lens, cfg, tp):
    return None


def step_without_lens(params, pool, toks, tables, lengths, cfg, tp):
    return None


@pytest.mark.parametrize("broken", ["no_tables", "no_lens", "no_prefill"])
def test_a_broken_hook_contract_fails_at_hook_time(monkeypatch, broken):
    import repro.serve.engine as E
    import serve_cell as S
    if broken == "no_prefill":
        monkeypatch.delattr(E, "prefill_with_kv")
        err = AttributeError
    else:
        monkeypatch.setattr(E, "_decode_step_jit", {
            "no_tables": step_without_tables,
            "no_lens": step_without_lens}[broken])
        err = TypeError
    with pytest.raises(err):
        S.hook(hooks_only(), S.Log())


def test_write_page_span_waits_on_every_pool_array(monkeypatch):
    """A pool of another layout: the ``write_page`` span waits on each of
    its device arrays, before and after the write, whatever their names."""
    import jax
    import jax.numpy as jnp
    import serve_tracing as ST
    from types import SimpleNamespace as NS

    class Pool:
        def __init__(self):
            self.latent = jnp.zeros(4)
            self.rope = jnp.zeros(2)
            self.scales = jnp.ones(3)
            self.meta = {}

        def write_page(self, *args):
            return "written"
    pool = Pool()
    waited = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or x)
    ST.Spans().install({"engine": NS(_prefill_one=None, _decode_batch=None),
                        "tiered": NS(rebalance=None), "pool": pool})
    assert pool.write_page() == "written"
    assert len(waited) == 2
    for w in waited:
        assert sorted(map(id, w)) == sorted(
            map(id, (pool.latent, pool.rope, pool.scales)))


@pytest.mark.parametrize("model,widths,err", [
    ({"moe_intermediate_size": 1408}, None, "differs"),
    ({"kv_lora_rank": 512, "qk_rope_head_dim": 64}, None, "differs"),
    ({"hidden_width": 96}, {"hidden_width": "d_model"}, "differs"),
    ({"hidden_width": 64}, {"hidden_width": "no_such_field"}, "no field"),
    ({"q_lora_rank": None, "kv_lora_rank": 0, "hidden_width": 64},
     {"hidden_width": "d_model"}, None),
])
def test_program_widths_are_checked(tmp_path, monkeypatch, model, widths,
                                    err):
    """Expert and latent widths, and a configuration's own ``widths``,
    have to equal the program's fields (a null reads as 0)."""
    import serve_cell as S
    cell = smoke_cell(tmp_path, monkeypatch, model, widths)
    if err is None:
        assert S.program_config(cell).d_model == 64
    else:
        with pytest.raises(ValueError, match=err):
            S.program_config(cell)


# --------------------------------------------------------------- dispatch
def test_a_new_serving_configuration_needs_only_files(smoke, tmp_path,
                                                      monkeypatch):
    """``run.cell_runner`` picks the serving runner by the configuration's
    ``kind``: the throwaway configuration runs with no code change."""
    import functools
    import run as RUN
    import serve_cell as S
    name, bench, _ = smoke_bench(tmp_path)
    monkeypatch.setattr(S, "spec", lambda: bench)
    d = RUN.cell_runner(name)
    assert isinstance(d, functools.partial) and d.func is S.run_cell
    res = d(name, SEED, 0.1, False, need_chips=False)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name", ["msr16-bind", "msr16-fit"])
def test_control_plane_cells_run_as_before(name, cpu_run, monkeypatch,
                                           capsys):
    """The control plane's cells take ``run_cell`` through the dispatch:
    ``main`` prints what ``run_cell`` returns, timings aside."""
    import run as RUN
    from conftest import tiny
    assert RUN.cell_runner(name) is RUN.run_cell
    direct = cpu_run(tiny(name), seed=SEED, seconds=0)   # one pool cycle
    cells = {name: tiny(name)}

    class Tiny(RUN.C.Cell):
        def __new__(cls, n):
            return cells[n]
    monkeypatch.setattr(RUN, "require_chips", lambda chips: {})
    monkeypatch.setattr(RUN.C, "Cell", Tiny)
    assert RUN.main(["--workload", name, "--seed", str(SEED),
                     "--seconds", "0", "--trace", "0"]) == 0
    via = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    def timeless(r):
        return {**r, "metrics": {k: v["unit"]
                                 for k, v in r["metrics"].items()}}
    assert timeless(via) == timeless(json.loads(json.dumps(direct)))


def test_no_chip_no_serving_result(monkeypatch, capsys):
    import jax
    import run as RUN
    monkeypatch.setattr(RUN, "setup_jax", lambda: jax)
    rc = RUN.main(["--workload", "kv-qwen3-noshare", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_busy_within_intervals():
    import serve_tracing as ST
    ev = [("/device:TPU:0", "XLA Ops", "a", 0, 10),
          ("/device:TPU:0", "XLA Ops", "b", 5, 10),
          ("/device:TPU:0", "XLA Ops", "c", 30, 10)]
    bz = ST.Busy(ev)
    assert bz.within(0, 100) == 25
    assert bz.within(3, 8) == 5
    assert bz.within(12, 35) == 8
    assert bz.within(15, 30) == 0
