"""The runner of a serving cell: a configuration of ``"kind": "serving"``.

The system under test is the program's serving tier:
``MultiTenantEngine`` over a ``TieredKVCache`` whose HBM page pool is
partitioned across tenants by ``ECICacheManager``.  The program builds
it, and decides the pool's layout, through ``build_server``
(``serve_build``); the harness only drives and judges it.  Everything
particular to a cell is read from its files:

  * the configuration (``configs/<name>.json``): ``arch``, resolved with
    ``repro.configs.get_config`` (its widths must equal the file's
    ``model`` keys, the published ``config.json``'s names: ``WIDTHS``,
    and the file's own ``widths`` for fields ``WIDTHS`` lacks), ``tenants``,
    ``pool`` (pages, page size, pages a sequence may hold, dtype),
    ``tiered`` (events per ECI window), ``manager`` (the manager's
    keywords) and ``reference``, the plain reference under ``serve_ref/``,
    loaded by its path; it also makes the weights from the seed
    (``weights``) and holds the model arithmetic (``flops_*``,
    ``*_bytes``);
  * the traffic (``traffic/<mix>.json``, read by ``traffic/serving.py``).

One run: weights from ``--seed`` in one jitted call, the server, then
set-up's warm-up (``serving.warmup``: every prompt length in pages the
mix can draw, every decode batch size up to ``warm_batch``, and the
tenants' system prompts into the prefix cache; then ``warm_eci``: every
shape of tenant tapes an ECI window can hand the monitor); then the
measured window:
whole schedules, open loop on the wall clock, at least ``--seconds``.
A request is submitted once it is due and the serving loop is free; its
time to first token runs from its due time, so a late generator counts
against the server.  Pool and ECI state carry over from set-up.
Afterwards the reference judges what the timed path produced
(``serve_check``).

The hooks (``hook``) hold the program to this contract, and raise when
it is set up if it is broken: ``repro.serve.engine`` calls its prefill
as ``prefill_with_kv`` and its decode step as ``_decode_step_jit``
(``serve_tracing.DECODE_PROGRAM``, the program a traced run counts once
a step), through those module names; the decode step has the parameters
``tables`` (the batch's block tables) and ``lens``; both return the
logits first.  Everything else they take, the cache among it, is passed
through as it comes.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from collections import deque
from functools import partial
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH / "traffic"))

import serving as traffic  # noqa: E402  (the serving traffic generator)

JUDGED = 4                # requests the reference judges per run
MAX_WINDOW_S = 240.0      # a window that has not drained by then fails
PREFILL_PROGRAM = "prefill_with_kv"   # the engine's prefill, hooked by name


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def config_of(workload: str, bench: dict | None = None) -> dict:
    bench = bench or spec()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in bench["configs"]}[cells[workload]["config"]]
    return json.loads((ROOT / cfg["file"]).read_text())


def is_serving(workload: str) -> bool:
    return config_of(workload).get("kind") == "serving"


def load_reference(rel: str):
    path = BENCH / rel
    spec_ = importlib.util.spec_from_file_location(
        f"chipbench_ref_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


class ServeCell:
    """A serving workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or spec()
        self.entry = {w["name"]: w for w in bench["workloads"]}[name]
        self.name = name
        self.config = config_of(name, bench)
        self.mix = traffic.load_mix(self.entry["traffic"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.ref = load_reference(self.config["reference"])

    @property
    def model(self) -> dict:
        return self.config["model"]


# published config.json key -> the program's ModelConfig field
WIDTHS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
          "tie_word_embeddings": "tie_embeddings",
          "n_routed_experts": "n_experts", "num_experts": "n_experts",
          "num_experts_per_tok": "top_k",
          "moe_intermediate_size": "expert_d_ff",
          "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
          "qk_nope_head_dim": "qk_nope_dim",
          "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim"}


def program_config(cell: ServeCell):
    """The program's ``ModelConfig`` for the cell's ``arch`` (a
    configuration may name ``smoke_arch`` instead: the architecture's
    reduced preset, for tests), checked against the file's widths: each
    ``model`` key of ``WIDTHS`` or of the file's ``widths`` (published
    key -> field, for fields ``WIDTHS`` lacks) has to equal the field, a
    null read as 0 (``q_lora_rank`` null: no query compression).  A
    mapped field the program lacks raises."""
    from repro.configs import get_config, get_smoke_config
    c = cell.config
    cfg = (get_smoke_config(c["smoke_arch"]) if "smoke_arch" in c
           else get_config(c["arch"]))
    widths = {**WIDTHS, **c.get("widths", {})}
    off = {}
    for k, v in cell.model.items():
        if k not in widths:
            continue
        if not hasattr(cfg, widths[k]):
            raise ValueError(f"{cell.name}: the program's ModelConfig has "
                             f"no field {widths[k]!r} for {k!r}")
        have = getattr(cfg, widths[k])
        if have != (0 if v is None else v):
            off[k] = (v, have)
    if off:
        raise ValueError(f"{cell.name}: the program's {cfg.name} differs "
                         f"from the configuration: {off}")
    if str(cfg.dtype) != c["dtype"]:
        raise ValueError(f"{cell.name}: the program serves {cfg.dtype}, "
                         f"the configuration states {c['dtype']}")
    return cfg


class Server:
    """The system under test, built by the program's builder
    (``serve_build.builder``) from the configuration's sizes, with the
    weights made from ``seed`` by the cell's reference module."""

    def __init__(self, cell: ServeCell, seed: int):
        import jax
        import serve_build
        from repro.models import model as M
        c = cell.config
        self.cfg = cfg = program_config(cell)
        self.shapes = jax.eval_shape(partial(M.init_params, cfg),
                                     jax.random.PRNGKey(0))
        params = cell.ref.weights(seed, cell.model, self.shapes)
        jax.block_until_ready(params)
        p = c["pool"]
        built = serve_build.builder()(
            cfg, params, tenants=c["tenants"], pool_pages=p["pages"],
            page_size=p["page_size"],
            max_pages_per_seq=p["max_pages_per_seq"], pool_dtype=p["dtype"],
            window_events=c["tiered"]["window_events"], manager=c["manager"])
        self.pool, self.manager = built.pool, built.manager
        self.tiered, self.engine = built.tiered, built.engine
        self.page = p["page_size"]


class Log:
    """What the run saw, request by request and window by window; the
    hooks into the program (``hook``) fill it."""

    def __init__(self):
        self.judged_rids: set[int] = set()
        self.first: dict[int, float] = {}          # rid -> first token
        self.tokens: dict[int, list[float]] = {}   # rid -> later tokens
        self.failed: set[int] = set()
        self.steps: list[tuple[float, int, int]] = []   # (end, B, context)
        self.prefills: list[tuple[float, int]] = []     # (end, prompt len)
        self.kept: dict[int, list] = {}            # rid -> logit rows
        self.eci: list[dict] = []                  # per ECI window
        self.batch: list = []
        self.current = None
        self.warming = False
        self.tables = None                         # block-table hook


def _pick():
    import jax
    return jax.jit(lambda logits, i: logits[i])


def hook(server: Server, log: Log):
    """Instrument the run: the first-token and token clocks, failures,
    the logit rows of judged requests, and every ECI window's page events
    and decision.  Returns the function that undoes the module-level
    hooks (the rest live on the run's own objects)."""
    import inspect

    import jax.numpy as jnp
    import repro.serve.engine as E
    import serve_tracing as ST
    for name in (PREFILL_PROGRAM, ST.DECODE_PROGRAM):
        if not callable(getattr(E, name, None)):
            raise AttributeError(f"repro.serve.engine has no {name}: the "
                                 "serving hooks cannot reach the program")
    orig_prefill_fn = getattr(E, PREFILL_PROGRAM)
    orig_decode_fn = getattr(E, ST.DECODE_PROGRAM)
    decode_sig = inspect.signature(orig_decode_fn)
    lacks = {"tables", "lens"} - set(decode_sig.parameters)
    if lacks:
        raise TypeError(f"repro.serve.engine.{ST.DECODE_PROGRAM} has no "
                        f"parameter {', '.join(sorted(lacks))}: "
                        f"{decode_sig}")
    eng, mgr, tiered = server.engine, server.manager, server.tiered
    pick = _pick()

    def keep(rid, logits, i):
        """A judged request's logit row, kept on the device; set-up
        builds the program for each shape."""
        if log.warming or rid in log.judged_rids:
            row = pick(logits, jnp.int32(i))
            if not log.warming:
                log.kept.setdefault(rid, []).append(row)

    def prefill_with_kv(*args, **kwargs):
        out = orig_prefill_fn(*args, **kwargs)
        if log.current is not None:
            keep(log.current.rid, out[0], 0)
        return out

    def decode_step(*args, **kwargs):
        if log.tables is not None:
            call = decode_sig.bind(*args, **kwargs)
            call.arguments["tables"] = log.tables(
                server, log, call.arguments["tables"])
            args, kwargs = call.args, call.kwargs
        out = orig_decode_fn(*args, **kwargs)
        for i, r in enumerate(log.batch):
            keep(r.rid, out[0], i)
        return out

    def fail(reqs, err):
        for r in reqs:
            for pid in r.pages:
                server.pool.unpin(pid)
            log.failed.add(r.rid)
        eng.active = [r for r in eng.active if r.rid not in log.failed]
        print(f"chipbench: {len(reqs)} requests failed: {err}",
              file=sys.stderr, flush=True)

    prefill_one, decode_batch = eng._prefill_one, eng._decode_batch

    def timed_prefill(req):
        log.current = req
        try:
            prefill_one(req)
        except RuntimeError as e:
            if "KV pool exhausted" not in str(e):
                raise
            fail([req], e)
            return
        finally:
            log.current = None
        t = time.perf_counter()
        log.first[req.rid] = t
        log.tokens[req.rid] = []
        log.prefills.append((t, len(req.prompt)))

    def timed_decode():
        log.batch = [r for r in eng.active if not r.done]
        ctx = sum(r.length for r in log.batch)
        try:
            decode_batch()
        except RuntimeError as e:
            if "KV pool exhausted" not in str(e):
                raise
            fail(log.batch, e)
            return
        finally:
            batch, log.batch = log.batch, []
        t = time.perf_counter()
        for r in batch:
            log.tokens[r.rid].append(t)
        if batch:
            log.steps.append((t, len(batch), ctx))

    eng._prefill_one, eng._decode_batch = timed_prefill, timed_decode

    record, analyze, rebalance = mgr.record, mgr.analyze, tiered.rebalance
    pending: dict[int, tuple] = {}

    def recorded(tenant, addrs, is_read):
        pending[tenant] = (np.array(addrs, np.int64), np.array(is_read, bool))
        return record(tenant, addrs, is_read)

    def analyzed(*a, **k):
        dec = analyze(*a, **k)
        log.eci.append({"tapes": dict(pending),
                        "curves": [t.h_fn for t in mgr.tenants],
                        "urd": [int(t.urd_size) for t in mgr.tenants],
                        "decision": dec})
        pending.clear()
        return dec

    def rebalanced():
        n = len(log.eci)
        rebalance()
        if len(log.eci) > n:
            log.eci[-1]["quotas"] = [tiered.quotas[i]
                                     for i in range(len(mgr.tenants))]

    mgr.record, mgr.analyze, tiered.rebalance = recorded, analyzed, rebalanced
    setattr(E, PREFILL_PROGRAM, prefill_with_kv)
    setattr(E, ST.DECODE_PROGRAM, decode_step)

    def unhook():
        setattr(E, PREFILL_PROGRAM, orig_prefill_fn)
        setattr(E, ST.DECODE_PROGRAM, orig_decode_fn)
    return unhook


def warm_up(server: Server, cell: ServeCell, seed: int, log: Log) -> int:
    """Set-up's requests, all at once, served to the end (see
    ``serving.warmup``).  Returns the largest decode batch reached."""
    from repro.serve.engine import Request
    reqs = traffic.warmup(cell.mix, seed, server.cfg.vocab_size, server.page)
    for t, prompt, n in reqs:
        server.engine.submit(Request(tenant=t, prompt=prompt,
                                     max_new_tokens=n))
    log.warming = True
    server.engine.run(max_steps=len(reqs) + 8)
    log.warming = False
    if server.engine.waiting or server.engine.active or log.failed:
        raise RuntimeError(f"{cell.name}: warm-up did not serve its "
                           f"{len(reqs)} requests ({len(log.failed)} failed)")
    warm_eci(cell)
    return max(b for _, b, _ in log.steps)


def eci_tapes(events: int, tenants: int) -> list[list[int]]:
    """Per-tenant tape lengths of set-up's ECI windows: for every power
    of two ``w`` up to the one that holds ``events``, and every count
    ``c`` of tenants whose tapes can pad to ``w`` in a window of
    ``events`` page events (each longer than ``w / 2``), a window with
    ``c`` tapes of ``w // 2 + 1`` events, and a tape of one event beside
    a lone one (a window of the cell's traffic holds several tenants).
    The monitor builds one program per (padded width, tapes of that
    width), so these windows reach every program a measured window can
    ask for."""
    out = []
    w = 1
    while w // 2 < events:
        n = w // 2 + 1
        out += [[n] * c + [1] * (c == 1)
                for c in range(1, min(tenants, (events - 1) // n) + 1)]
        w *= 2
    return out


def warm_eci(cell: ServeCell) -> None:
    """Run ``eci_tapes``' windows through a manager built as the
    server's (its own, so the server's state and the check see none of
    them): page events of a window carry reads and writes, with reuse."""
    from repro.core import ECICacheManager
    c = cell.config
    mgr = ECICacheManager(
        tenant_names=[f"tenant{i}" for i in range(c["tenants"])],
        **c["manager"])
    rng = np.random.default_rng(0)
    for lens in eci_tapes(int(c["tiered"]["window_events"]), c["tenants"]):
        for t, n in enumerate(lens):
            mgr.record(t, rng.integers(0, n // 2 + 1, n),
                       rng.random(n) < 0.5)
        mgr.analyze()
        for t in mgr.tenants:
            t.clear_window()


class Window:
    """The measured window's requests: per request its pass, index in the
    schedule, due time (s from the window's start) and ``Request``."""

    def __init__(self, cell: ServeCell, seed: int, seconds: float, vocab):
        from repro.serve.engine import Request
        self.sched = traffic.schedule(cell.mix)
        self.n_pass = traffic.passes(cell.mix, seconds)
        span = float(self.sched.due[-1]) + 1.0 / cell.mix["arrivals"]["rate"]
        self.items = []
        for p in range(self.n_pass):
            prompts = traffic.prompts(cell.mix, self.sched, seed, vocab, p)
            for i in range(len(self.sched)):
                self.items.append((p, i, p * span + float(self.sched.due[i]),
                                   Request(tenant=int(self.sched.tenant[i]),
                                           prompt=prompts[i],
                                           max_new_tokens=int(
                                               self.sched.out_len[i]))))
        self.judged_idx = judged(self.sched, seed)
        self.submitted: dict[int, float] = {}      # rid -> submit time
        self.due: dict[int, float] = {}            # rid -> due (abs clock)

    def judged_rids(self) -> set[int]:
        last = self.n_pass - 1
        return {r.rid for p, i, _, r in self.items
                if p == last and i in self.judged_idx}

    def requests(self) -> list:
        return [r for _, _, _, r in self.items]


def judged(sched: traffic.Schedule, seed: int, k: int = JUDGED) -> set[int]:
    """Indices of the judged requests: the last and the longest, and
    others drawn from ``seed``."""
    n = len(sched)
    out = {n - 1, int(np.argmax(sched.user_len + sched.out_len))}
    rng = np.random.default_rng([seed, 3])
    for i in rng.permutation(n).tolist():
        if len(out) >= min(k, n):
            break
        out.add(int(i))
    return out


def serve(server: Server, win: Window, log: Log, on_step=None) -> float:
    """The open loop over the window's schedules; returns the seconds from
    the first due time to the last request's end."""
    eng = server.engine
    for r in win.requests():
        eng.submit(r)                       # assigns the request ids
    eng.waiting.clear()
    log.judged_rids = win.judged_rids()
    pending = deque(sorted(((d, r) for _, _, d, r in win.items),
                           key=lambda x: x[0]))
    t0 = time.perf_counter()
    while pending or eng.waiting or eng.active:
        now = time.perf_counter() - t0
        if now > MAX_WINDOW_S:
            raise RuntimeError(f"the window has not drained after "
                               f"{MAX_WINDOW_S:.0f} s")
        while pending and pending[0][0] <= now:
            d, r = pending.popleft()
            win.due[r.rid] = t0 + d
            win.submitted[r.rid] = time.perf_counter()
            eng.waiting.append(r)
        if eng.waiting or eng.active:
            eng.step()
            if on_step is not None:
                on_step()
        elif pending:
            time.sleep(max(0.0, pending[0][0] - now))
    return time.perf_counter() - t0


def end_to_end(win: Window, log: Log, elapsed: float) -> dict:
    """tokens_per_s, ttft_p90_ms and itl_p95_ms of the window (a run
    reports those of the cell's end-to-end metrics; the sweep all
    three).  Requests that failed are left out of the tails; the check
    counts them in ``served_off``, so such a run is not correct."""
    ok = [r for r in win.requests() if r.rid not in log.failed and r.done]
    ttft = [1000.0 * (log.first[r.rid] - win.due[r.rid]) for r in ok]
    itl = []
    for r in ok:
        t = [log.first[r.rid], *log.tokens[r.rid]]
        itl.extend(1000.0 * (b - a) for a, b in zip(t, t[1:]))
    return {"tokens_per_s": sum(len(r.generated) for r in ok) / elapsed,
            "ttft_p90_ms": float(np.percentile(ttft, 90)),
            "itl_p95_ms": float(np.percentile(itl, 95))}


def lateness(win: Window) -> list[float]:
    return [1000.0 * (win.submitted[k] - win.due[k]) for k in win.due]


def run_cell(workload, seed: int, seconds: float, trace: bool,
             harness, need_chips: bool = True, tables=None, detail: dict | None = None,
             control: bool = False, also_judge=None) -> dict:
    """One run of a serving cell; returns the result object (``checks``
    last).  ``harness`` is the ``run`` module (its set-up, device look and
    clock); ``need_chips=False`` skips the look for a chip (tests on the
    CPU).  The control runs use the rest: ``tables(server, log, tables)
    -> tables`` (a hook on
    each decode step's block tables), ``detail`` (filled with each
    judged request's numbers), ``control`` (the check compares the
    reference in float8 in the program's place, ``serve_check.judge``)
    and ``also_judge() -> rids`` (more requests whose served tokens the
    reference reads, into ``detail`` only)."""
    import serve_check
    import serve_tracing as ST
    import tracing
    cell = (workload if isinstance(workload, ServeCell)
            else ServeCell(workload))
    jax = harness.setup_jax()
    peaks = (harness.require_chips(int(cell.entry["chips"])) if need_chips
             else {})
    compiles = tracing.CompileCounter()
    t_build = time.perf_counter()
    server = Server(cell, seed)
    log = Log()
    log.tables = tables
    unhook = hook(server, log)
    try:
        t_warm = time.perf_counter()
        warm_b = warm_up(server, cell, seed, log)
        win = Window(cell, seed, seconds, server.cfg.vocab_size)
        setup_s = time.perf_counter() - harness.T_START
        built0 = compiles.built
        print(f"chipbench: {cell.name} seed {seed}: set-up {setup_s:.3f} s "
              f"(server {t_warm - t_build:.3f} s, warm-up "
              f"{time.perf_counter() - t_warm:.3f} s to decode batch "
              f"{warm_b}), {built0} programs built, {compiles.seconds:.3f} "
              "s compiling or loading", file=sys.stderr, flush=True)
        log.steps.clear()
        log.prefills.clear()
        spans = ST.Spans() if trace else None
        tr = {}
        if trace:
            spans.install({"engine": server.engine, "tiered": server.tiered,
                           "pool": server.pool})
            shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(harness.TRACE_DIR),
                                     profiler_options=tracing
                                     .profiler_options())
            tr["ann"] = jax.profiler.TraceAnnotation(ST.WINDOW_SPAN)
            tr["ann"].__enter__()

        def stop_trace():
            tr["until"] = time.perf_counter()
            tr["steps"] = len(log.steps)
            tr.pop("ann").__exit__(None, None, None)
            jax.profiler.stop_trace()

        def on_step():
            if "ann" in tr and len(log.steps) >= ST.TRACE_STEPS:
                stop_trace()

        elapsed = serve(server, win, log, on_step if trace else None)
        if "ann" in tr:
            stop_trace()
        in_window = compiles.built - built0
        late = lateness(win)
        print(f"chipbench: {len(win.items)} requests in {elapsed:.3f} s "
              f"({win.n_pass} schedules), {len(log.steps)} decode steps, "
              f"largest batch {max(b for _, b, _ in log.steps)}, "
              f"{in_window} programs built in the window; generator late "
              f"p50 {np.percentile(late, 50):.3f} ms, p95 "
              f"{np.percentile(late, 95):.3f} ms, max {max(late):.3f} ms",
              file=sys.stderr, flush=True)
        device = harness.device_info()
        extra = {}
        if trace:
            metrics, dev_trace, extra["breakdown"] = traced_metrics(
                cell, harness, spans, log, tr, peaks, need_chips,
                {"compiles_in_window": in_window, "setup_programs": built0})
            device.update(dev_trace)
        else:
            values = {"setup_s": setup_s, **end_to_end(win, log, elapsed)}
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
        served = serve_check.Served.collect(win, log, server)
        if detail is not None:
            detail["urd_demand"] = [int(sum(w["urd"])) for w in log.eci]
        if also_judge is not None:
            served.also = set(also_judge()) - set(served.judged)
    finally:
        unhook()
    del server, log
    t_check = time.perf_counter()
    lim = serve_check.limits()
    numbers, bad = serve_check.judge(cell, seed, served, lim, detail,
                                     control)
    print(f"chipbench: the reference judged {len(served.judged)} requests "
          f"and {len(served.eci)} ECI windows in "
          f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr,
          flush=True)
    ok, shown = serve_check.verdict(numbers, lim)
    return {"correct": ok, "attempted": len(served.window),
            "failed": len(served.failed | bad), "metrics": metrics,
            "device": device, **extra, "checks": shown}


def traced_metrics(cell: ServeCell, harness, spans, log: Log, tr: dict,
                   peaks: dict, on_chip: bool, counts: dict):
    """The cell's per-layer metrics from the profiler trace and the
    spans, the device's busy and window seconds, and the breakdown.  On
    the chip a trace that lost events fails the run."""
    import reduce
    import serve_tracing as ST
    names = [ST.WINDOW_SPAN, *(n for n, _ in ST.SPANNED)]
    events = ST.read_events(harness.TRACE_DIR, names)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    ops = ST.ops_only(events)
    lines = {}
    for e in events:
        lines[e[:2]] = lines.get(e[:2], 0) + 1
    print("chipbench: trace events by line: " + ", ".join(
        f"{p} {ln} {n}" for (p, ln), n in sorted(lines.items())),
        file=sys.stderr, flush=True)
    host = reduce.host_spans(ops, names)
    wins = [s for s in host if s[0] == ST.WINDOW_SPAN]
    if len(wins) != 1:
        raise RuntimeError(f"the trace holds {len(wins)} window spans")
    _, lo, hi = wins[0]
    decode_spans = sorted(s for s in host if s[0] == ST.DECODE)
    if on_chip:
        if not reduce.device_planes(ops):
            raise RuntimeError("the trace holds no TPU operation")
        n_dec = sum(1 for p in ST.programs(events, lo, hi)
                    if ST.DECODE_PROGRAM in p[0])
        if n_dec < tr["steps"] or len(decode_spans) < tr["steps"]:
            raise RuntimeError(
                f"the trace lost events: {n_dec} decode programs and "
                f"{len(decode_spans)} decode spans for {tr['steps']} "
                "decode steps")
    busy_s, window_s = reduce.busy(ops, lo, hi), (hi - lo) / 1e9
    bz = ST.Busy(ops)
    reb = [s for s in host if s[0] == ST.REBALANCE]
    decode_busy = sum(bz.within(a, b) for _, a, b in decode_spans) \
        - sum(bz.within(a, b) for _, a, b in reb
              if any(d[1] <= a and b <= d[2] for d in decode_spans))
    until = tr["until"]
    ctx = {"spans": spans, "events": ops,
           "bounds": (lo, hi), "busy_s": busy_s, "window_s": window_s,
           "peaks": peaks, "model": cell.ref, "spec": cell.model,
           "prefills": [n for t, n in log.prefills if t <= until],
           "steps": [(b, c) for t, b, c in log.steps if t <= until],
           "decode_busy_s": decode_busy / 1e9, **counts}
    metrics = {}
    for m in cell.per_layer:
        v = harness.read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    layers = [s for s in host if s[0] != ST.WINDOW_SPAN]
    top = ST.top_programs(events, lo, hi) or reduce.top_ops(ops, lo, hi)
    breakdown = {"device_ops": top,
                 "idle_gaps": reduce.idle_gaps(ops, lo, hi, layers)}
    return metrics, {"busy_s": busy_s, "window_s": window_s}, breakdown
