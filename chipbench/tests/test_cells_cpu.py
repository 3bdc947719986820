"""Every control-plane cell's loop end to end at a tiny size on the CPU:
set-up, warm-up, measured window, the reference check, and the result
line (the serving cells: ``test_serving_cells.py``)."""
import json

import pytest

from conftest import BENCH, tiny

_SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
_KIND = {c["name"]: json.loads((BENCH.parent / c["file"]).read_text())
         .get("kind") for c in _SPEC["configs"]}
CELLS = [w["name"] for w in _SPEC["workloads"]
         if _KIND[w["config"]] != "serving"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_proves_correct(name, cpu_run):
    res = cpu_run(tiny(name))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"window_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(set(v) == {"value", "limit"} for v in res["checks"].values())


def test_traced_run_reports_host_spans(cpu_run):
    res = cpu_run(tiny("msr16-bind"), trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for k in ("replay_ms", "monitor_ms", "partition_ms"):
        assert m[k]["value"] > 0
    assert m["compiles_in_window"]["value"] == 0
    assert m["setup_programs"]["value"] >= 0      # 0 once all are built
    # no TPU planes on the CPU: the device readers find nothing to read
    assert "replay_kernel_ms" not in m and "cache_sim_roofline" not in m
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_a_missing_span_target_fails_the_traced_run(cpu_run, monkeypatch):
    import repro.core.manager as M
    monkeypatch.delattr(M, "analyze_windows")
    with pytest.raises(AttributeError):
        cpu_run(tiny("msr16-bind"), trace=True)


def test_no_chip_no_result(monkeypatch, capsys):
    import run as RUN
    import jax
    monkeypatch.setattr(RUN, "setup_jax", lambda: jax)
    rc = RUN.main(["--workload", "msr16-bind", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", CELLS)
def test_warm_up_ends_where_the_state_repeats(name):
    """After set-up, every further cycle of the pool leaves the manager in
    the state set-up left it in, so the measured windows meet the
    (state, tape) pairs, and with them the program shapes, of warm-up."""
    import cell as C
    run = C.Run(tiny(name), 2**31 + 99)
    run.setup()
    settled = run.state_key()
    for _ in range(2):
        for _ in run.pool:
            run.step()
        assert run.state_key() == settled


def test_a_state_that_does_not_settle_fails_set_up():
    import cell as C
    run = C.Run(tiny("msr16-bind", cycles=1), 2**31 + 99)
    with pytest.raises(RuntimeError, match="did not repeat"):
        run.setup()
