"""The trace reduction, on synthetic events and on a small trace recorded
on a TPU v5e (``data/trace_small.json.gz``, from ``record_trace.py``)."""
import gzip
import json
from pathlib import Path

import pytest

import reduce

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json.gz"
TPU = "/device:TPU:0"


def ev(plane, line, name, start, dur):
    return (plane, line, name, start, dur)


SYN = [ev(TPU, "XLA Ops", "fusion.1", 100, 50),
       ev(TPU, "XLA Ops", "%cache_sim_scan.3 = s32[4] custom-call()", 140, 200),
       ev(TPU, "XLA Ops", "%cache_sim_levels_scan.1 = s32[4]", 500, 100),
       ev(TPU, "XLA Modules", "jit_cache_sim_op", 100, 400),
       ev("/host:CPU", "python", "simulate_many", 0, 450),
       ev("/host:CPU", "python", "partition_fn", 600, 400),
       ev("/host:CPU", "python", "chipbench_window", 0, 1000)]


def test_busy_is_the_union_of_op_intervals():
    # ops cover [100, 340) and [500, 600): 340 ns of the window
    assert reduce.busy(SYN, 0, 1000) == pytest.approx(340e-9)
    assert reduce.busy(SYN, 200, 550) == pytest.approx(190e-9)


def test_kernels_are_found_by_operation_name():
    assert reduce.kernel_seconds(SYN, ["cache_sim_scan"], 0, 1000) == \
        pytest.approx(200e-9)
    assert reduce.kernel_seconds(
        SYN, ["cache_sim_scan", "cache_sim_levels_scan"], 0, 1000) == \
        pytest.approx(300e-9)


def test_idle_gaps_are_named_by_the_host_span_around_them():
    spans = reduce.host_spans(SYN, ["simulate_many", "partition_fn"])
    gaps = reduce.idle_gaps(SYN, 0, 1000, spans)
    assert gaps[0] == ["partition_fn", pytest.approx(400e-9)]
    assert ["simulate_many", pytest.approx(100e-9)] in gaps
    assert sum(g[1] for g in gaps) == pytest.approx(660e-9)


def test_top_ops_sums_by_name():
    top = reduce.top_ops(SYN + [ev(TPU, "XLA Ops", "fusion.1", 700, 250)],
                         0, 1000)
    assert top[0] == ["fusion.1", pytest.approx(300e-9)]
    assert ["%cache_sim_scan.3", pytest.approx(200e-9)] in top


WINS = [("chipbench_window", 0, 450), ("chipbench_window", 450, 1000)]
KERNELS = ("cache_sim_scan", "cache_sim_levels_scan")


def test_a_complete_trace_passes_the_check():
    reduce.check_complete(SYN, WINS, KERNELS)


@pytest.mark.parametrize("lost", [
    "%cache_sim_levels_scan.1 = s32[4]",       # the second window's ops
    "%cache_sim_scan.3 = s32[4] custom-call()"])  # one kernel launch
def test_a_trace_that_lost_events_fails_the_check(lost):
    events = [e for e in SYN if e[2] != lost]
    with pytest.raises(RuntimeError, match="lost events"):
        reduce.check_complete(events, WINS, KERNELS)
    with pytest.raises(RuntimeError, match="no TPU operation"):
        reduce.check_complete([e for e in SYN if e[0] != TPU], WINS, KERNELS)


def test_recorded_tpu_trace():
    rec = json.loads(gzip.decompress(DATA.read_bytes()))
    events = [tuple(e[:5]) for e in rec["events"]]
    assert [TPU, reduce.OPS_LINE] in rec["lines"]
    win = reduce.host_spans(events, ["chipbench_window"])
    (_, lo, hi), = win
    b = reduce.busy(events, lo, hi)
    assert 0 < b < (hi - lo) / 1e9
    k = reduce.kernel_seconds(events, ["cache_sim_scan"], lo, hi)
    assert 0 < k <= b
    reduce.check_complete(events, win, ["cache_sim_scan"])
    gaps = reduce.idle_gaps(events, lo, hi, reduce.host_spans(
        events, ["simulate_many", "analyze_windows", "partition_fn"]))
    assert gaps and all(g[1] > 0 for g in gaps)
    assert b + sum(g[1] for g in reduce.idle_gaps(
        events, lo, hi, [], k=10**6)) == pytest.approx((hi - lo) / 1e9)
