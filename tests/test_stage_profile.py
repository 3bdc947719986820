"""``StageProfile``, the control plane's tracer, and its stages through
``ECICacheManager.run_window``."""
import glob

import numpy as np
import pytest

import repro.core.batch_sim as batch_sim
import repro.kernels.cache_sim.ops as cache_sim_ops
from repro.core import ECICacheManager, StageProfile, Trace
from repro.core import device_pipeline, stage_profile

# every stage of a window with its parent
WINDOW_STAGES = {
    "window": None,
    "replay": "window",
    "replay_tape": "replay",
    "replay_live_counts": "replay",
    "replay_ro_tokens": "replay",
    "replay_residency": "replay",
    "replay_account": "replay",
    "monitor": "window",
    "partition": "window",
}
REPLAY_STAGES = [s for s, p in WINDOW_STAGES.items() if p == "replay"]


def test_importable_from_its_old_homes():
    assert device_pipeline.StageProfile is stage_profile.StageProfile
    assert StageProfile is stage_profile.StageProfile


def test_stages_nest_count_and_reset():
    prof = StageProfile()
    with prof.stage("outer"):
        with prof.stage("inner"):
            prof.count("items", 3)
        with prof.stage("inner"):
            prof.count("items")
            prof.sync()
    assert prof.parents == {"outer": None, "inner": "outer"}
    assert prof.counts == {"items": 4} and prof.syncs == 1
    assert 0 < prof.times["inner"] <= prof.times["outer"]
    assert prof.self_time("outer") == pytest.approx(
        prof.times["outer"] - prof.times["inner"])
    assert prof.self_time("inner") == prof.times["inner"]
    rep = prof.report()
    assert rep["counts"] == {"items": 4} and rep["parents"]["inner"] == "outer"
    prof.reset()
    assert (prof.times, prof.parents, prof.counts, prof.syncs,
            prof.windows) == ({}, {}, {}, 0, 0)


def test_steps_tile_a_body_and_close_with_their_parent():
    prof = StageProfile()
    with prof.stage("call"):
        prof.step("a")
        prof.step("b")
        prof.step(None)
    assert prof.parents == {"call": None, "a": "call", "b": "call"}
    assert prof.times["a"] + prof.times["b"] <= prof.times["call"]
    with pytest.raises(RuntimeError):
        with prof.stage("call"):
            prof.step("c")
            raise RuntimeError("a step left open")
    assert prof._open == [] and "c" in prof.times
    prof.step("top")                # a step outside any stage
    prof.step(None)
    assert prof._open == [] and prof.parents["top"] is None


def test_a_window_counts_once_at_the_outermost_caller():
    prof = StageProfile()
    with prof.stage("window"):
        prof.end_window()           # a layer inside the caller's window
    prof.end_window()
    assert prof.windows == 1


def test_stages_are_host_spans_in_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    prof = StageProfile()
    jax.profiler.start_trace(str(tmp_path))
    with prof.stage("stage_outer"):
        prof.step("stage_step")
        prof.step(None)
    jax.profiler.stop_trace()
    names = {ev.name
             for path in glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                                   recursive=True)
             for plane in ProfileData.from_file(path).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events}
    assert {"stage_outer", "stage_step"} <= names


# ------------------------------------------------ through run_window
def _tapes(w: int, n: int = 400, space: int = 120, tenants: int = 4):
    """Half writes over a small address space: Alg. 3 makes every tenant
    RO, and the cut cache puts it under eviction pressure."""
    out = []
    for i in range(tenants):
        r = np.random.default_rng(1000 * w + i)
        out.append(Trace(r.integers(0, space, n).astype(np.int64),
                         r.random(n) < 0.5, f"t{i}"))
    return out


def _manager(profile=None, **kwargs):
    return ECICacheManager(capacity=72, tenant_names=[f"t{i}" for i in
                                                      range(4)],
                           c_min=6, w_threshold=0.1, initial_blocks=18,
                           profile=profile, **kwargs)


def _recorded(monkeypatch, module, name, log):
    fn = getattr(module, name)

    def wrapped(*args, **kwargs):
        log.append((name, len(args[0])))
        return fn(*args, **kwargs)
    monkeypatch.setattr(module, name, wrapped)


def _same_run(a, b, capacity2):
    """Two managers that ran the same windows decided and hold the same."""
    assert len(a.history) == len(b.history)
    for x, y in zip(a.history, b.history):
        np.testing.assert_array_equal(x.sizes, y.sizes)
        np.testing.assert_array_equal(x.sizes2, y.sizes2)
        assert x.policies == y.policies and x.policies2 == y.policies2
    assert a.summary() == b.summary()
    for ta, tb in zip(a.tenants, b.tenants):
        assert ta.result == tb.result
        levels = ((ta.cache, tb.cache), (ta.cache2, tb.cache2)) \
            if capacity2 else ((ta.cache, tb.cache),)
        for ca, cb in levels:
            for xa, xb in zip(ca.state_arrays(), cb.state_arrays()):
                np.testing.assert_array_equal(xa, xb)


def _three_windows(monkeypatch, accel, capacity2, profile=None):
    monkeypatch.setattr(batch_sim, "_ACCEL", accel)
    mgr = _manager(profile, capacity2=capacity2)
    for w in range(3):
        mgr.run_window(_tapes(w))
    return mgr


@pytest.mark.parametrize("accel, capacity2", [
    pytest.param(False, 0, id="host"),
    pytest.param(True, 0, id="device"),
    pytest.param(False, 48, id="host_two_level"),
    pytest.param(True, 48, id="device_two_level")])
def test_run_window_stages_and_counters(monkeypatch, accel, capacity2):
    """Stages, counters and host syncs of RO tenants under eviction
    pressure; on the accelerator path every result equals the host's."""
    tokens, launches = [], []
    for name in ("_ro_token_replay", "_ro_token_replay_levels"):
        _recorded(monkeypatch, batch_sim, name, tokens)
    for name in ("stack_distances_accel", "residency_levels_accel",
                 "stack_distances_segments_accel"):
        _recorded(monkeypatch, cache_sim_ops, name, launches)
    prof = StageProfile()
    mgr = _three_windows(monkeypatch, accel, capacity2, prof)
    assert all(t.policy.value == "ro" for t in mgr.tenants)
    assert prof.windows == 3
    assert {s: prof.parents.get(s, "missing") for s in WINDOW_STAGES} \
        == WINDOW_STAGES
    replay = prof.times["replay"]
    assert sum(prof.times[s] for s in REPLAY_STAGES) == \
        pytest.approx(replay, rel=0.05)
    assert prof.self_time("window") == pytest.approx(
        prof.times["window"] - replay - prof.times["monitor"]
        - prof.times["partition"])
    assert tokens and prof.counts["ro_tokens"] == sum(n for _, n in tokens)
    if accel:
        # the exact monitor reuses the replay's distances: no launch of
        # its own; every replay program is one fetch
        assert "stack_distances_segments_accel" not in dict(launches)
        assert prof.syncs == len(launches)
        _same_run(_three_windows(monkeypatch, False, capacity2), mgr,
                  capacity2)
    else:
        assert not launches and prof.syncs == 0


def test_a_profile_leaves_every_result_unchanged():
    plain, profiled = _manager(), _manager(StageProfile())
    for w in range(4):
        for mgr in (plain, profiled):
            mgr.run_window(_tapes(w))
    for a, b in zip(plain.history, profiled.history):
        np.testing.assert_array_equal(a.sizes, b.sizes)
        assert a.policies == b.policies
    assert plain.summary() == profiled.summary()
    for ta, tb in zip(plain.tenants, profiled.tenants):
        assert ta.result == tb.result
        for xa, xb in zip(ta.cache.state_arrays(), tb.cache.state_arrays()):
            np.testing.assert_array_equal(xa, xb)
