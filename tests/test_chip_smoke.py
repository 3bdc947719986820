"""``chip_smoke.py`` on CPU at tiny sizes, so the script cannot rot between
chip runs: every phase runs with its references, and ``main`` refuses to
run (non-zero exit, no ok line) when JAX finds no TPU."""
import importlib.util
import pathlib

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_a_matches_references(smoke):
    res = smoke.phase_a(n_access=256, windows=2, capacity=64, c_min=8,
                        initial_blocks=32, n_tenants=2, label="cpu")
    # off TPU the device pipeline runs in f64: decisions are bit-equal
    assert res["exact_decisions"] == 2
    assert res["summary"]["accesses"] == 2 * 2 * 256
    assert set(res["stages"]["device"]) >= {"ingest", "count", "curve"}


def test_kernel_proof_refuses_the_cpu_path(smoke, tmp_path, monkeypatch):
    """Off TPU the replay and monitors count through the jnp references,
    so the per-manager kernel check must find no kernel and raise."""
    monkeypatch.setattr(smoke, "DIAG_DIR", tmp_path / "diag")
    with pytest.raises(AssertionError, match="host:cache_sim_scan"):
        smoke.phase_a(n_access=256, windows=1, capacity=64, c_min=8,
                      initial_blocks=32, n_tenants=2, label="cpu",
                      trace_dir=tmp_path / "trace")
    assert sorted(p.name for p in (tmp_path / "trace").iterdir()) == \
        sorted(smoke.KERNELS)
    assert (tmp_path / "diag" / "smoke_kernel_events.json").is_file()


def test_phase_b_matches_reference(smoke):
    from repro.configs import get_smoke_config
    res = smoke.phase_b(get_smoke_config("qwen3_0_6b"), requests=4,
                        tenants=2, system_prompt=32, user_prompt=16,
                        new_tokens=4, page_size=16, pool_pages=32,
                        capacity=16, label="cpu")
    assert res["tokens"] == 16 and res["agree"] == 16
    assert res["prefill_err"] <= smoke.LOGIT_TOL


def test_phase_sharded_matches_device(smoke):
    res = smoke.phase_sharded(n_tenants=8, n_access=256, windows=2,
                              capacity=8 * 16, c_min=8, label="cpu")
    assert res["exact"] == 2


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.setup_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.setup_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
