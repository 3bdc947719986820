"""Distributed behaviour on multi-host-device CPU meshes.

Each test runs in a subprocess that overwrites ``XLA_FLAGS`` with its own
``xla_force_host_platform_device_count`` before importing jax, so the
device count each script sees is exactly what it asked for — independent
of the 8-device flag conftest now sets for the in-process shard suite
(``tests/test_shard_pipeline.py``).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout: int = 420) -> str:
    code = (
        "import os\n"
        f'os.environ["XLA_FLAGS"] = '
        f'"--xla_force_host_platform_device_count={devices}"\n'
        + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_pjit_train_step_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models import model as M
        from repro.optim.adamw import AdamWConfig
        from repro.train.steps import make_train_step, init_train_state
        from repro.launch.mesh import make_test_mesh
        from repro.distributed.sharding import state_specs, batch_specs
        from repro.distributed.ctx import activation_rules, default_train_rules
        from repro.data.lm import SyntheticLM

        cfg = get_smoke_config("qwen3_0_6b")
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        opt = AdamWConfig(lr_peak=1e-3)
        step = make_train_step(cfg, opt)
        state = init_train_state(params)
        data = SyntheticLM(cfg.vocab_size, 32, 8, seed=0)
        b = data.batch_at(0)

        # single device
        s1, m1 = jax.jit(step)(state, b)

        # 2x2 mesh
        mesh = make_test_mesh(2, 2)
        sspec = state_specs(state, mesh)
        bspec = batch_specs(b, mesh)
        with mesh:
            with activation_rules(default_train_rules(mesh)):
                f = jax.jit(step, in_shardings=(sspec, bspec),
                            out_shardings=(sspec, NamedSharding(mesh, P())))
                s2, m2 = f(jax.device_put(state, sspec),
                           jax.device_put(b, bspec))
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert abs(l1 - l2) / abs(l1) < 2e-4, (l1, l2)
        # params agree after one step
        for a, c in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-3, atol=2e-4)
        print("PJIT_MATCH_OK", l1, l2)
    """)
    assert "PJIT_MATCH_OK" in out


def test_multipod_mesh_and_dp_axes():
    out = run_py("""
        from repro.launch.mesh import make_test_mesh, dp_axes
        m = make_test_mesh(2, 2, pod=2)
        assert m.axis_names == ("pod", "data", "model")
        assert dp_axes(m) == ("pod", "data")
        print("MESH_OK")
    """)
    assert "MESH_OK" in out


def test_sharding_specs_divisibility_all_archs():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import ARCH_IDS, get_smoke_config
        from repro.models import model as M
        from repro.launch.mesh import make_test_mesh
        from repro.distributed.sharding import param_specs
        mesh = make_test_mesh(2, 2)
        for arch in ARCH_IDS:
            cfg = get_smoke_config(arch)
            sds = jax.eval_shape(
                lambda k: M.init_params(cfg, k),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            specs = param_specs(sds, mesh)
            # every spec must evenly divide its leaf (or be replicated)
            def check(path, leaf, spec):
                for dim, entry in zip(leaf.shape, spec.spec):
                    if entry is None: continue
                    axes = entry if isinstance(entry, tuple) else (entry,)
                    tot = 1
                    for a in axes:
                        tot *= dict(zip(mesh.axis_names,
                                        mesh.devices.shape))[a]
                    assert dim % tot == 0, (arch, path, leaf.shape, spec)
            jax.tree_util.tree_map_with_path(
                lambda p, l, s: check(p, l, s), sds, specs)
        print("SPECS_OK")
    """)
    assert "SPECS_OK" in out


def test_elastic_restore_across_meshes():
    out = run_py("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models import model as M
        from repro.launch.mesh import make_test_mesh
        from repro.distributed.sharding import param_specs
        from repro.checkpoint.checkpoint import (save_checkpoint,
                                                 restore_checkpoint)
        cfg = get_smoke_config("qwen3_0_6b")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        mesh4 = make_test_mesh(2, 2)
        p4 = jax.device_put(params, param_specs(params, mesh4))
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, p4, 1)
            mesh2 = make_test_mesh(2, 1)       # "shrunk cluster"
            restored, s = restore_checkpoint(
                d, params, shardings=param_specs(params, mesh2))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=1e-6)
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


def test_compressed_psum_shard_map():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.optim.compression import compressed_psum, init_error_state
        mesh = make_test_mesh(4, 1)
        rows = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.3
        g = {"w": rows}
        err = init_error_state(g)

        def body(g, e):
            return compressed_psum(g, e, "data")

        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P("data"), P("data")),
                          out_specs=(P("data"), P("data")))
        red, new_err = f(g, err)
        # DP mean across shards, each with its own int8 scale
        expect = jnp.broadcast_to(rows.mean(axis=0, keepdims=True), rows.shape)
        np.testing.assert_allclose(np.asarray(red["w"]),
                                   np.asarray(expect),
                                   rtol=2e-2, atol=2e-2)
        print("COMPRESS_OK")
    """)
    assert "COMPRESS_OK" in out


def test_decode_cache_specs_multipod():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import model as M
        from repro.launch.mesh import make_test_mesh
        from repro.distributed.sharding import cache_specs
        mesh = make_test_mesh(2, 2, pod=2)
        cfg = get_smoke_config("command_r_plus_104b")
        cache = jax.eval_shape(lambda: M.init_decode_cache(cfg, 8, 64))
        specs = cache_specs(cache, mesh)
        kspec = specs["kv"]["k"].spec
        assert kspec[1] is not None       # batch sharded over DP
        assert kspec[2] == "model"        # sequence split-KV
        print("CACHE_SPEC_OK", kspec)
    """, devices=8)
    assert "CACHE_SPEC_OK" in out
