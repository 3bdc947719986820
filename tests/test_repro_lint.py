"""Fixture-driven tests for the repro-lint AST pass (tools/repro_lint).

Per rule: a true positive (the violation is found), a true negative (the
compliant idiom is NOT flagged — precision is what makes the pass
adoptable), and suppression handling.  Plus the meta-tests the satellite
demands: registry / README catalog / --list-rules stay in sync, the real
tree lints clean, and a seeded ``.item()`` violation in a copy of
``device_pipeline.py`` is caught (the CI-failure path).
"""
import json
import pathlib
import re
import subprocess
import sys
import textwrap

import pytest

from tools.repro_lint import REGISTRY, lint_paths
from tools.repro_lint.cli import list_rules

REPO = pathlib.Path(__file__).resolve().parents[1]


def run_lint(tmp_path, files, select=None):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return lint_paths([tmp_path], root=tmp_path, select=select)


def rule_hits(res, rule):
    return [f for f in res.findings if f.rule == rule]


# ================================================================= RL001
JITTED_SYNC = """
    import jax
    import jax.numpy as jnp

    def stage(d):
        s = jnp.sum(d["x"])
        return s.item()

    prog = jax.jit(stage)
"""


def test_rl001_item_in_jitted_function(tmp_path):
    res = run_lint(tmp_path, {"core/device_pipeline.py": JITTED_SYNC},
                   select=["RL001"])
    (f,) = rule_hits(res, "RL001")
    assert ".item()" in f.message and f.path == "core/device_pipeline.py"


def test_rl001_float_and_numpy_on_traced(tmp_path):
    src = """
        import jax
        import numpy as np

        def stage(d):
            a = float(d["x"])
            b = np.asarray(d["y"])
            return a, b

        prog = jax.jit(stage)
    """
    res = run_lint(tmp_path, {"core/device_pipeline.py": src},
                   select=["RL001"])
    msgs = " | ".join(f.message for f in rule_hits(res, "RL001"))
    assert "float()" in msgs and "np.asarray" in msgs


def test_rl001_negative_static_and_shape_sanitized(tmp_path):
    src = """
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("width",))
        def op(x, width):
            t = int(width)              # static: a trace-time python int
            n = int(x.shape[0])         # sanitized through .shape
            return x * t + n

        def host_wrapper(arr):
            import numpy as np
            return np.asarray(arr)      # not reachable from any jit
    """
    res = run_lint(tmp_path, {"kernels/foo/ops.py": src}, select=["RL001"])
    assert rule_hits(res, "RL001") == []


def test_rl001_propagates_through_called_helper(tmp_path):
    src = """
        import jax
        import jax.numpy as jnp

        def helper(v):
            return v.item()

        def stage(d):
            return helper(jnp.sum(d["x"]))

        prog = jax.jit(stage)
    """
    res = run_lint(tmp_path, {"core/device_pipeline.py": src},
                   select=["RL001"])
    (f,) = rule_hits(res, "RL001")
    assert ".item()" in f.message


def test_rl001_suppression_honored(tmp_path):
    src = JITTED_SYNC.replace(
        "return s.item()",
        "return s.item()  # repro-lint: disable=RL001")
    res = run_lint(tmp_path, {"core/device_pipeline.py": src},
                   select=["RL001"])
    assert res.ok and len(res.suppressed) == 1


def test_rl001_suppression_on_preceding_comment_line(tmp_path):
    src = JITTED_SYNC.replace(
        "return s.item()",
        "# repro-lint: disable=RL001\n        return s.item()")
    res = run_lint(tmp_path, {"core/device_pipeline.py": src},
                   select=["RL001"])
    assert res.ok and len(res.suppressed) == 1


def test_rl001_sees_through_shard_map_bodies(tmp_path):
    """A host sync inside a ``shard_map`` body is traced code exactly like
    a jitted function (the rule gap the shard pipeline exposed): flagged,
    while a sync-free body stays clean."""
    src = """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def body(tape):
            s = jnp.sum(tape["x"])
            return s.item()

        prog = jax.shard_map(body, mesh=None, in_specs=(P(),),
                             out_specs=P())
    """
    res = run_lint(tmp_path, {"core/shard_pipeline.py": src},
                   select=["RL001"])
    (f,) = rule_hits(res, "RL001")
    assert ".item()" in f.message and f.path == "core/shard_pipeline.py"
    clean = src.replace("return s.item()", "return s")
    res = run_lint(tmp_path, {"core/shard_pipeline.py": clean},
                   select=["RL001"])
    assert rule_hits(res, "RL001") == []


# ================================================================= RL002
def test_rl002_missing_oracle(tmp_path):
    res = run_lint(tmp_path, {
        "kernels/foo/kernel.py": """
            __all__ = ["foo_scan"]
            def foo_scan(x):
                return x
        """,
        "kernels/foo/ref.py": """
            __all__ = ["unrelated_ref"]
            def unrelated_ref(x):
                return x
        """,
    }, select=["RL002"])
    (f,) = rule_hits(res, "RL002")
    assert "no matching oracle" in f.message


def test_rl002_missing_differential_test(tmp_path):
    res = run_lint(tmp_path, {
        "kernels/foo/kernel.py": """
            __all__ = ["foo_scan"]
            def foo_scan(x):
                return x
        """,
        "kernels/foo/ref.py": """
            __all__ = ["foo_ref"]
            def foo_ref(x):
                return x
        """,
        "tests/test_other.py": "def test_nothing():\n    pass\n",
    }, select=["RL002"])
    (f,) = rule_hits(res, "RL002")
    assert "differential coverage" in f.message


def test_rl002_triad_complete(tmp_path):
    res = run_lint(tmp_path, {
        "kernels/foo/kernel.py": """
            __all__ = ["foo_scan"]
            def foo_scan(x):
                return x
        """,
        "kernels/foo/ref.py": """
            __all__ = ["foo_ref"]
            def foo_ref(x):
                return x
        """,
        "tests/test_foo.py": """
            from kernels.foo.kernel import foo_scan
            from kernels.foo.ref import foo_ref
            def test_match():
                assert foo_scan(1) == foo_ref(1)
        """,
    }, select=["RL002"])
    assert res.ok


# ================================================================= RL003
def test_rl003_default_on_and_wrong_enum(tmp_path):
    res = run_lint(tmp_path, {
        "core/monitor.py": """
            def analyze_windows(traces, kind="urd", shiny=True,
                                pipeline="device"):
                return None
        """,
        "tests/test_m.py": "def test_x():\n    pass\n",
    }, select=["RL003"])
    msgs = " | ".join(f.message for f in rule_hits(res, "RL003"))
    assert "must default to False" in msgs
    assert "must default to 'host'" in msgs
    assert "not named in any test" in msgs


def test_rl003_compliant_flags(tmp_path):
    res = run_lint(tmp_path, {
        "core/monitor.py": """
            def analyze_windows(traces, kind="urd", shiny=False,
                                pipeline="host"):
                return None
        """,
        "tests/test_m.py": """
            def test_bit_identity():
                shiny = False
                pipeline = "host"
        """,
    }, select=["RL003"])
    assert res.ok


def test_rl003_suppression(tmp_path):
    res = run_lint(tmp_path, {
        "core/monitor.py": """
            def analyze_windows(
                    traces,
                    shiny=True):  # repro-lint: disable=RL003
                return None
        """,
        "tests/test_m.py": "def test_x():\n    shiny = True\n",
    }, select=["RL003"])
    assert res.ok and len(res.suppressed) == 1


# ================================================================= RL004
COUNTER_CLASS = """
    class Mgr:
        def __init__(self):
            self.foo_events = 0
            self.bar_windows = 0
            self._hidden_windows = 0

        def work(self):
            self.foo_events += 1
            self.bar_windows += 1
            self._hidden_windows += 1

        def summary(self):
            return {"bar_windows": self.bar_windows}
"""


def test_rl004_unregistered_and_untested_counter(tmp_path):
    res = run_lint(tmp_path, {
        "core/m.py": COUNTER_CLASS,
        "tests/test_m.py": """
            def test_counts():
                assert mgr.summary()["bar_windows"] == 1
        """,
    }, select=["RL004"])
    hits = rule_hits(res, "RL004")
    msgs = " | ".join(f.message for f in hits)
    assert "missing from Mgr.summary()" in msgs
    assert "no test assertion" in msgs
    # private attrs and registered+tested counters are not flagged
    assert all("foo_events" in f.message for f in hits)


def test_rl004_clean_when_registered_and_tested(tmp_path):
    res = run_lint(tmp_path, {
        "core/m.py": COUNTER_CLASS.replace(
            '{"bar_windows": self.bar_windows}',
            '{"bar_windows": self.bar_windows, '
            '"foo_events": self.foo_events}'),
        "tests/test_m.py": """
            def test_counts():
                assert mgr.summary()["bar_windows"] == 1
                assert mgr.summary()["foo_events"] == 1
        """,
    }, select=["RL004"])
    assert res.ok


# ================================================================= RL005
def test_rl005_global_config_mutation(tmp_path):
    res = run_lint(tmp_path, {"core/x.py": """
        import jax
        jax.config.update("jax_enable_x64", True)
    """}, select=["RL005"])
    (f,) = rule_hits(res, "RL005")
    assert "global" in f.message


def test_rl005_unscoped_call_and_attr_assign(tmp_path):
    res = run_lint(tmp_path, {"core/x.py": """
        import jax
        from jax.experimental import enable_x64

        def f():
            enable_x64()          # called for effect: leaks

        jax.config.jax_enable_x64 = True
    """}, select=["RL005"])
    assert len(rule_hits(res, "RL005")) == 2


def test_rl005_scoped_uses_allowed(tmp_path):
    res = run_lint(tmp_path, {"core/x.py": """
        import contextlib
        from jax.experimental import enable_x64

        def _x64(f64):
            if f64:
                return enable_x64()
            return contextlib.nullcontext()

        def work():
            with enable_x64():
                return 1
    """}, select=["RL005"])
    assert res.ok


# ================================================================= RL006
def test_rl006_closure_mutation_in_scan_body(tmp_path):
    res = run_lint(tmp_path, {"core/x.py": """
        from jax import lax

        def outer(xs):
            acc = []

            def body(c, x):
                acc.append(x)
                return c, x

            return lax.scan(body, 0, xs)
    """}, select=["RL006"])
    (f,) = rule_hits(res, "RL006")
    assert "acc" in f.message and "scan" in f.message


def test_rl006_nonlocal_and_subscript_write(tmp_path):
    res = run_lint(tmp_path, {"core/x.py": """
        from jax import lax

        def outer(n, table):
            total = 0

            def body(i, c):
                nonlocal total
                table[i] = c
                return c + 1

            return lax.fori_loop(0, n, body, 0)
    """}, select=["RL006"])
    msgs = " | ".join(f.message for f in rule_hits(res, "RL006"))
    assert "nonlocal" in msgs and "table" in msgs


def test_rl006_pure_bodies_clean(tmp_path):
    res = run_lint(tmp_path, {"core/x.py": """
        import jax.numpy as jnp
        from jax import lax

        def outer(n, xs, hist):
            def body(i, carry):
                acc, h = carry
                local = {}
                local["k"] = i                   # local container: fine
                h = h.at[i].add(1)               # functional update: fine
                return acc + xs[i], h

            return lax.fori_loop(0, n, body, (jnp.float32(0), hist))
    """}, select=["RL006"])
    assert res.ok


# ============================================================== meta-tests
ALL_RULES = ("RL001", "RL002", "RL003", "RL004", "RL005", "RL006")


def test_registry_has_all_rules():
    assert tuple(sorted(REGISTRY)) == ALL_RULES
    for rid, rule in REGISTRY.items():
        assert rule.id == rid and rule.name and rule.summary


def test_list_rules_matches_registry():
    out = list_rules()
    for rid, rule in REGISTRY.items():
        assert re.search(rf"^{rid} {re.escape(rule.name)}:", out,
                         re.MULTILINE), rid


def test_readme_catalog_matches_registry():
    readme = (REPO / "tools" / "repro_lint" / "README.md").read_text()
    table_ids = set(re.findall(r"^\|\s*(RL\d{3})\s*\|", readme,
                               re.MULTILINE))
    assert table_ids == set(REGISTRY)
    for rule in REGISTRY.values():
        assert rule.name in readme, rule.id


def test_real_tree_is_clean():
    """The standing quality bar: src + tests + benchmarks lint clean."""
    res = lint_paths([REPO / "src", REPO / "tests", REPO / "benchmarks"],
                     root=REPO)
    assert res.ok, "\n".join(f.render() for f in res.findings)


def test_seeded_violation_fails_the_run(tmp_path):
    """CI failure path: an .item() seeded into the real device pipeline
    module (inside the jitted count stage) must be caught."""
    real = (REPO / "src" / "repro" / "core" /
            "device_pipeline.py").read_text()
    anchor = '        hot = d["gprev"] >= 0'
    assert anchor in real
    seeded = real.replace(anchor, "        counts.item()\n" + anchor, 1)
    out = tmp_path / "core" / "device_pipeline.py"
    out.parent.mkdir(parents=True)
    out.write_text(seeded)
    res = lint_paths([out], root=tmp_path, select=["RL001"])
    assert not res.ok
    (f,) = rule_hits(res, "RL001")
    assert ".item()" in f.message


def test_cli_exit_codes_and_json(tmp_path):
    bad = tmp_path / "core" / "device_pipeline.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent(JITTED_SYNC))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.repro_lint", str(bad),
         "--root", str(tmp_path), "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["findings"][0]["rule"] == "RL001"
    good = tmp_path / "ok.py"
    good.write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.repro_lint", str(good),
         "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0
