"""Shared set-up of the benchmark's own tests (``pytest chipbench/tests``).

They run on the CPU at tiny sizes: the harness's look for a chip is
skipped and the persistent compilation cache is left off.
"""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (ROOT / "src", BENCH, BENCH / "traffic"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def tiny(name: str, div: int = 16, pool: int = 3, cycles: int = 12):
    """Cell ``name`` with every tenant's accesses and the cache cut by
    ``div`` (tests only)."""
    import cell as C
    c = C.Cell(name)
    c.mix = dict(c.mix, tape_pool_windows=pool, max_warmup_cycles=cycles,
                 accesses=[[k, max(n // div, 8)] for k, n in c.mix["accesses"]])
    c.config = json.loads(json.dumps(c.config))
    m = c.config["manager"]
    for key in ("capacity", "c_min", "initial_blocks"):
        m[key] = max(m[key] // div, 2)
    return c


@pytest.fixture
def cpu_run(monkeypatch):
    """``run.run_cell`` with no chip and no persistent cache."""
    import jax
    import run as RUN
    monkeypatch.setattr(RUN, "setup_jax", lambda: jax)

    def go(cell, seed=2**31 + 12345, seconds=0.3, trace=False, **kw):
        return RUN.run_cell(cell, seed, seconds, trace, need_chips=False,
                            **kw)
    return go
