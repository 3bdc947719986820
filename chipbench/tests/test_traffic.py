"""The traffic generator: a verbatim copy of the program's, and the mix
files' shape."""
import json

import numpy as np
import pytest

import generator

from conftest import BENCH


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 99])
@pytest.mark.parametrize("profile", ["wdev_0", "stg_1", "hm_1", "rsrch_2"])
def test_copy_reproduces_program_tapes(profile, seed):
    from repro.data.traces import msr_trace
    ref = msr_trace(profile, 600, seed=seed)
    got = generator.generate_trace(generator.MSR_PROFILES[profile], 600,
                                   seed, profile)
    assert np.array_equal(got.addrs, ref.addrs)
    assert np.array_equal(got.is_read, ref.is_read)


def test_profiles_are_the_programs():
    from repro.data.traces import MSR_PROFILES
    assert {k: v.__dict__ for k, v in generator.MSR_PROFILES.items()} == \
        {k: v.__dict__ for k, v in MSR_PROFILES.items()}


def test_every_cell_mix_covers_its_tenants():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cfgs = {c["name"]: json.loads((BENCH.parent / c["file"]).read_text())
            for c in spec["configs"]}
    for w in spec["workloads"]:
        if cfgs[w["config"]].get("kind") == "serving":
            continue        # test_serving_cells.py
        mix = generator.load_mix(w["traffic"])
        n = cfgs[w["config"]]["tenants"]["count"]
        assert len(generator.tenant_lengths(mix, n)) == n


def test_pool_is_a_function_of_the_seed():
    mix = {"accesses": [[3, 50]], "tape_pool_windows": 2, "base_seed": 0}
    a = generator.build_pool(["hm_1", "wdev_0", "mds_1"], mix, 2**33 + 1)
    b = generator.build_pool(["hm_1", "wdev_0", "mds_1"], mix, 2**33 + 1)
    c = generator.build_pool(["hm_1", "wdev_0", "mds_1"], mix, 2**33 + 2)
    assert all(np.array_equal(x.addrs, y.addrs)
               for wa, wb in zip(a, b) for x, y in zip(wa, wb))
    assert not np.array_equal(a[0][0].addrs, c[0][0].addrs)


def first_touch(addrs):
    """Each access labelled by the position of its block's first access."""
    _, first, inv = np.unique(addrs, return_index=True, return_inverse=True)
    return first[inv]


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 2**33 + 17])
def test_a_seed_renames_blocks_and_keeps_the_work(seed):
    """Every seed gives the base tapes' reuse structure, read flags and
    SHARDS hash bits, on blocks of its own names."""
    mix = {"accesses": [[2, 400]], "tape_pool_windows": 2, "base_seed": 3}
    profiles = ["prxy_0", "stg_1"]
    base = [[generator.generate_trace(generator.MSR_PROFILES[p], 400,
                                      3 + 1000 * w + i, p)
             for i, p in enumerate(profiles)] for w in range(2)]
    got = generator.build_pool(profiles, mix, seed)
    for i in range(2):
        b = np.concatenate([base[w][i].addrs for w in range(2)])
        g = np.concatenate([got[w][i].addrs for w in range(2)])
        assert np.array_equal(first_touch(b), first_touch(g))
        assert np.array_equal(g % 2**32, b)
        assert np.all((g >> 32 >= 1) & (g >> 32 <= 255))
        assert all(np.array_equal(base[w][i].is_read, got[w][i].is_read)
                   for w in range(2))
    assert not np.array_equal(got[0][0].addrs,
                              generator.build_pool(profiles, mix,
                                                   seed + 1)[0][0].addrs)
