"""The control and the planted faults of ``control.py`` come out not
correct (``control.py`` runs them on the chip at each cell's own size;
here at a tiny size on the CPU).

The configuration states float64 curves and latency.  The control
``f32_reference`` puts the plain reference, in float32, in the place of
the replay's latency accounting and the monitor's curve heights.  The
fault ``first_fit`` keeps every constraint of Eq. 2 and breaks only its
objective.
"""
import pytest

import control
from conftest import tiny


def test_f32_reference_control_is_not_correct(cpu_run):
    res = cpu_run(tiny("msr16-bind", div=16), in_place=control.f32_reference)
    assert not res["correct"]
    c = res["checks"]
    assert c["latency_rel_gap"]["value"] > c["latency_rel_gap"]["limit"], c


def test_first_fit_partition_breaks_only_the_objective(cpu_run):
    res = cpu_run(tiny("msr16-bind"), **control.CONTROLS["first_fit"])
    assert not res["correct"]
    c = res["checks"]
    assert c["partition_off"]["value"] == 0, c
    assert c["partition_gap"]["value"] > c["partition_gap"]["limit"], c


def test_the_program_partition_is_near_the_optimum(cpu_run):
    res = cpu_run(tiny("msr16-bind"))
    assert 0 < res["checks"]["partition_gap"]["value"] \
        <= res["checks"]["partition_gap"]["limit"]
