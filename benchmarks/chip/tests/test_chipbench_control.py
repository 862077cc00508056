"""The controls, the reference one precision below the configuration put in
the program's place (weights stored there, or only computed there), fail
the cell's limits (tiny size, CPU). On the chip the same readings were
taken at the cells' own sizes with calibrate.py."""

import pytest

import chipbench_tiny
from compare import readings, reference_run


@pytest.mark.parametrize("control", ["weights", "compute"])
@pytest.mark.parametrize("name", chipbench_tiny.CELLS)
def test_control_fails_a_limit(name, control):
    import run

    cell = chipbench_tiny.tiny_cell(name)
    counter = run.CompileCounter()
    try:
        data, hook, fed_seed = run.drive(cell, 2 ** 31 + 5, None, None, counter)
    finally:
        counter.close()
    ref = reference_run(cell, data, fed_seed, hook.cohorts)
    low = reference_run(cell, data, fed_seed, hook.cohorts, control=control)
    got = readings(low, ref)
    assert any(got[n] > lim for n, lim in cell["limits"].items()), got
    sound = readings(dict(hook.snap, loss=hook.loss, metric=hook.metric), ref)
    assert all(sound[n] <= lim for n, lim in cell["limits"].items()), sound
