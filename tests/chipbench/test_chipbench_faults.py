"""Whole runs of the cells on the CPU, the look for a chip skipped: each
fault planted in the timed path turns ``correct`` false, and the unbroken
run stays correct."""
import time

import pytest
from tinycells import tiny

from chipbench import faults, run, spec

CELLS = {
    name: faults.names(spec.resolve(name).kind)
    for name in (
        "paper-regular100.fig1-decafork",
        "paper-regular100.fig1-baseline",
        "rwsgd-regular100.learn",
    )
}


def _run(name, fault):
    cell = tiny(name)
    with faults.planted(cell.kind, fault):
        return run.run(cell, 2**31 + 11, 0.1, False, require_tpu=False, t0=time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_unbroken_run_is_correct(name):
    res = _run(name, None)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["checks"]["mismatch_rounds"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("name,fault", [(c, f) for c, fs in CELLS.items() for f in fs])
def test_broken_run_is_not_correct(name, fault):
    res = _run(name, fault)
    assert not res["correct"], res["checks"]
