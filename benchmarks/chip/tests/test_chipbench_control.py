"""The check's control and its faults, at a size a test run holds.

For each cell: the program passes its limits; the control (the plain
reference in the program's place, in 8-bit floats) fails at least one of
them; and a whole run with each fault the cell can have planted under the
timed path comes out ``correct: false``.
"""
import os
import time

import pytest

import chipbench_tiny as tiny
from benchmarks.chip.lib import faults as F
from benchmarks.chip.lib import harness as H

CELLS = ["tiny.ligo", "tiny.train", "tiny.serve"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _cell(root, name):
    return H.find_cell(H.load_benchmark(root), name,
                       os.path.join(root, "benchmarks", "chip"))


def _readings(root, name, seed=7):
    cell = _cell(root, name)
    driver = cell.driver()
    ctx = H.Context(cell=cell, seed=seed, seconds=1.0, trace=False,
                    t_start=time.perf_counter())
    state = driver.setup(ctx)
    if cell.traffic["driver"] == "serve_hop":
        driver.window(ctx, state)
    return cell, driver.readings(ctx, state)


def _failing(cell, nums):
    return [k for k, v in nums.items()
            if not H.Check(k, v, cell.limits.get(k)).ok]


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_control_fails(root, name):
    cell, r = _readings(root, name)
    assert set(r["program"]) == set(cell.limits)
    assert _failing(cell, r["program"]) == []
    assert _failing(cell, r["control"])


DRIVERS = {"tiny.ligo": "ligo_hop", "tiny.train": "train_step",
           "tiny.serve": "serve_hop"}


@pytest.mark.parametrize("name,fault", [
    (c, f) for c in CELLS for f in F.FAULTS[DRIVERS[c]]])
def test_fault_makes_the_run_incorrect(root, name, fault):
    with F.plant(DRIVERS[name], fault):
        res = tiny.run(root, name, seed=11)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
