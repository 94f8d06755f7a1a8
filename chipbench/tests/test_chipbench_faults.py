"""`correct` comes out false when the timed path is broken underneath.

Each test drives a whole run of the harness (set-up, window, comparison
with the reference) past its look for a chip, on the CPU at a small size
and with each cell's own limits: first sound, then once per fault the
cell can have."""
import time

import jax
import pytest

from chipbench import harness, spec
from chipbench.tests import faults

SEED = 2**31 + 4099
CELLS = spec.load_benchmark()["workloads"]


def run(cell):
    return harness.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                            time.perf_counter())


@pytest.mark.parametrize("name", [w["name"] for w in CELLS
                                  if w["chips"] == 1])
def test_sound_run_is_correct_and_each_fault_is_not(name):
    cell = faults.tiny(name, 4)
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4
    for fault, plant in faults.FAULTS.items():
        with plant(cell.mix["fidelity"]):
            broken = run(cell)
        assert not broken["correct"], (fault, broken["checks"])

