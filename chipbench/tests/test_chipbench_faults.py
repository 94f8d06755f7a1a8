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
BENCH = spec.load_benchmark()
# every cell whose Studies run without a mesh (the mesh cell's runs are
# in test_chipbench_mesh.py)
NO_MESH = [w["name"] for w in BENCH["workloads"]
           if "mesh" not in spec.find_cell(BENCH, w["name"]).mix]


def run(cell):
    return harness.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                            time.perf_counter())


@pytest.mark.parametrize("name", NO_MESH)
def test_sound_run_is_correct_and_each_fault_is_not(name):
    cell = faults.tiny(name, 4)
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4
    for fault, plant in faults.for_cell(cell.mix).items():
        with plant(cell.mix["fidelity"]):
            broken = run(cell)
        assert not broken["correct"], (fault, broken["checks"])

