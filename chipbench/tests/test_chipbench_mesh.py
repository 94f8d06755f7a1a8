"""The harness's `mesh` branch: the mesh is built over the run's own
devices, its size has to be the cell's chips, a mix without `mesh`
runs `Study.run()` as before, and on four CPU devices `correct` comes
out false under each fault the four-chip cell can have."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness, spec
from chipbench.tests import faults

MESH_CELL = "vitb-edp.search-rung-mesh4"


@pytest.fixture(scope="module")
def four_devices():
    """`mesh_faults` in a process with eight CPU devices."""
    src = os.path.join(spec.ROOT, "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   p for p in (src, spec.ROOT, os.environ.get("PYTHONPATH"))
                   if p))
    p = subprocess.run([sys.executable, "-m", "chipbench.tests.mesh_faults"],
                       cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=400)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_is_built_over_exactly_the_devices_given(four_devices):
    assert four_devices["mesh"] == four_devices["given"] == [4, 5, 6, 7]
    assert four_devices["axes"] == ["data"]
    # every Study of every run was given the mesh
    assert four_devices["calls"]
    assert all(c == ["mesh"] for c in four_devices["calls"])


def test_sound_run_on_four_devices_is_correct(four_devices):
    r = four_devices["sound"]
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4
    assert r["device"]["count"] == 4


@pytest.mark.parametrize("fault", [*faults.FAULTS, *faults.MESH_FAULTS])
def test_each_fault_on_four_devices_reads_not_correct(four_devices, fault):
    r = four_devices["faults"][fault]
    assert not r["correct"], (fault, r["checks"])


def test_a_mesh_of_another_size_than_the_cells_chips_is_an_error():
    cell = faults.tiny(MESH_CELL, 4)
    with pytest.raises(ValueError, match="mesh"):
        harness.Workload(cell, 3, ["a", "b"])
    cell.mix["mesh"] = {"shape": [2], "axes": ["data"]}
    with pytest.raises(ValueError, match="mesh"):
        harness.Workload(cell, 3, ["a", "b", "c", "d"])


def test_a_mix_without_mesh_runs_the_study_with_no_arguments(monkeypatch):
    from repro.api import Study
    calls = []
    monkeypatch.setattr(Study, "run", lambda self, **kw: calls.append(kw))
    for w in spec.load_benchmark()["workloads"]:
        cell = spec.find_cell(spec.load_benchmark(), w["name"])
        if "mesh" in cell.mix:
            continue
        cell.mix["slots"] = cell.mix["slots"][:2]
        wl = harness.Workload(cell, 3, ["a"])
        assert wl.mesh is None
        wl.run(0)
    assert calls and all(kw == {} for kw in calls)
