"""Runs of the harness on a mesh of four CPU devices, for
`test_chipbench_mesh.py`.  In a process of its own, since JAX fixes its
device count when it starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python3 -m chipbench.tests.mesh_faults

The cell `vitb-edp.search-rung-mesh4` at a small size is given the last
four of the eight devices.  Prints one JSON line: the devices its mesh
holds and those it was given, the keyword arguments of each
`Study.run`, and the checks and `correct` of a sound run and of a run
under each fault.
"""
import json
import sys
import time

CELL = "vitb-edp.search-rung-mesh4"
SEED = 2**31 + 4111


def main() -> int:
    import jax
    from repro.api import Study
    from chipbench import harness
    from chipbench.tests import faults

    devices = jax.devices()[4:8]
    cell = faults.tiny(CELL, 4)
    calls = []
    real_run = Study.run

    def recorded(self, **kw):
        calls.append(sorted(kw))
        return real_run(self, **kw)
    Study.run = recorded

    def run():
        return harness.run_cell(cell, SEED, 0.2, False, devices,
                                time.perf_counter())

    mesh = harness.Workload(cell, SEED, devices).mesh
    out = {"given": [d.id for d in devices],
           "mesh": [d.id for d in mesh.devices.flat],
           "axes": list(mesh.axis_names), "sound": run(), "faults": {}}
    for name, plant in {**faults.FAULTS, **faults.MESH_FAULTS}.items():
        with plant(cell.mix["fidelity"]):
            out["faults"][name] = run()
    out["calls"] = calls
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
