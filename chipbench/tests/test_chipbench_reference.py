"""The plain reference against the program, and the control: the same
reference computed in bfloat16 must fail each cell's limits."""
import ml_dtypes
import numpy as np
import pytest

from chipbench import check, designs as dz, harness
from chipbench import reference as ref
from chipbench import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
# designs that cover each part of the reference: every dataflow, one
# and two DRAM channels at both bandwidths, the layout stage on and off
SLOTS = [
    {"array": 32, "dataflow": "os", "channels": 2, "bw": 19.2,
     "layout_banks": 0},
    {"array": 128, "dataflow": "os", "channels": 2, "bw": 19.2,
     "layout_banks": 0},
    {"array": 32, "dataflow": "is", "channels": 1, "bw": 9.6,
     "layout_banks": 64},
    {"array": 64, "dataflow": "is", "channels": 1, "bw": 9.6,
     "layout_banks": 64},
    {"array": 64, "dataflow": "ws", "channels": 2, "bw": 9.6,
     "layout_banks": 16},
]


def _designs(cell, slots):
    mix = dict(cell.mix, slots=slots)
    return dz.draw(cell.config, mix, 2**31 + 17, 0)


@pytest.mark.parametrize("name", CELLS)
def test_control_in_bfloat16_fails_the_limits(name):
    cell = spec.find_cell(spec.load_benchmark(), name)
    cell.config["gemms"] = cell.config["gemms"][:4]
    designs = _designs(cell, cell.mix["slots"][:6])
    cells = [(d, dict(ref.cell_metrics(dz.plain(d), cell.config["gemms"],
                                       fid, cell.config,
                                       num=ml_dtypes.bfloat16),
                      fidelity=fid))
             for fid in harness.fidelities(cell.mix) for d in designs]
    values = {"bad_cells": 0, **check.readings(
        cells, range(len(cells)), cell.config)}
    checks, correct = check.judge(values, cell.limits)
    assert not correct, checks


def test_reference_matches_the_program_on_a_small_study():
    from repro.api import Study
    from repro.core.accelerator import AcceleratorConfig
    from repro.core.energy import ERT
    from repro.core.workloads import Op
    from repro.trace.generator import TraceSpec
    cell = spec.find_cell(spec.load_benchmark(), CELLS[0])
    cfg = cell.config
    cfg["gemms"] = cfg["gemms"][:4]
    designs = _designs(cell, SLOTS)
    res = (Study("t").designs(
        [AcceleratorConfig.from_dict(d) for d in designs],
        [f"d{j}" for j in range(len(designs))])
        .workloads({"w": [Op(*g) for g in cfg["gemms"]]})
        .fidelity("fast", "trace")
        .options(ert=ERT(**cfg["ert"]), trace_spec=TraceSpec(
            **cfg["trace_spec"])).run())
    for fid in ("fast", "trace"):
        rows = res.filter(fidelity=fid).rows()
        cells = [(designs[int(r["design"][1:])], r) for r in rows]
        assert len(cells) == len(designs)
        got = check.readings(cells, range(len(cells)), cfg)
        assert got["analytic_err"] < 1e-5
        assert got["cycles_err"] < (1e-5 if fid == "fast" else
                                    cell.limits["cycles_err"])
        assert np.all(res.filter(fidelity=fid)["cell_status"] == 0)
