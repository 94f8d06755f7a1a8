"""A mix that names several fidelities, and SRAM sizes to the byte.

The three one-fidelity mixes make the same `Study.fidelity` call and
draw the same designs as before; a two-fidelity mix expects a cell per
design and fidelity, and each sampled row is held to the reference at
its own fidelity; the `flip-trace` mix draws the designs of
`studies.dataflow_dram_flip()` field by field."""
import hashlib
import json

import jax
import numpy as np
import pytest

from chipbench import check, harness, spec
from chipbench import designs as dz
from chipbench import reference as ref
from chipbench.tests import faults

FLIP = "resnet18-dram.flip-trace"
# sha256 of the draws `_draw_digest` makes, as the harness drew them
# before a mix could name several fidelities or an SRAM size in tenths
# of a KiB
DRAW_DIGESTS = {
    "vitb-edp.search-rung":
        "24d886900c3789ce6fafc7de53b8f71decb0a132a90aca10eab0c57f977d4860",
    "vitb-edp.search-rung-mesh4":
        "24d886900c3789ce6fafc7de53b8f71decb0a132a90aca10eab0c57f977d4860",
    "vitb-edp.search-screen":
        "a177fe97aae04c111be8d7694f2199ef06507f5372e98d34e5c5a10175e1e4ed",
}


def _cell(name):
    return spec.find_cell(spec.load_benchmark(), name)


def _draw_digest(cell):
    h = hashlib.sha256()
    for seed in (0, 7, 2**31 + 977, 2**33 + 5, -3):
        for k in (dz.WARMUP, 0, 1, 5):
            h.update(json.dumps(dz.draw(cell.config, cell.mix, seed, k),
                                sort_keys=True).encode())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(DRAW_DIGESTS))
def test_one_fidelity_mixes_draw_byte_identical_designs(name):
    assert _draw_digest(_cell(name)) == DRAW_DIGESTS[name]


@pytest.mark.parametrize("name", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_each_mix_calls_study_fidelity_once_with_its_fidelities(
        name, monkeypatch):
    from repro.api import Study
    calls = []
    real = Study.fidelity

    def record(self, *fids):
        calls.append(fids)
        return real(self, *fids)
    monkeypatch.setattr(Study, "fidelity", record)
    monkeypatch.setattr(Study, "run", lambda self, **kw: None)
    cell = _cell(name)
    cell.mix.pop("mesh", None)
    cell.mix["slots"] = cell.mix["slots"][:2]
    harness.Workload(cell, 3, ["a"]).run(0)
    fid = cell.mix["fidelity"]
    # a string mix makes the call it always made, with its one string
    assert calls == [(fid,) if isinstance(fid, str) else tuple(fid)]
    if name in DRAW_DIGESTS:
        assert isinstance(fid, str)
    if name == FLIP:
        assert calls == [("fast", "trace")]


def _frame(designs, fids, drop=0):
    from repro.api.study import StudyResult
    keys = [(f, d) for f in fids for d in range(designs)][drop:]
    cols = {"design": np.array([f"d{d}" for _, d in keys], object),
            "workload": np.array(["w"] * len(keys), object),
            "fidelity": np.array([f for f, _ in keys], object)}
    for c in ref.COLUMNS:
        cols[c] = np.ones(len(keys))
    cols["batched"] = np.ones(len(keys))
    cols["cell_status"] = np.zeros(len(keys))
    res = StudyResult(cols, {"design": [f"d{d}" for d in range(designs)],
                             "workload": ["w"], "fidelity": list(fids)})
    res.meta["engine"] = "xla"
    return res


def test_a_two_fidelity_mix_expects_a_cell_per_design_and_fidelity():
    wl = harness.Workload(_cell(FLIP), 3, ["a"])
    assert wl.fidelities == ("fast", "trace")
    assert wl.n_cells == 4
    picks = wl.picks(0)
    whole = _frame(2, wl.fidelities)
    attempted, bad, cells = harness.tally([(whole, picks)], wl.n_cells, "xla")
    assert (attempted, bad, len(cells)) == (4, 0, 4)
    # a Study that answered one fidelity only is half bad
    fast_only = _frame(2, ("fast",))
    attempted, bad, cells = harness.tally(
        [(whole, picks), (fast_only, picks)], wl.n_cells, "xla")
    assert (attempted, bad, len(cells)) == (8, 2, 6)
    assert check.count_bad(_frame(2, wl.fidelities, drop=1), 4, "xla") == 1
    # a one-fidelity mix still expects a cell per design
    one = harness.Workload(_cell("vitb-edp.search-rung"), 3, ["a"])
    assert one.n_cells == len(one.mix["slots"]) == 16


@pytest.mark.parametrize("seed", [0, 2**31 + 977, 2**33 + 5, -3])
def test_flip_mix_draws_the_designs_of_dataflow_dram_flip(seed):
    from repro.api.study import dataflow_dram_flip
    from repro.core import replay
    study = dataflow_dram_flip()
    cell = _cell(FLIP)
    want = [c.to_dict() for _, c in study._designs]
    assert [label for label, _ in study._designs] == ["ws", "os"]
    for k in (dz.WARMUP, 0, 1):
        got = dz.draw(cell.config, cell.mix, seed, k)
        assert got == want
        assert got[0]["memory"]["ifmap_sram_bytes"] == 139810
    wl = harness.Workload(cell, seed, ["a"])
    assert wl.fidelities == study._fidelities
    assert [(o.name, o.M, o.N, o.K, o.count) for o in wl.ops] == [
        (o.name, o.M, o.N, o.K, o.count)
        for o in study._workloads["resnet18-6"]]
    # the registered study's options are its defaults: the same ERT,
    # trace spec and engine as the configuration and mix state
    assert wl.ert == study._ert
    assert wl.spec == study._spec_for("trace")
    assert replay.resolve_engine(study._engine) == wl.engine


def test_whole_kib_sizes_give_the_bytes_they_always_gave():
    cfg = _cell("vitb-edp.search-rung").config
    slot = {"array": 32, "dataflow": "os", "channels": 2, "bw": 19.2,
            "layout_banks": 0}
    for kb in dz.sram_axis(cfg):
        d = dz.design(cfg, slot, kb)
        assert d["memory"]["ifmap_sram_bytes"] == kb * 1024 // 3
    assert dz.design(cfg, slot, 409.6)["memory"]["ofmap_sram_bytes"] == (
        int(0.4 * (1 << 20) / 3))


def test_each_row_is_held_to_the_reference_at_its_own_fidelity():
    cell = faults.tiny(FLIP, 2)
    wl = harness.Workload(cell, 2**31 + 77, jax.devices()[:1])
    frames = [(wl.run(k), wl.picks(k)) for k in range(2)]
    _, bad, cells = harness.tally(frames, wl.n_cells, wl.engine)
    assert bad == 0 and len(cells) == 8
    pick = check.draw_sample([r["total_cycles"] for _, r in cells], 8, 5)
    calls = []

    def counted(design, gemms, fidelity, cfg):
        calls.append(fidelity)
        return ref.cell_metrics(design, gemms, fidelity, cfg)
    values = {"bad_cells": bad,
              **check.readings(cells, pick, cell.config, counted)}
    checks, correct = check.judge(values, cell.limits)
    assert correct, checks
    # one reference per design and fidelity: the memo keys the fidelity
    assert sorted(calls) == ["fast", "fast", "trace", "trace"]

    def fast_for_all(design, gemms, fidelity, cfg):
        return ref.cell_metrics(design, gemms, "fast", cfg)
    values = {"bad_cells": bad,
              **check.readings(cells, pick, cell.config, fast_for_all)}
    checks, correct = check.judge(values, cell.limits)
    assert not correct, checks

