"""The unified `repro.api` surface: Simulator facade parity with the
engine, config serde + presets, and the batched sweep path."""
import json

import numpy as np
import pytest

from repro.api import (Simulator, as_config, get_preset, list_presets,
                       preset_grid, register_preset)
from repro.core import (AcceleratorConfig, simulate_network, simulate_op,
                        tpu_like_config)
from repro.core.accelerator import LayoutConfig, SparsityConfig
from repro.core.workloads import Op, resnet18
from repro.launch.mesh import auto_mesh


# ---- facade parity ---------------------------------------------------------

def test_simulator_fast_matches_engine():
    wl = resnet18()
    rep = Simulator("paper-32").run(wl)
    old = simulate_network(tpu_like_config(array=32), wl)
    assert rep.total_cycles == pytest.approx(old.total_cycles)
    assert rep.energy_pj == pytest.approx(old.energy_pj)
    assert rep.stall_cycles == pytest.approx(old.stall_cycles)
    assert [o.total_cycles for o in rep.ops] == \
        pytest.approx([o.total_cycles for o in old.ops])


def test_simulator_cycle_matches_engine():
    wl = resnet18()[:2]
    rep = Simulator("paper-32", fidelity="cycle").run(wl)
    old = simulate_network(tpu_like_config(array=32), wl,
                           dram_fidelity="cycle")
    assert rep.total_cycles == pytest.approx(old.total_cycles)
    assert rep.ops[0].dram_stats is not None


def test_simulator_feature_configs_compose():
    sp = Simulator("paper-32").with_(
        sparsity=SparsityConfig(enabled=True, n=2, m=4))
    lay = Simulator("paper-32").with_(layout=LayoutConfig(enabled=True))
    base = Simulator("paper-32").run(resnet18()[:3])
    assert sp.run(resnet18()[:3]).compute_cycles < base.compute_cycles
    assert lay.run(resnet18()[:3]).total_cycles >= base.total_cycles


def test_workload_by_name_and_stage_names():
    sim = Simulator("paper-32")
    assert sim.run("resnet18").total_cycles > 0
    names = sim.stage_names()
    assert names[0] == "mapping" and names[-1] == "energy"
    assert "dram[fast]" in names
    assert "dram[cycle]" in Simulator(fidelity="cycle").stage_names()
    with pytest.raises(ValueError):
        Simulator(fidelity="nope")
    with pytest.raises(KeyError):
        sim.run("not_a_workload")


# ---- config serde + presets ------------------------------------------------

def test_config_dict_roundtrip_json_safe():
    for name in ("paper-32", "multicore-16x32", "edge-8"):
        cfg = get_preset(name)
        d = json.loads(json.dumps(cfg.to_dict()))   # through real JSON
        assert AcceleratorConfig.from_dict(d) == cfg


def test_from_dict_partial_and_as_config():
    cfg = AcceleratorConfig.from_dict(
        {"dataflow": "os", "cores": [{"rows": 16, "cols": 16}]})
    assert cfg.dataflow == "os" and cfg.cores[0].num_pes == 256
    assert as_config("paper-64").cores[0].rows == 64
    assert as_config(cfg) is cfg
    assert as_config(cfg.to_dict()) == cfg
    with pytest.raises(TypeError):
        as_config(42)


def test_preset_registry():
    assert {"paper-32", "tpu-like", "edge-8"} <= set(list_presets())
    assert get_preset("tpu-like", array=8).cores[0].rows == 8
    with pytest.raises(KeyError):
        get_preset("no-such-accelerator")
    with pytest.raises(ValueError):
        register_preset("paper-32")(lambda: None)
    grid = preset_grid(array=[8, 16], sram_mb=[1.0, 2.0])
    assert len(grid) == 4 and grid[0].cores[0].rows == 8


# ---- batched sweep ---------------------------------------------------------

OPS = [Op("a", 256, 1024, 512), Op("b", 512, 197, 768, count=3.0),
       Op("v", kind="vector", vector_elems=8192.0, count=2.0)]


def test_sweep_smoke_2x2_grid():
    grid = preset_grid(array=[16, 32], sram_mb=[0.5, 2.0])
    res = Simulator().sweep(grid, OPS)
    assert res.batched and len(res) == 4
    for i, cfg in enumerate(grid):
        rep = simulate_network(cfg, OPS)
        assert res.total_cycles[i] == pytest.approx(rep.total_cycles,
                                                    rel=1e-3)
        assert res.energy_pj[i] == pytest.approx(rep.energy_pj, rel=1e-3)
        assert res.dram_bytes[i] == pytest.approx(rep.dram_bytes, rel=1e-3)
        assert res.utilization[i] == pytest.approx(rep.utilization,
                                                   rel=1e-3, abs=1e-6)
    assert res.edp.shape == (4,)
    assert res.best("latency") is grid[res.argbest("latency")]


def test_sweep_64_points_single_batched_call():
    """Acceptance: a >= 64-point grid in one vmapped call, per-point results
    within 1e-3 of loop-of-simulate_op."""
    grid = preset_grid(array=[8, 16, 32, 64],
                       sram_mb=[0.25, 0.5, 1.0, 4.0],
                       dataflow=["ws", "os", "is", "ws"])
    assert len(grid) == 64
    res = Simulator().sweep(grid, OPS)
    assert res.batched
    for i in (0, 7, 21, 42, 63):
        rep = simulate_network(grid[i], OPS)
        assert res.total_cycles[i] == pytest.approx(rep.total_cycles,
                                                    rel=1e-3)
        assert res.energy_pj[i] == pytest.approx(rep.energy_pj, rel=1e-3)


def test_sweep_trace_fidelity_batched():
    """ISSUE 2 acceptance: trace-fidelity points run through the batched
    (vmapped) path for traceable configs — no per-op Python fallback —
    and match the per-op engine."""
    grid = preset_grid(array=[16, 32], sram_mb=[0.5, 2.0])
    res = Simulator(fidelity="trace").sweep(grid, OPS[:2])
    assert res.batched and len(res) == 4
    for i in (0, 3):
        rep = simulate_network(grid[i], OPS[:2], dram_fidelity="trace")
        assert res.total_cycles[i] == pytest.approx(rep.total_cycles,
                                                    rel=1e-3)
        assert res.stall_cycles[i] == pytest.approx(rep.stall_cycles,
                                                    rel=1e-3, abs=1.0)
    # generated-trace stalls differ from the first-order model
    fast = Simulator(fidelity="fast").sweep(grid, OPS[:2])
    assert not np.allclose(res.stall_cycles, fast.stall_cycles)


def test_core_index_selects_heterogeneous_core():
    """The facade models the selected core's geometry in every
    core-dependent stage — not a silent cores[0] mix. (Compute cycles
    are partition-stage territory on a multi-core mesh; SRAM and DRAM
    traffic expose the per-core geometry directly.)"""
    from repro.core.accelerator import CoreConfig, MemoryConfig
    from repro.core.stages import CoreStage
    cfg = AcceleratorConfig(
        cores=(CoreConfig(rows=32, cols=32), CoreConfig(rows=8, cols=8)),
        mesh_rows=2, mesh_cols=1,
        memory=MemoryConfig(ifmap_sram_bytes=1 << 13,
                            filter_sram_bytes=1 << 13,
                            ofmap_sram_bytes=1 << 13))
    op = Op("g", 256, 256, 256)
    sim1 = Simulator(cfg, core_index=1)
    assert all(s.core_index == 1 for s in sim1.pipeline
               if isinstance(s, CoreStage))
    r0 = Simulator(cfg, core_index=0).run_op(op)
    r1 = sim1.run_op(op)
    assert r0.dram_bytes != r1.dram_bytes
    assert r0.sram_reads != r1.sram_reads


def test_trace_stage_names_and_spec():
    sim = Simulator("paper-32", fidelity="trace")
    assert "dram[trace]" in sim.stage_names()
    assert sim.trace_spec is not None
    assert sim.with_(dataflow="os").trace_spec == sim.trace_spec


def test_sweep_mixed_grid_batches_sparse_cells():
    """ISSUE 5: sparsity no longer ejects a cell from the batched path —
    a mixed dense/sparse grid sweeps fully vmapped and matches the
    per-op engine; the oracle stays reachable behind force_fallback."""
    grid = preset_grid(array=[16, 32])
    sparse = grid[0].with_(sparsity=SparsityConfig(enabled=True, n=2, m=4))
    res = Simulator().sweep(grid + [sparse], OPS[:2])
    assert res.batched
    rep = simulate_network(sparse, OPS[:2])
    assert res.total_cycles[2] == pytest.approx(rep.total_cycles, rel=1e-3)
    assert res.total_cycles[2] < res.total_cycles[0]
    oracle = Simulator().sweep(grid + [sparse], OPS[:2],
                               force_fallback=True)
    assert not oracle.batched
    assert oracle.total_cycles[2] == pytest.approx(rep.total_cycles,
                                                   rel=1e-6)


def test_sweep_sharded_over_host_mesh():
    import jax
    mesh = auto_mesh((len(jax.devices()),), ("data",))
    grid = preset_grid(array=[8, 16, 32], sram_mb=[1.0])   # pads to size
    res = Simulator().sweep(grid, OPS[:1], mesh=mesh)
    rep = simulate_network(grid[1], OPS[:1])
    assert res.total_cycles[1] == pytest.approx(rep.total_cycles, rel=1e-3)


# ---- energy breakdown (NetworkReport contract) -----------------------------

def test_energy_breakdown_populated_and_in_csv(tmp_path):
    rep = Simulator("paper-32").run(resnet18()[:4])
    assert rep.energy_breakdown                       # non-empty
    assert sum(rep.energy_breakdown.values()) == \
        pytest.approx(rep.energy_pj, rel=1e-6)
    assert all(v >= 0 for v in rep.energy_breakdown.values())
    p = tmp_path / "rep.csv"
    rep.write_csv(str(p))
    header, first = p.read_text().splitlines()[:2]
    assert "energy_mac_pj" in header and "energy_dram_pj" in header
    row = dict(zip(header.split(","), first.split(",")))
    groups = sum(float(row[k]) for k in ("energy_mac_pj", "energy_sram_pj",
                                         "energy_dram_pj",
                                         "energy_static_pj"))
    assert groups == pytest.approx(float(row["energy_pj"]), rel=1e-3)
