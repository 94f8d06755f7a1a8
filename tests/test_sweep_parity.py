"""Differential parity + full-coverage batching (ISSUE 5 tentpole).

Randomized design grids mixing dense / layer-wise N:M / row-wise N:M
sparsity, data-layout modeling and multi-core partitioning must (a) run
entirely through the batched jit+vmap sweep kernels
(`fraction_batched == 1.0`) and (b) agree with the per-op engine oracle —
kept alive behind `force_fallback=` purely for this suite — to <= 1e-3
per metric column. Cache hits must replay bit-identical frames.
"""
import numpy as np
import pytest

from repro.api import Simulator, Study, preset_grid
from repro.api.presets import as_sparsity, get_preset, with_cores
from repro.core.accelerator import LayoutConfig, SparsityConfig
from repro.core.workloads import Op

PARITY_COLUMNS = ("total_cycles", "compute_cycles", "stall_cycles",
                  "dram_bytes", "energy_pj", "utilization", "edp",
                  "energy_mac_pj", "energy_sram_pj", "energy_dram_pj",
                  "energy_static_pj")

# the last gemm carries a per-op N:M override (exercises
# stages.resolve_sparsity in both paths); (1, 4) stays legal when the
# design's SparsityConfig is row-wise (N <= M/2)
OPS = [Op("a", 256, 1024, 512), Op("b", 512, 197, 768, count=3.0),
       Op("v", kind="vector", vector_elems=8192.0, count=2.0),
       Op("c", 384, 256, 1024, sparsity_nm=(1, 4))]

SPARSITIES = (None, "2:4", "1:4", "2:8", "1:4-rw", "2:8-rw")


def _mixed_designs(seed: int, n: int, arrays=(8, 16, 32),
                   core_counts=(1, 4)):
    rng = np.random.default_rng(seed)
    out = {}
    for i in range(n):
        cfg = get_preset("tpu-like", array=int(rng.choice(arrays)),
                         sram_mb=float(rng.choice([0.25, 1.0])))
        cfg = cfg.with_(dataflow=str(rng.choice(["ws", "os", "is"])))
        cores = int(rng.choice(core_counts))
        if cores > 1:
            cfg = with_cores(cfg, cores)
        sp = SPARSITIES[int(rng.integers(len(SPARSITIES)))]
        if sp is not None:
            cfg = cfg.with_(sparsity=as_sparsity(sp))
        if rng.random() < 0.5:
            cfg = cfg.with_(layout=LayoutConfig(enabled=True))
        out[f"d{i}-{cores}c-{sp}"] = cfg
    return out


def _assert_parity(batched, oracle, columns=PARITY_COLUMNS, tol=1e-3):
    assert len(batched) == len(oracle)
    for col in columns:
        a = np.asarray(batched[col], float)
        b = np.asarray(oracle[col], float)
        rel = np.abs(a - b) / np.maximum(np.abs(b), 1.0)
        i = int(rel.argmax()) if len(rel) else 0
        assert rel.max(initial=0.0) <= tol, \
            (col, batched.row(i)["design"], a[i], b[i], float(rel.max()))


@pytest.mark.parametrize("seed", [0, 1])
def test_randomized_mixed_grid_parity_fast(seed):
    designs = _mixed_designs(seed, n=14)
    mk = lambda: (Study().designs(designs)
                  .workloads({"w": OPS, "w2": OPS[:2]}).fidelity("fast"))
    res = mk().run()
    assert res.fraction_batched == 1.0
    oracle = mk().options(force_fallback=True).run()
    assert oracle.fraction_batched == 0.0
    _assert_parity(res, oracle)


def test_randomized_mixed_grid_parity_trace():
    from repro.trace import TraceSpec
    designs = _mixed_designs(7, n=6, arrays=(16, 32))
    spec = TraceSpec(cap=1024)
    mk = lambda: (Study().designs(designs).workloads({"w": OPS[:2]})
                  .fidelity("trace").options(trace_spec=spec))
    res = mk().run()
    assert res.fraction_batched == 1.0
    oracle = mk().options(force_fallback=True).run()
    _assert_parity(res, oracle)
    # the generated-trace stalls genuinely differ from the fast model
    fast = (Study().designs(designs).workloads({"w": OPS[:2]})
            .fidelity("fast").run())
    assert not np.allclose(res["stall_cycles"], fast["stall_cycles"])


def test_acceptance_grid_dense_sparse_cores_layout():
    """The ISSUE 5 acceptance grid: {dense, 2:4 layer-wise, row-wise} x
    {1, 4} cores x layout on/off — fraction_batched == 1.0 from Study,
    batched metrics match the per-op oracle <= 1e-3."""
    grid = preset_grid(array=[32], sparsity=[None, "2:4", "1:4-rw"],
                       cores=[1, 4])
    designs = {}
    for i, c in enumerate(grid):
        for lay in (False, True):
            designs[f"g{i}{'-lay' if lay else ''}"] = c.with_(
                layout=LayoutConfig(enabled=lay))
    assert len(designs) == 12
    mk = lambda: Study().designs(designs).workloads({"w": OPS}) \
                        .fidelity("fast")
    res = mk().run()
    assert res.fraction_batched == 1.0
    _assert_parity(res, mk().options(force_fallback=True).run())


def test_cache_hits_bit_identical_on_mixed_grid(tmp_path):
    designs = _mixed_designs(3, n=6)
    cache = str(tmp_path / "cells")
    mk = lambda: (Study("parity-cache").designs(designs)
                  .workloads({"w": OPS[:2]}).fidelity("fast").cache(cache))
    first = mk().run()
    second = mk().run()
    assert second.cache_hits == len(first) and second.executed_cells == 0
    assert first.equals(second)            # bit-identical, every column
    # the oracle never aliases batched cells in the cache
    oracle = mk().options(force_fallback=True).run()
    assert oracle.cache_hits == 0


def test_sweep_facade_mixed_grid_fraction_batched():
    grid = preset_grid(array=[16], sparsity=[None, "2:4"], cores=[1, 4])
    res = Simulator().sweep(grid, OPS[:2])
    assert res.batched and len(res) == 4
    oracle = Simulator().sweep(grid, OPS[:2], force_fallback=True)
    assert not oracle.batched
    rel = np.abs(res.total_cycles - oracle.total_cycles) \
        / np.maximum(oracle.total_cycles, 1.0)
    assert rel.max() <= 1e-3


def test_invalid_per_op_override_raises_in_both_paths():
    """An Op.sparsity_nm override that cannot form a valid SparsityConfig
    with a design's row_wise flag must raise in the batched path exactly
    like the per-op oracle (no silent wrong answers)."""
    cfg = get_preset("tpu-like", array=16).with_(
        sparsity=as_sparsity("2:8-rw"))
    ops = [Op("g", 128, 128, 256, sparsity_nm=(3, 4))]   # 3 > 4//2
    mk = lambda **kw: (Study().designs({"d": cfg}).workloads({"w": ops})
                       .fidelity("fast").options(**kw))
    with pytest.raises(ValueError):
        mk().run()
    with pytest.raises(ValueError):
        mk(force_fallback=True).run()


def test_sparse_speedup_study_claims():
    from repro.api import studies
    res = studies.sparse_speedup(smoke=True).run()
    assert res.claims_ok(), res.check_claims()
    assert res.fraction_batched == 1.0


# ---- the layout stage's per-program curve table ----------------------------

def _screen_like_designs(n: int, banks: int = 32, seed: int = 0):
    """`n` designs of one layout program of the search's fast screen:
    arrays 32/64/128 in turn, one bank count, SRAM drawn from the Table V
    axis (512 KiB up: 16 KiB a bank holds for 32 banks)."""
    from repro.search.space import SearchPoint
    from repro.search.studies import table_v_space
    space = table_v_space()
    ax = {a.name: a for a in space.axes}
    srams = np.random.default_rng(seed).choice(
        len(ax["sram_kb"].values), size=n, replace=False)
    designs = {}
    for i, s in enumerate(srams):
        vals = dict(array=i % 3, sram_kb=int(s), dataflow=0, channels=0,
                    bw=0, layout_banks=ax["layout_banks"].values.index(banks))
        p = SearchPoint(tuple(vals[a.name] for a in space.axes))
        assert space.is_valid(p)
        designs[space.label(p)] = space.config(p)
    return designs


def test_screen_layout_program_equals_one_design_per_group():
    """A screen-like fast Study (ViT-base's 48 GEMMs, arrays 32/64/128 in
    one layout program: 3 curves) gives, column for column and exactly,
    the frame of its designs run one design per program."""
    from repro.core.workloads import vit_base_linear
    designs = _screen_like_designs(60)
    ops = vit_base_linear()
    assert len(ops) == 48
    study = Study().designs(designs).workloads({"vit": ops}).fidelity("fast")
    assert len(study.plan().groups) == 1
    res = study.run()
    assert res.fraction_batched == 1.0 and not res.failed_cells
    single = [Study().designs({k: c}).workloads({"vit": ops})
              .fidelity("fast").run() for k, c in designs.items()]
    for col in PARITY_COLUMNS + ("batched",):
        one = np.concatenate([s[col] for s in single])
        assert np.array_equal(res[col], one), col


def test_layout_program_on_a_four_device_mesh_equals_unsharded():
    """A layout group of 10 designs (not a multiple of 4, so the mesh pads
    the `lay_row` design column like every other) gives the same frame on
    a 4-device mesh as without one (8 forced host devices, in a
    subprocess: the device count locks at the first jax init)."""
    import os
    import subprocess
    import sys
    import textwrap
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.api import Study
        from repro.core.workloads import vit_linear
        from repro.launch.mesh import auto_mesh
        from tests.test_sweep_parity import PARITY_COLUMNS, _screen_like_designs
        mesh = auto_mesh((4,), ("data",), devices=jax.devices()[:4])
        mk = lambda: (Study().designs(_screen_like_designs(10, banks=16))
                      .workloads({"vit": vit_linear(768, 2, 3072)})
                      .fidelity("fast"))
        assert len(mk().plan().groups) == 1
        plain, shard = mk().run(), mk().run(mesh=mesh)
        assert plain.fraction_batched == shard.fraction_batched == 1.0
        for col in PARITY_COLUMNS:
            assert np.array_equal(plain[col], shard[col]), col
        print("mesh layout frame matches")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join([os.path.join(root, "src"), root]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560, cwd=root)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "mesh layout frame matches" in out.stdout
