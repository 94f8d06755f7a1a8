"""The Study layer (repro.api.study): cross-product plan compilation,
batched execution parity, the columnar frame ops, serialization + cache,
and the named-study registry. The two paper studies' claims are covered
in tests/test_paper_claims.py on the same fixtures."""
import json

import numpy as np
import pytest

from repro.api import (Simulator, Study, StudyResult, get_study,
                       list_studies, preset_grid, register_study, studies)
from repro.core.workloads import Op
from repro.launch.mesh import auto_mesh

OPS_A = [Op("a", 256, 1024, 512), Op("b", 512, 197, 768, count=3.0),
         Op("v", kind="vector", vector_elems=8192.0, count=2.0)]
OPS_B = [Op("c", 128, 512, 256), Op("d", 384, 64, 384)]


# ---- plan + batched execution ---------------------------------------------

def test_cross_product_parity_with_simulator_loop():
    """designs x workloads x fidelity frame matches a python loop of
    `Simulator.run` per cell to <= 1e-3."""
    grid = preset_grid(array=[16, 32], sram_mb=[0.5, 2.0])
    res = (Study().designs(grid)
           .workloads({"wa": OPS_A, "wb": OPS_B})
           .fidelity("fast").run())
    assert len(res) == len(grid) * 2
    assert (res["batched"] == 1.0).all()
    for row_i in range(len(res)):
        row = res.row(row_i)
        # row order: workload-major, design fastest (one fidelity)
        cfg = grid[row_i % len(grid)]
        assert row["workload"] == ("wa" if row_i < len(grid) else "wb")
        rep = Simulator(cfg).run(OPS_A if row["workload"] == "wa" else OPS_B)
        assert row["total_cycles"] == pytest.approx(rep.total_cycles,
                                                    rel=1e-3)
        assert row["energy_pj"] == pytest.approx(rep.energy_pj, rel=1e-3)
        assert row["edp"] == pytest.approx(rep.edp, rel=1e-3)
        # grouped energy columns (shared schema) sum to the total
        groups = sum(row[g] for g in ("energy_mac_pj", "energy_sram_pj",
                                      "energy_dram_pj", "energy_static_pj"))
        assert groups == pytest.approx(row["energy_pj"], rel=1e-3)


def test_plan_batches_all_traceable_cells():
    """Acceptance: a designs x workloads x {fast, trace} study executes
    through the batched path — traceable cells never hit the per-cell
    python loop."""
    grid = preset_grid(array=[16, 32], dataflow=["ws", "os"])
    study = (Study().designs(grid)
             .workloads({"wa": OPS_A[:2], "wb": OPS_B})
             .fidelity("fast", "trace"))
    plan = study.plan()
    assert len(plan) == 4 * 2 * 2
    assert not plan.fallback and plan.n_batched == len(plan)
    # groups are keyed by (workload, fidelity, dataflow[, dram])
    assert all(len(g.cells) == 2 for g in plan.groups)
    res = study.run()
    assert (res["batched"] == 1.0).all()
    # trace rows exist and differ from fast rows (different stall model)
    tr, fa = res.filter(fidelity="trace"), res.filter(fidelity="fast")
    assert not np.allclose(tr["stall_cycles"], fa["stall_cycles"])


def test_sparse_cells_batch_and_oracle_stays_reachable():
    """ISSUE 5: sparse cells run through the vmapped kernel (batched ==
    1.0, matching the engine <= 1e-3); the per-op oracle is kept alive
    behind force_fallback for the differential parity suite."""
    from repro.core.accelerator import SparsityConfig
    grid = preset_grid(array=[16])
    sparse = grid[0].with_(sparsity=SparsityConfig(enabled=True, n=2, m=4))
    mk = lambda: (Study().designs({"dense": grid[0], "sparse": sparse})
                  .workloads({"wa": OPS_A[:2]}).fidelity("fast"))
    res = mk().run()
    assert res.fraction_batched == 1.0
    rep = Simulator(sparse).run(OPS_A[:2])
    assert res.filter(design="sparse")["total_cycles"][0] == \
        pytest.approx(rep.total_cycles, rel=1e-3)
    oracle = mk().options(force_fallback=True).run()
    assert oracle.fraction_batched == 0.0
    assert oracle.filter(design="sparse")["total_cycles"][0] == \
        pytest.approx(rep.total_cycles, rel=1e-6)
    # 'cycle' fidelity still runs per-op (no traced DRAM scan twin)
    plan = (Study().designs({"d": grid[0]}).workloads({"wa": OPS_A[:2]})
            .fidelity("cycle").plan())
    assert plan.fallback and not plan.groups


def test_sharded_vs_unsharded_equality():
    import jax
    mesh = auto_mesh((len(jax.devices()),), ("data",))
    grid = preset_grid(array=[8, 16, 32], sram_mb=[1.0])
    mk = lambda: (Study().designs(grid).workloads({"wa": OPS_A[:1]})
                  .fidelity("fast"))
    plain = mk().run()
    shard = mk().run(mesh=mesh)
    for k in ("total_cycles", "energy_pj", "stall_cycles", "utilization"):
        assert np.allclose(plain[k], shard[k], rtol=1e-6)


def test_trace_replay_blocks_do_not_change_results(monkeypatch):
    """The trace sweep replays its (design, op) streams in blocks that
    bound device memory; streams replay independently, so the block size
    (here 3 streams per `lax.map` step, with a padded last block) keeps
    every result within the replay's 1e-3 contract (XLA vectorizes a
    different batch, and the fixed point stops within `tol` cycles)."""
    from repro.api import simulator as sim
    grid = preset_grid(array=[16, 32], sram_mb=[0.5, 2.0])
    mk = lambda: (Study().designs(grid).workloads({"wa": OPS_A})
                  .fidelity("trace"))
    whole = mk().run()
    monkeypatch.setattr(sim, "_SWEEP_FN_CACHE", {})
    monkeypatch.setattr(sim, "_REPLAY_BLOCK_REQUESTS", 3 * 4096)
    blocked = mk().run()
    for k in ("total_cycles", "stall_cycles", "energy_pj"):
        np.testing.assert_allclose(blocked[k], whole[k], rtol=1e-3)


# ---- frame ops on a known 3-design fixture --------------------------------

@pytest.fixture()
def fixture_frame():
    cols = {
        "design": np.array(["a", "b", "c"], dtype=object),
        "workload": np.array(["w", "w", "w"], dtype=object),
        "fidelity": np.array(["fast", "fast", "fast"], dtype=object),
        # a: fast+hungry, b: balanced, c: slow+frugal; b best EdP,
        # all three pareto-optimal on (cycles, energy)
        "total_cycles": np.array([1e6, 2e6, 8e6]),
        "energy_pj": np.array([9e9, 2e9, 1e9]),
        "edp": np.array([9e6, 4e6, 8e6]),
        "batched": np.ones(3),
    }
    axes = {"design": ["a", "b", "c"], "workload": ["w"],
            "fidelity": ["fast"]}
    return StudyResult(cols, axes)


def test_best_argbest_aliases(fixture_frame):
    f = fixture_frame
    assert f.best("latency")["design"] == "a"
    assert f.best("energy")["design"] == "c"
    assert f.best("edp")["design"] == "b"
    assert f.argbest("edp") == 1
    by = f.best("edp", by="design")
    assert set(by) == {"a", "b", "c"} and by["a"]["edp"] == 9e6


def test_pareto_front(fixture_frame):
    front = fixture_frame.pareto("total_cycles", "energy_pj")
    assert sorted(front["design"]) == ["a", "b", "c"]
    # dominate c with a strictly-better row -> c drops off the front
    dominated = fixture_frame._subset(np.array([True, True, True]))
    dominated.columns["total_cycles"] = np.array([1e6, 2e6, 8e6])
    dominated.columns["energy_pj"] = np.array([9e9, 0.5e9, 1e9])
    assert sorted(dominated.pareto("total_cycles",
                                   "energy_pj")["design"]) == ["a", "b"]


def test_filter_group_compare(fixture_frame):
    f = fixture_frame
    assert len(f.filter(design="a")) == 1
    assert len(f.filter(design=["a", "c"])) == 2
    assert len(f.filter(lambda r: r["total_cycles"] < 3e6)) == 2
    assert set(f.group("design")) == {"a", "b", "c"}
    ratios = f.compare("total_cycles", axis="design", baseline="a")
    assert ratios["b"][0] == pytest.approx(2.0)
    assert ratios["c"][0] == pytest.approx(8.0)
    with pytest.raises(KeyError):
        f.compare("total_cycles", axis="design", baseline="zzz")


def test_topk_is_stable_sorted_and_nan_safe(fixture_frame):
    f = fixture_frame
    assert list(f.topk("edp", 2)["design"]) == ["b", "c"]
    # k past the frame clamps; result is sorted ascending
    top = f.topk("edp", 99)
    assert list(top["design"]) == ["b", "c", "a"]
    assert list(top["edp"]) == sorted(f["edp"])
    assert len(f.topk("edp", 0)) == 0
    with pytest.raises(ValueError):
        f.topk("edp", -1)
    # NaN rows (failed cells) never place, even with k >= len
    g = f._subset(np.array([True, True, True]))
    g.columns["edp"] = np.array([9e6, np.nan, 8e6])
    assert list(g.topk("edp", 3)["design"]) == ["c", "a"]
    # ties keep original row order (stable sort)
    h = f._subset(np.array([True, True, True]))
    h.columns["edp"] = np.array([5e6, 5e6, 1e6])
    assert list(h.topk("edp", 3)["design"]) == ["c", "a", "b"]


def test_concat_unions_columns_and_nan_fills(fixture_frame):
    other = StudyResult(
        {
            "design": np.array(["d"], dtype=object),
            "workload": np.array(["w"], dtype=object),
            "fidelity": np.array(["trace"], dtype=object),
            "total_cycles": np.array([4e6]),
            "energy_pj": np.array([3e9]),
            "edp": np.array([6e6]),
            # a metric fixture_frame does not have
            "dram_stall_cycles": np.array([1e5]),
        },
        {"design": ["d"], "workload": ["w"], "fidelity": ["trace"]},
        executed_cells=1, cache_hits=2)
    fixture_frame.executed_cells = 3
    cat = StudyResult.concat([fixture_frame, other])
    assert len(cat) == 4
    # column union in first-seen order, missing metrics NaN-filled
    assert cat.column_names()[:len(fixture_frame.column_names())] == \
        fixture_frame.column_names()
    assert "dram_stall_cycles" in cat.columns
    assert np.isnan(cat["dram_stall_cycles"][:3]).all()
    assert cat["dram_stall_cycles"][3] == 1e5
    # fixture_frame lacks "batched"? no — other lacks it: NaN-filled
    assert np.isnan(cat["batched"][3])
    # axis vocabularies merge first-seen
    assert cat.axes["design"] == ["a", "b", "c", "d"]
    assert cat.axes["fidelity"] == ["fast", "trace"]
    # accounting sums; claims/meta never propagate
    assert cat.executed_cells == 4 and cat.cache_hits == 2
    assert cat._claims == [] and cat.meta == {}
    # NaN-safe consumers ignore the fill
    assert cat.best("edp")["design"] == "b"
    with pytest.raises(ValueError):
        StudyResult.concat([])


def test_concat_checks_schema_version_and_axis_columns(fixture_frame):
    alien = fixture_frame._subset(np.array([True, False, False]))
    alien.schema_version = 999  # a frame from a foreign/future schema
    with pytest.raises(ValueError, match="schema_version"):
        StudyResult.concat([fixture_frame, alien])
    # axis columns must exist in every frame — no NaN fill for axes
    noaxis = StudyResult(
        {"design": np.array(["e"], dtype=object),
         "workload": np.array(["w"], dtype=object),
         "edp": np.array([1.0])},
        {"design": ["e"], "workload": ["w"]})
    with pytest.raises(ValueError, match="fidelity"):
        StudyResult.concat([fixture_frame, noaxis])


def test_concat_and_topk_roundtrip_csv_json(tmp_path, fixture_frame):
    other = fixture_frame._subset(np.array([True, True, False]))
    other.columns["design"] = np.array(["x", "y"], dtype=object)
    other.axes["design"] = ["x", "y"]
    other.columns["fidelity"] = np.array(["trace", "trace"], dtype=object)
    other.axes["fidelity"] = ["trace"]
    cat = StudyResult.concat([fixture_frame, other])
    assert cat.equals(StudyResult.from_json(cat.to_json()))
    p = tmp_path / "cat.csv"
    cat.to_csv(str(p))
    back = StudyResult.from_csv(str(p))
    for k in cat.columns:
        assert np.array_equal(back.columns[k], cat.columns[k]), k
    # NaN survives the trip too
    cat.columns["edp"][0] = np.nan
    cat.to_csv(str(p))
    nback = StudyResult.from_csv(str(p))
    assert np.isnan(nback["edp"][0])
    assert nback.equals(StudyResult.from_json(cat.to_json()))
    # and topk subframes serialize like any frame
    top = cat.topk("total_cycles", 2)
    assert top.equals(StudyResult.from_json(top.to_json()))


# ---- serialization + cache -------------------------------------------------

def test_csv_json_roundtrip_and_schema(tmp_path):
    res = (Study().designs(preset_grid(array=[16, 32]))
           .workloads({"wa": OPS_A[:2]}).fidelity("fast").run())
    # JSON round-trip carries the shared schema version
    d = json.loads(res.to_json())
    from repro.core.engine import RESULT_SCHEMA_VERSION
    assert d["schema_version"] == RESULT_SCHEMA_VERSION
    assert res.equals(StudyResult.from_json(res.to_json()))
    # CSV round-trip is lossless (repr floats via the shared writer)
    p = tmp_path / "frame.csv"
    res.to_csv(str(p))
    back = StudyResult.from_csv(str(p))
    for k in res.columns:
        assert np.array_equal(back.columns[k], res.columns[k]), k
    # a deserialized frame has no claims: claims_ok is loud, not True
    with pytest.raises(ValueError):
        back.claims_ok()
    # claims are scoped to the full frame — subframes don't carry them
    with pytest.raises(ValueError):
        res.filter(design=res.axes["design"][0]).claims_ok()
    # NetworkReport shares the version stamp and group columns
    rep = Simulator("paper-32").run(OPS_A[:2])
    rd = json.loads(rep.to_json())
    assert rd["schema_version"] == RESULT_SCHEMA_VERSION
    rep.write_csv(str(tmp_path / "rep.csv"))
    header = (tmp_path / "rep.csv").read_text().splitlines()[0].split(",")
    for g in ("energy_mac_pj", "energy_sram_pj", "energy_dram_pj",
              "energy_static_pj"):
        assert g in header and g in res.columns


def test_cache_hits_return_identical_frame(tmp_path):
    cache = str(tmp_path / "cells")
    mk = lambda: (Study("cached").designs(preset_grid(array=[16, 32]))
                  .workloads({"wa": OPS_A[:2]}).fidelity("fast")
                  .cache(cache))
    first = mk().run()
    assert first.executed_cells == 2 and first.cache_hits == 0
    import os
    mtimes = {f: os.path.getmtime(os.path.join(cache, f))
              for f in os.listdir(cache)}
    second = mk().run()
    assert second.executed_cells == 0 and second.cache_hits == 2
    assert first.equals(second)
    # pure hits must not rewrite the cache files
    assert mtimes == {f: os.path.getmtime(os.path.join(cache, f))
                      for f in os.listdir(cache)}
    # a changed cell (new design) re-executes only the new cell
    third = (Study("cached")
             .designs(preset_grid(array=[16, 32, 64]))
             .workloads({"wa": OPS_A[:2]}).fidelity("fast")
             .cache(cache).run())
    assert third.executed_cells == 1 and third.cache_hits == 2
    assert np.array_equal(third["total_cycles"][:2], first["total_cycles"])


# ---- named studies / registry ---------------------------------------------

def test_registry_and_namespace():
    assert {"edp_array_size", "dataflow_dram_flip",
            "multicore_contention"} <= set(list_studies())
    assert isinstance(get_study("edp_array_size", smoke=True), Study)
    with pytest.raises(KeyError):
        get_study("no-such-study")
    with pytest.raises(AttributeError):
        studies.no_such_study
    with pytest.raises(ValueError):
        register_study("edp_array_size")(lambda: None)


def test_contention_study_claims():
    """The multi-core contention study (custom evaluator over
    `simulate_multicore_contention`): shared DRAM never beats isolation
    and extra channels relieve the shared makespan."""
    from repro.trace import TraceSpec
    res = studies.multicore_contention(
        channels=(1, 4), gemm=(256, 512, 512),
        spec=TraceSpec(cap=1024)).run()
    assert res.claims_ok(), res.check_claims()
    assert (res["batched"] == 0.0).all()      # custom evaluator: per-cell
    assert "makespan_shared" in res.columns and "channels" in res.columns


def test_preset_grid_preset_and_dataflow_axes():
    grid = preset_grid(preset=["paper-32", "edge-8"],
                       dataflow=["ws", "os"])
    assert len(grid) == 4
    assert [(c.cores[0].rows, c.dataflow) for c in grid] == \
        [(32, "ws"), (32, "os"), (8, "ws"), (8, "os")]
    # factory kwargs still cross as before
    grid = preset_grid(array=[8, 16], sram_mb=[1.0], dataflow=["ws", "os"])
    assert len(grid) == 4 and grid[1].dataflow == "os"


def test_filter_predicate_on_empty_frame(fixture_frame):
    empty = fixture_frame.filter(design="nonexistent")
    assert len(empty) == 0
    assert len(empty.filter(lambda r: r["total_cycles"] < 1e6)) == 0


def test_csv_roundtrip_with_comma_in_label(tmp_path):
    res = (Study().designs({"a,b": "paper-32"})
           .workloads({"w,1": OPS_A[:1]}).fidelity("fast").run())
    p = tmp_path / "comma.csv"
    res.to_csv(str(p))
    back = StudyResult.from_csv(str(p))
    assert back["design"][0] == "a,b" and back["workload"][0] == "w,1"
    assert np.array_equal(back["total_cycles"], res["total_cycles"])


def test_run_cache_kwarg_does_not_stick(tmp_path):
    study = (Study().designs(preset_grid(array=[16]))
             .workloads({"w": OPS_A[:1]}).fidelity("fast"))
    study.run(cache=str(tmp_path / "once"))
    assert study._cache_dir is None
    again = study.run()                       # no cache dir -> no hits
    assert again.cache_hits == 0 and again.executed_cells == 1


def test_cache_tolerates_corrupt_and_truncated_files(tmp_path):
    """ISSUE 6 satellite: a torn/garbage cache file is a miss, never a
    crash — concurrent farm writers (and interrupted single-user runs)
    leave partial files behind on pre-atomic layouts."""
    import os
    cache = str(tmp_path / "cells")
    mk = lambda: (Study("robust").designs(preset_grid(array=[16, 32]))
                  .workloads({"wa": OPS_A[:2]}).fidelity("fast")
                  .cache(cache))
    first = mk().run()
    files = sorted(os.listdir(cache))
    assert files and not [f for f in files if ".tmp." in f], \
        "atomic store must not leave temp litter"
    # corrupt one cell every way a torn write or stray file could
    victim = os.path.join(cache, files[0])
    for garbage in ("", "{\"schema_version\":", "[1, 2, 3]", "null",
                    '{"schema_version": "v0-bogus", "metrics": {}}',
                    '{"metrics": "not-a-dict"}'):
        with open(victim, "w") as f:
            f.write(garbage)
        again = mk().run()
        # the corrupt cell re-executes (miss), the other still hits
        assert again.executed_cells == 1 and again.cache_hits == 1
        assert again.equals(first), garbage
    # the re-run healed the cache in place
    final = mk().run()
    assert final.executed_cells == 0 and final.cache_hits == 2


def test_cache_store_is_atomic_rename(tmp_path, monkeypatch):
    """_cache_store never exposes a partially-written file under the
    final name: the content appears via os.replace only."""
    import os
    seen = []
    real_replace = os.replace

    def spying_replace(src, dst):
        # at replace time the temp file is complete and parseable
        with open(src) as f:
            json.load(f)
        seen.append(os.path.basename(dst))
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", spying_replace)
    cache = str(tmp_path / "cells")
    (Study("atomic").designs(preset_grid(array=[16]))
     .workloads({"wa": OPS_A[:1]}).fidelity("fast").cache(cache).run())
    assert len(seen) == 1 and seen[0].endswith(".json")


def test_distinct_evaluators_never_share_cache(tmp_path):
    cache = str(tmp_path / "cells")

    def mk(fn):
        return (Study().designs(preset_grid(array=[16]))
                .workloads({"w": OPS_A[:1]}).fidelity("fast")
                .evaluator(fn).cache(cache))

    first = mk(lambda c, o, f: {"m": 1.0}).run()
    second = mk(lambda c, o, f: {"m": 2.0}).run()   # same qualname
    assert first.executed_cells == 1 and second.executed_cells == 1
    assert second["m"][0] == 2.0


def test_empty_sweep_still_returns_empty_result():
    res = Simulator().sweep([], OPS_A[:1])
    assert len(res) == 0 and res.batched
    assert res.total_cycles.shape == (0,)


def test_csv_writer_accepts_numpy_scalars(tmp_path):
    from repro.core.engine import write_csv_table
    p = tmp_path / "np.csv"
    write_csv_table(str(p), ["x"], [[np.float64(1.5)]])
    assert p.read_text().splitlines()[1] == "1.5"


def test_study_validation_errors():
    with pytest.raises(ValueError):
        Study().workloads({"w": OPS_A}).run()          # no designs
    with pytest.raises(ValueError):
        Study().designs(preset_grid(array=[16])).run()  # no workloads
    with pytest.raises(ValueError):
        Study().fidelity("nope")
    with pytest.raises(TypeError):
        Study().workloads(42)
    with pytest.raises(KeyError):
        (Study().designs(preset_grid(array=[16]))
         .workloads({"w": OPS_A[:1]}).metrics("not_a_metric").run())


# ---- failure semantics (ISSUE 8) ------------------------------------------

def test_evaluator_exception_degrades_to_failed_cell():
    """One sick cell must not poison the study: its row gets
    cell_status 1.0 + NaN metrics, the rest stay healthy."""
    def ev(cfg, ops, fid):
        if cfg.cores[0].rows == 16:
            raise RuntimeError("sick cell")
        return {"m": float(cfg.cores[0].rows), "edp": 1.0}

    res = (Study("sick").designs(preset_grid(array=[8, 16, 32]))
           .workloads({"w": OPS_B[:1]}).fidelity("fast")
           .evaluator(ev).run())
    assert len(res) == 3
    assert res.failed_cells == [1]
    assert res["cell_status"][1] == 1.0 and np.isnan(res["m"][1])
    ok = res.ok()
    assert len(ok) == 2 and (ok["cell_status"] == 0.0).all()
    assert res.argbest("m") == 0          # NaN row never wins
    assert res.best("m")["design"] == res["design"][0]


def test_batched_group_exception_recorded_in_meta(monkeypatch):
    """A batched group whose sweep raises (a device fault, a compiler
    refusal) degrades to failed cells AND keeps what it raised: the
    exception type and message land in meta["cell_errors"]."""
    import repro.api.study as study_mod

    def boom(*a, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")

    monkeypatch.setattr(study_mod, "_sweep_batched", boom)
    res = (Study("faulty").designs(preset_grid(array=[8, 16]))
           .workloads({"w": OPS_B[:1]}).fidelity("fast").run())
    assert res.failed_cells == [0, 1]
    errs = res.meta["cell_errors"]
    assert len(errs) == 1 and errs[0]["cells"] == [0, 1]
    assert errs[0]["error"] == ("RuntimeError: RESOURCE_EXHAUSTED: "
                                "out of device memory")
    assert errs[0]["group"].startswith("w/fast/")
    # a healthy study carries no error record
    monkeypatch.undo()
    ok = (Study("fine").designs(preset_grid(array=[8]))
          .workloads({"w": OPS_B[:1]}).fidelity("fast").run())
    assert "cell_errors" not in ok.meta


def test_non_finite_canonical_metrics_flag_cell_failed():
    """NaN anywhere fails a cell; Inf fails only canonical metric
    columns — a custom evaluator column may legitimately be Inf."""
    def ev(cfg, ops, fid):
        r = cfg.cores[0].rows
        if r == 8:
            return {"edp": float("nan")}
        if r == 16:
            return {"total_cycles": float("inf"), "edp": 1.0}
        return {"edp": 2.0, "stall_inflation": float("inf")}

    res = (Study("nonfinite").designs(preset_grid(array=[8, 16, 32]))
           .workloads({"w": OPS_B[:1]}).fidelity("fast")
           .evaluator(ev).run())
    assert res.failed_cells == [0, 1]
    assert res["cell_status"][2] == 0.0
    assert res["stall_inflation"][2] == float("inf")


def test_argbest_all_failed_raises_loudly():
    def ev(cfg, ops, fid):
        return {"m": float("nan")}
    res = (Study("allbad").designs(preset_grid(array=[8, 16]))
           .workloads({"w": OPS_B[:1]}).fidelity("fast")
           .evaluator(ev).run())
    assert res.failed_cells == [0, 1]
    with pytest.raises(ValueError, match="no finite"):
        res.argbest("m")


def test_pareto_excludes_failed_rows():
    """NaN compares false against everything: without the finite mask a
    failed cell would always survive as 'non-dominated'."""
    cols = {
        "design": np.array(["d0", "d1", "d2"], dtype=object),
        "workload": np.array(["w", "w", "w"], dtype=object),
        "fidelity": np.array(["fast"] * 3, dtype=object),
        "a": np.array([1.0, np.nan, 2.0]),
        "b": np.array([2.0, np.nan, 1.0]),
        "cell_status": np.array([0.0, 1.0, 0.0]),
    }
    res = StudyResult(cols, {"design": ["d0", "d1", "d2"],
                             "workload": ["w"], "fidelity": ["fast"]})
    front = res.pareto("a", "b")
    assert sorted(front["design"]) == ["d0", "d2"]


def test_failed_cells_never_cached(tmp_path):
    """A transient failure must re-execute next run — caching a failed
    cell would make it permanent."""
    cache = str(tmp_path / "cells")
    attempt = {"n": 0}

    def ev(cfg, ops, fid):
        if cfg.cores[0].rows == 16:
            attempt["n"] += 1
            if attempt["n"] == 1:
                raise RuntimeError("transient")
        return {"m": float(cfg.cores[0].rows)}

    mk = lambda: (Study("retry").designs(preset_grid(array=[8, 16, 32]))
                  .workloads({"w": OPS_B[:1]}).fidelity("fast")
                  .evaluator(ev).cache(cache))
    first = mk().run()
    assert first.failed_cells == [1] and first.executed_cells == 2
    second = mk().run()             # healthy cells hit, sick cell retries
    assert second.failed_cells == [] and not np.isnan(second["m"]).any()
    assert second.cache_hits == 2 and second.executed_cells == 1


def test_checkpoint_resume_after_midrun_crash(tmp_path):
    """Cells checkpoint to the cache as they complete: a run killed
    mid-study resumes from its last completed cell."""
    from repro.faults import InjectedCrash
    cache = str(tmp_path / "cells")
    calls = []

    def ev(cfg, ops, fid):
        calls.append(cfg.cores[0].rows)
        if len(calls) == 3:
            raise InjectedCrash("kill -9 mid-study")
        return {"m": float(cfg.cores[0].rows)}

    mk = lambda: (Study("ckpt").designs(preset_grid(array=[8, 16, 32, 64]))
                  .workloads({"w": OPS_B[:1]}).fidelity("fast")
                  .evaluator(ev).cache(cache))
    with pytest.raises(InjectedCrash):
        mk().run()
    assert len(calls) == 3          # two completed + the killed one
    res = mk().run()                # resumes: only 2 cells re-execute
    assert res.cache_hits == 2 and res.executed_cells == 2
    assert res.failed_cells == []
    assert list(res["m"]) == [8.0, 16.0, 32.0, 64.0]
