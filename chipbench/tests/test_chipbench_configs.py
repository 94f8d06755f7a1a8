"""The benchmark's files: found by name, and the copied inputs equal what
the program's own workload builders give today."""
import json
import os
import re
import shutil

import pytest

from chipbench import harness, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_gemm_lists_match_the_program_builders():
    from repro.core.workloads import vit_linear
    ops = vit_linear(768, 12, 3072, prefix="vitb")
    with open(os.path.join(spec.HERE, "configs", "vitb-edp.json")) as f:
        cfg = json.load(f)
    assert [tuple(g) for g in cfg["gemms"]] == [
        (o.name, o.M, o.N, o.K, o.count) for o in ops]
    assert all(o.kind == "gemm" and o.sparsity_nm is None for o in ops)


def test_design_template_is_the_table_v_corner_preset():
    from repro.api.presets import get_preset
    with open(os.path.join(spec.HERE, "configs", "vitb-edp.json")) as f:
        cfg = json.load(f)
    assert cfg["design_template"] == get_preset("table-v-corner").to_dict()


def _config(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet18_dram_is_the_registered_flip_studys_input():
    from dataclasses import asdict
    from repro.core.accelerator import tpu_like_config
    from repro.core.energy import ERT
    from repro.core.workloads import resnet18, resnet18_six_layers
    from repro.trace.generator import TraceSpec
    cfg = _config("resnet18-dram")
    ops = resnet18_six_layers()
    assert [tuple(g) for g in cfg["gemms"]] == [
        (o.name, o.M, o.N, o.K, o.count) for o in ops]
    assert all(o.kind == "gemm" and o.sparsity_nm is None for o in ops)
    # the depth is the one cut: the first 6 of ResNet-18's 21 GEMMs
    assert cfg["reduced"] == ["num_gemms"]
    assert cfg["num_gemms"] == len(cfg["gemms"]) == 6
    assert cfg["source_num_gemms"] == len(resnet18()) == 21
    assert cfg["design_template"] == tpu_like_config(
        array=32, dataflow="ws", sram_mb=0.4).to_dict()
    assert cfg["design_template"]["memory"]["ifmap_sram_bytes"] == 139810
    assert cfg["ert"] == asdict(ERT())
    assert cfg["trace_spec"] == asdict(TraceSpec())
    assert cfg["precision"] == "float32"


def test_design_template_builds_a_config_with_explicit_fields(bench):
    from repro.core.accelerator import AcceleratorConfig
    for c in bench["configs"]:
        with open(c["file"]) as f:
            cfg = json.load(f)
        tmpl = cfg["design_template"]
        # every field written out: the config round-trips without defaults
        assert AcceleratorConfig.from_dict(tmpl).to_dict() == tmpl


def test_every_cell_finds_its_files_by_name(bench):
    for w in bench["workloads"]:
        cell = spec.find_cell(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert set(cell.limits) >= {"bad_cells", "analytic_err",
                                    "cycles_err"}
        fids = harness.fidelities(cell.mix)
        assert fids and set(fids) <= {"fast", "trace"}
        assert len(set(fids)) == len(fids)
        for m in cell.per_layer:
            assert callable(spec.reader(m["name"]))


@pytest.mark.parametrize("key", ["configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_benchmark_names_and_units_use_allowed_characters(bench, key):
    names = [e["name"] for e in bench[key]]
    assert len(names) == len(set(names))
    for e in bench[key]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200, e[k]
                assert "\n" not in e[k] and "\t" not in e[k]


def test_a_new_config_mix_metric_and_cell_need_no_edit(tmp_path, bench):
    base = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, base, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (base / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "gemms": [["g", 8, 8, 8, 1.0]]}))
    (base / "traffic" / "one.json").write_text(json.dumps(
        {"fidelity": "fast", "engine": "xla", "sram_kb_pool": [1024],
         "slots": [{"array": 8, "dataflow": "ws", "channels": 1,
                    "bw": 9.6, "layout_banks": 0}]}))
    (base / "limits" / "tiny.one.json").write_text(json.dumps(
        {"bad_cells": 0, "analytic_err": 1e-5, "cycles_err": 1e-5}))
    (base / "metrics" / "new_metric.py").write_text(
        "def read(record):\n    return record['cells'] * 2\n")
    grown = dict(bench)
    grown["workloads"] = bench["workloads"] + [
        {"name": "tiny.one", "config": "tiny", "traffic": "one", "chips": 1,
         "why": "test"}]
    grown["per_layer"] = bench["per_layer"] + [
        {"name": "new_metric", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "device",
         "moves": "cells_per_s", "workloads": ["tiny.one"]}]
    cell = spec.find_cell(grown, "tiny.one", base=str(base))
    assert cell.config["gemms"] == [["g", 8, 8, 8, 1.0]]
    assert cell.mix["slots"][0]["array"] == 8
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert spec.reader("new_metric", base=str(base))({"cells": 3}) == 6
    # the metric is listed for the new cell only
    old = spec.find_cell(grown, bench["workloads"][0]["name"], base=str(base))
    assert "new_metric" not in [m["name"] for m in old.per_layer]
