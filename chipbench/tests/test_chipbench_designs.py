"""The seeded draw of a Study's designs."""
import json
import os
from collections import Counter

import pytest

from chipbench import designs as dz
from chipbench import spec

SEEDS = [0, 1, 7, 12345, 2**31 - 1, 2**31 + 977, 2**33 + 5, -3,
         987654321, 42, 31337, 2**32]


def _load(name, kind):
    with open(os.path.join(spec.HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _load("vitb-edp", "configs")


@pytest.fixture(scope="module")
def mix():
    return _load("search-rung", "traffic")


def _shapes(designs):
    """What fixes the sweep programs' shapes: per flavor, the designs,
    the distinct demand streams and the largest array row count."""
    out = {}
    for d in designs:
        key = (d["dataflow"], d["dram"]["channels"],
               d["dram"]["bandwidth_bytes_per_cycle"],
               d["layout"]["enabled"] and d["layout"]["num_banks"])
        n, streams, rows = out.get(key, (0, set(), 0))
        streams.add((d["cores"][0]["rows"], d["memory"]["ifmap_sram_bytes"]))
        out[key] = (n + 1, streams, max(rows, d["cores"][0]["rows"]))
    return {k: (n, len(s), r) for k, (n, s, r) in out.items()}


def test_config_space_is_the_search_space_of_search_edp(cfg):
    from repro.search.studies import table_v_space
    axes = {a.name: list(a.values) for a in table_v_space().axes}
    sp = cfg["design_space"]
    assert axes["array"] == sp["array"]
    assert axes["sram_kb"] == dz.sram_axis(cfg)
    assert axes["dataflow"] == sp["dataflows"]
    assert axes["channels"] == sp["dram_channels"]
    assert axes["bw"] == sp["dram_bandwidth_bytes_per_cycle"]
    assert axes["layout_banks"] == sp["layout_banks"]


def test_drawn_designs_are_the_configs_of_search_edp(cfg, mix):
    from repro.search.space import SearchPoint
    from repro.search.studies import table_v_space
    space = table_v_space()
    axes = [list(a.values) for a in space.axes]
    for d, s in zip(dz.draw(cfg, mix, 2**31 + 5, 0), mix["slots"]):
        third = d["memory"]["ifmap_sram_bytes"]
        kb = next(v for v in mix["sram_kb_pool"] if v * 1024 // 3 == third)
        vals = [s["array"], kb, s["dataflow"], s["channels"], s["bw"],
                s["layout_banks"]]
        p = SearchPoint(tuple(ax.index(v) for ax, v in zip(axes, vals)))
        assert space.is_valid(p)
        assert space.config(p).to_dict() == d


def test_mix_lies_in_the_space(cfg, mix):
    sp = cfg["design_space"]
    assert set(mix["sram_kb_pool"]) <= set(dz.sram_axis(cfg))
    for s in mix["slots"]:
        assert s["array"] in sp["array"]
        assert s["dataflow"] in sp["dataflows"]
        assert s["channels"] in sp["dram_channels"]
        assert s["bw"] in sp["dram_bandwidth_bytes_per_cycle"]
        assert s["layout_banks"] in sp["layout_banks"]
        assert min(mix["sram_kb_pool"]) >= 16 * s["layout_banks"]
    # each flavor draws its slots' SRAM sizes without replacement
    assert max(Counter(map(dz.flavor, mix["slots"])).values()) <= len(
        mix["sram_kb_pool"])


def test_draw_is_deterministic_and_its_shapes_do_not_depend_on_seed(
        cfg, mix):
    shapes = set()
    for seed in SEEDS:
        for k in (dz.WARMUP, 0, 1):
            a = dz.draw(cfg, mix, seed, k)
            assert a == dz.draw(cfg, mix, seed, k)
            assert len(a) == len(mix["slots"])
            shapes.add(tuple(sorted(_shapes(a).items())))
    assert len(shapes) == 1
    # every design of a flavor has a demand stream of its own
    assert all(n == s for n, s, _ in _shapes(a).values())


def test_studies_and_seeds_draw_different_designs(cfg, mix):
    assert dz.draw(cfg, mix, 5, 0) != dz.draw(cfg, mix, 5, 1)
    assert dz.draw(cfg, mix, 5, 0) != dz.draw(cfg, mix, 6, 0)
