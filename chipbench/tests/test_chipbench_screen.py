"""The `search-screen` mix: its slots are the fast screen of the seed-0
search, and its seeded draw gives every Study the same sweep programs
with valid designs; the `search-rung-mesh4` mix is `search-rung` on a
mesh."""
import json
import os
from collections import Counter

import pytest

from chipbench import designs as dz
from chipbench import spec

SEEDS = [0, 7, 2**31 - 1, 2**31 + 977, 2**33 + 5, -3]


def _load(name, kind):
    with open(os.path.join(spec.HERE, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _load("vitb-edp", "configs")


@pytest.fixture(scope="module")
def mix():
    return _load("search-screen", "traffic")


def _programs(designs):
    """Designs per fast sweep program (dataflow, layout banks), with the
    largest array of each (which sizes the layout stage), and designs
    per draw flavor."""
    prog, flav = {}, Counter()
    for d in designs:
        banks = d["layout"]["enabled"] and d["layout"]["num_banks"]
        key = (d["dataflow"], banks)
        n, rows = prog.get(key, (0, 0))
        prog[key] = (n + 1, max(rows, d["cores"][0]["rows"]))
        flav[(d["dataflow"], d["dram"]["channels"],
              d["dram"]["bandwidth_bytes_per_cycle"], banks)] += 1
    return sorted(prog.items()), sorted(flav.items())


def test_slots_are_the_seed_0_screen_of_search_edp(mix):
    from chipbench.search_rungs import SLOT_AXES, screen_slots
    from repro.search.studies import table_v_space
    assert mix["fidelity"] == "fast" and len(mix["slots"]) == 768
    assert mix["slots"] == screen_slots(0, 768)
    # the search's screen is the space's first seeded sample, and the
    # smoke search's 768 are the full search's first 768 of 1536
    space = table_v_space()
    full = [{k: space.values(p)[k] for k in SLOT_AXES}
            for p in space.sample(1536, seed=0, salt=0)]
    assert mix["slots"] == full[:768]


def test_pool_is_the_sram_axis_that_holds_64_banks(cfg, mix):
    assert mix["sram_kb_pool"] == [kb for kb in dz.sram_axis(cfg)
                                   if kb >= 16 * 64]


def test_every_drawn_design_is_valid_in_the_search_space(cfg, mix):
    from repro.search.space import SearchPoint
    from repro.search.studies import table_v_space
    space = table_v_space()
    axes = [list(a.values) for a in space.axes]
    kb_of = {kb * 1024 // 3: kb for kb in mix["sram_kb_pool"]}
    for k in (dz.WARMUP, 0):
        for d, s in zip(dz.draw(cfg, mix, 2**31 + 5, k), mix["slots"]):
            kb = kb_of[d["memory"]["ifmap_sram_bytes"]]
            vals = [s["array"], kb, s["dataflow"], s["channels"], s["bw"],
                    s["layout_banks"]]
            p = SearchPoint(tuple(ax.index(v) for ax, v in zip(axes, vals)))
            assert space.is_valid(p)
            assert space.config(p).to_dict() == d


def test_draw_is_deterministic_and_every_study_has_the_same_programs(
        cfg, mix):
    shapes = set()
    for seed in SEEDS:
        for k in (dz.WARMUP, 0, 1):
            a = dz.draw(cfg, mix, seed, k)
            assert a == dz.draw(cfg, mix, seed, k)
            # no design twice: the draw is without replacement per flavor
            assert len({json.dumps(d, sort_keys=True) for d in a}) == len(a)
            shapes.add(repr(_programs(a)))
    assert len(shapes) == 1
    assert dz.draw(cfg, mix, 5, 0) != dz.draw(cfg, mix, 5, 1)
    # twelve programs, three quarters of the designs with a layout stage
    progs, _ = _programs(a)
    assert len(progs) == 12
    assert sum(n for (_, banks), (n, _) in progs if banks) == 557


def test_mesh4_mix_is_search_rung_on_a_mesh_of_four():
    rung = _load("search-rung", "traffic")
    mesh4 = _load("search-rung-mesh4", "traffic")
    assert mesh4.pop("mesh") == {"shape": [4], "axes": ["data"]}
    mesh4.pop("notes"), rung.pop("notes")
    assert mesh4 == rung
