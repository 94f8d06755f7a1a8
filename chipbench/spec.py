"""Find a cell's configuration, traffic mix, limits and per-layer metric
readers by the names `BENCHMARK.json` gives them.

    chipbench/configs/<config>.json     sizes of one configuration
    chipbench/traffic/<mix>.json        parameters of one traffic mix
    chipbench/limits/<cell>.json        the limits `correct` is held to
    chipbench/metrics/<metric>.py       `read(record)` of one metric

Adding a configuration, a mix, a cell or a metric adds files and entries
and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: Dict, name: str, base: str = HERE) -> Cell:
    """The cell `name` of `bench`, with its files read from `base`."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]

    def applies(m: Dict) -> bool:
        return name in m.get("workloads", [name])

    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(os.path.join(base, "configs", w["config"] + ".json")),
        mix=_json(os.path.join(base, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(base, "limits", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def reader(metric: str, base: str = HERE
           ) -> Callable[[Dict], Optional[float]]:
    """`read(record)` of `metrics/<metric>.py`."""
    path = os.path.join(base, "metrics", metric + ".py")
    modspec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    return mod.read
