"""The comparison that decides `correct`.

What the window's Studies produced is compared with the plain reference
(`reference.py`) once the window has closed:

  bad_cells     every cell of every window Study: failed (`cell_status`),
                a non-finite metric, a row missing from the frame, a cell
                that did not run batched, or a Study whose replay engine
                is not the mix's.  Limit 0.
  analytic_err  on a seeded sample of cells: the largest relative gap of
                compute cycles, DRAM bytes, energy and its four groups
                (the stall-free analytic stages and the energy model);
                energy groups are taken relative to the cell's energy.
  cycles_err    on the same sample: the largest gap of total and stall
                cycles relative to the cell's total cycles, and of
                utilization and EdP relative to themselves (what the
                DRAM stall feeds).

The sample holds the cell with the most total cycles and the rest drawn
from the seed; each row is held to the reference at its own fidelity,
and a design met twice at one fidelity is computed once.
"""
from __future__ import annotations

import json
import math
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from . import designs as dz
from . import reference as ref

NUMBERS = ("bad_cells", "analytic_err", "cycles_err")
# the reading of a gap that could not be taken (no cell, a NaN): finite,
# so that the result line stays plain JSON
NO_READING = 1e30
CYCLE_COLS = ("total_cycles", "stall_cycles")
SELF_COLS = ("utilization", "edp")
ENERGY_COLS = ("energy_pj",) + tuple(ref.ENERGY_GROUPS)


def _gap(a: float, b: float, scale: float) -> float:
    return abs(a - b) / max(abs(scale), 1e-30)


def gaps(got: Dict[str, float], want: Dict[str, float]) -> Dict[str, float]:
    """(analytic_err, cycles_err) of one cell."""
    analytic = max(
        _gap(got["compute_cycles"], want["compute_cycles"],
             want["compute_cycles"]),
        _gap(got["dram_bytes"], want["dram_bytes"], want["dram_bytes"]),
        *(_gap(got[c], want[c], want["energy_pj"]) for c in ENERGY_COLS))
    cycles = max(
        *(_gap(got[c], want[c], want["total_cycles"]) for c in CYCLE_COLS),
        *(_gap(got[c], want[c], want[c]) for c in SELF_COLS))
    return {"analytic_err": analytic, "cycles_err": cycles}


def count_bad(frame, n_expected: int, engine: str) -> int:
    """Cells of one Study frame that cannot be counted as answered."""
    n = len(frame)
    if frame.meta.get("engine", engine) != engine:
        return n_expected
    bad = n_expected - n
    status = np.asarray(frame["cell_status"])
    batched = np.asarray(frame["batched"])
    finite = np.ones(n, bool)
    for c in ref.COLUMNS:
        finite &= np.isfinite(np.asarray(frame[c], np.float64))
    return int(bad + np.sum((status != 0) | (batched != 1) | ~finite))


def draw_sample(totals: Sequence[float], n: int, seed: int) -> List[int]:
    """Indices into the window's cells: the one with the most total
    cycles, then up to n - 1 more drawn from the seed."""
    m = len(totals)
    if m == 0:
        return []
    top = int(np.nanargmax(np.asarray(totals, np.float64)))
    rng = np.random.default_rng([seed % (1 << 64), 1 << 40])
    rest = [int(i) for i in rng.permutation(m) if int(i) != top]
    return [top] + rest[:max(0, n - 1)]


def readings(cells: List[Tuple[Dict, Dict[str, float]]],
             pick: Sequence[int], cfg: Dict,
             reference: Callable = ref.cell_metrics) -> Dict[str, float]:
    """(analytic_err, cycles_err) over the picked cells; `cells` is every
    answered cell as (design, frame row), and each row names its
    `fidelity`."""
    if not pick:
        return {"analytic_err": NO_READING, "cycles_err": NO_READING}
    worst = {"analytic_err": 0.0, "cycles_err": 0.0}
    memo: Dict[str, Dict[str, float]] = {}
    for i in pick:
        design, row = cells[i]
        fidelity = str(row["fidelity"])
        key = json.dumps([fidelity, design], sort_keys=True)
        if key not in memo:
            memo[key] = reference(dz.plain(design), cfg["gemms"], fidelity,
                                  cfg)
        for k, v in gaps(row, memo[key]).items():
            # a NaN gap is as wrong as it gets
            worst[k] = NO_READING if math.isnan(v) else max(worst[k], v)
    return worst


def judge(values: Dict[str, float], limits: Dict
          ) -> Tuple[Dict[str, Dict[str, float]], bool]:
    """Each number compared beside its limit, and the verdict."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())
