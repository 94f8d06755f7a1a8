"""The cohorts that `studies.search_edp` sends, which the `search-rung`
and `search-screen` traffic mixes copy.

    python3 -m chipbench.search_rungs 0 1 2
    python3 -m chipbench.search_rungs --screen 0 768

For each search seed: the full-size search (ViT-base, 12 layers, a
1536-point fast screen, two proposal rounds, eta 4) run at fast
fidelity up to its trace rung, and the 16 design labels that rung
re-evaluates (`a<array>-s<SRAM KiB>-<dataflow>-ch<channels>-bw<bytes per
cycle>-lay<layout banks>`), one JSON line per seed.  The trace rung
itself is not run: its cohort is decided by the fast rounds.  About
three minutes per seed on one CPU core.

With `--screen <seed> <size>`: the search's first cohort instead, the
designs it screens at fast fidelity before anything is evaluated (1536
at full size, 768 as `search_edp(smoke=True)` screens; the smaller is
the larger's prefix), as the slots of a mix (array, dataflow, DRAM
channels and bandwidth, layout banks; the SRAM size is the mix's to
draw).  Nothing is run; a second or two.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOT_AXES = ("array", "dataflow", "channels", "bw", "layout_banks")


def _search(cls, seed: int, screen: int = 1536):
    from repro.core.workloads import vit_linear
    from repro.search.studies import table_v_space
    wl = {"vit-base": vit_linear(768, 12, 3072, prefix="vitb")}
    # the knobs of studies.search_edp at full size
    return cls(table_v_space(), wl, seed=seed, metric="edp",
               objectives=("total_cycles", "energy_pj"),
               ladder=("fast", "trace"), screen=screen, eta=4.0,
               explore_rounds=2, rung_sizes=(16,))


def trace_rung(seed: int) -> List[str]:
    """The labels of the designs the search's trace rung re-evaluates."""
    from repro.search.driver import SearchDriver

    class RungOnly(SearchDriver):
        """Records the trace rung's cohort and evaluates it at fast."""

        def _eval_cohort(self, round_idx, fidelity, points):
            if fidelity == "trace":
                self.rung = [self.space.label(p) for p in points]
                fidelity = "fast"
            return super()._eval_cohort(round_idx, fidelity, points)

    d = _search(RungOnly, seed)
    d.run()
    return d.rung


class _Screened(Exception):
    pass


def screen_slots(seed: int, screen: int) -> List[Dict]:
    """The slots of the `screen` designs the search screens first, in
    its order."""
    from repro.search.driver import SearchDriver

    class ScreenOnly(SearchDriver):
        """Records the first cohort and stops before evaluating it."""

        def _eval_cohort(self, round_idx, fidelity, points):
            self.slots = [{k: self.space.values(p)[k] for k in SLOT_AXES}
                          for p in points]
            raise _Screened

    d = _search(ScreenOnly, seed, screen)
    try:
        d.run()
    except _Screened:
        pass
    return d.slots


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if args[:1] == ["--screen"]:
        seed, size = int(args[1]), int(args[2])
        print(json.dumps({"seed": seed, "screen": screen_slots(seed, size)}))
        return 0
    for seed in map(int, args):
        print(json.dumps({"seed": seed, "trace_rung": trace_rung(seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
