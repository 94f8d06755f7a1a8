"""The trace rungs that `studies.search_edp` sends, which the
`search-rung` traffic mix copies.

    python3 -m chipbench.search_rungs 0 1 2

For each search seed: the full-size search (ViT-base, 12 layers, a
1536-point fast screen, two proposal rounds, eta 4) run at fast
fidelity up to its trace rung, and the 16 design labels that rung
re-evaluates (`a<array>-s<SRAM KiB>-<dataflow>-ch<channels>-bw<bytes per
cycle>-lay<layout banks>`), one JSON line per seed.  The trace rung
itself is not run: its cohort is decided by the fast rounds.  About
three minutes per seed on one CPU core.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    seeds = [int(s) for s in (sys.argv[1:] if argv is None else argv)]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.workloads import vit_linear
    from repro.search.driver import SearchDriver
    from repro.search.studies import table_v_space

    class RungOnly(SearchDriver):
        """Records the trace rung's cohort and evaluates it at fast."""

        def _eval_cohort(self, round_idx, fidelity, points):
            if fidelity == "trace":
                self.rung = [self.space.label(p) for p in points]
                fidelity = "fast"
            return super()._eval_cohort(round_idx, fidelity, points)

    wl = {"vit-base": vit_linear(768, 12, 3072, prefix="vitb")}
    for seed in seeds:
        # the knobs of studies.search_edp at full size
        d = RungOnly(table_v_space(), wl, seed=seed, metric="edp",
                     objectives=("total_cycles", "energy_pj"),
                     ladder=("fast", "trace"), screen=1536, eta=4.0,
                     explore_rounds=2, rung_sizes=(16,))
        d.run()
        print(json.dumps({"seed": seed, "trace_rung": d.rung}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
