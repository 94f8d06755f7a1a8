"""Readings that the limits of `correct` are set from.

    python3 -m chipbench.calibrate --workload vitb-edp.search-rung \
        --seeds 11,12,13 --control-seeds 11,12,13 [--out FILE]

For each seed, in one process: the cell's first window Studies through
the program, as many as hold the cells a run compares, and the numbers
`check` compares on the sample a run draws (the program's readings).
For each control seed, the control on the same sample: the plain
reference computed in bfloat16, one precision below the configuration's
float32, read against the float64 reference (the control's readings).  One JSON line per seed; no measured window.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import ml_dtypes
    from chipbench import reference as ref
    from chipbench.spec import find_cell, load_benchmark
    cell = find_cell(load_benchmark(ROOT), args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"calibrate: needs {cell.chips} TPU chips, JAX found "
              f"{len(devs)} {devs[0].platform!r}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    def control(design, gemms, fidelity, cfg):
        return ref.cell_metrics(design, gemms, fidelity, cfg,
                                num=ml_dtypes.bfloat16)

    with (open(args.out, "w") if args.out
          else contextlib.nullcontext()) as out:
        for seed in seeds:
            line = reading(cell, seed, seed in ctrl, devs, control)
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    return 0


def reading(cell, seed: int, with_control: bool, devs, control) -> dict:
    """One seed's readings: the program's, and the control's if asked."""
    import math
    from chipbench import check, harness
    from chipbench import designs as dz
    wl = harness.Workload(cell, seed, devs[:cell.chips])
    # the window's first Studies, as many as hold the cells a run compares
    n = math.ceil(cell.mix["check_sample"] / wl.n_cells)
    t0 = time.perf_counter()
    frames = [(wl.run(k), wl.picks(k)) for k in range(n)]
    t1 = time.perf_counter()
    _, bad, cells = harness.tally(frames, wl.n_cells, wl.engine)
    pick = check.draw_sample([r["total_cycles"] for _, r in cells],
                             cell.mix["check_sample"], seed)
    line = {"workload": cell.name, "seed": seed, "studies": n,
            "study_s": (t1 - t0) / n, "bad_cells": bad,
            "program": check.readings(cells, pick, cell.config)}
    t2 = time.perf_counter()
    line["reference_s"] = t2 - t1
    if with_control:
        # the control in the program's place, on the same sample
        ctl_cells = list(cells)
        for i in pick:
            design, row = cells[i]
            fid = str(row["fidelity"])
            ctl_cells[i] = (design, dict(
                control(dz.plain(design), cell.config["gemms"], fid,
                        cell.config), fidelity=fid))
        line["control"] = check.readings(ctl_cells, pick, cell.config)
        line["control_s"] = time.perf_counter() - t2
    return line


if __name__ == "__main__":
    sys.exit(main())
