"""Benchmark harness: one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Modules:
  bench_partitioning   Fig. 3    spatial vs spatio-temporal tradeoff
  bench_sparsity       Figs. 5/7/8 + Sec. IX-B sparsity design point
  bench_dram           Figs. 9/10 + Sec. IX-B WS/OS DRAM flip
  bench_layout         Figs. 12/13 bank-conflict slowdown grid
  bench_energy         Fig. 15 + Table V latency/energy/EdP
  bench_multicore      Table VI iso-compute + heterogeneous cores
  bench_sim_throughput Table IV analog + batched Simulator.sweep path
  bench_kernels        Pallas kernel microbenchmarks

``--smoke`` runs every module on reduced grids (CI / quick sanity);
``--only mod1,mod2`` restricts the module list.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from .common import emit


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grids for CI")
    ap.add_argument("--only", default="",
                    help="comma-separated bench module suffixes")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_partitioning, bench_sparsity, bench_dram,
                   bench_layout, bench_energy, bench_multicore,
                   bench_sim_throughput, bench_kernels)
    mods = [bench_partitioning, bench_sparsity, bench_dram, bench_layout,
            bench_energy, bench_multicore, bench_sim_throughput,
            bench_kernels]
    if args.only:
        want = {w.strip() for w in args.only.split(",") if w.strip()}
        known = {m.__name__.split("bench_")[-1] for m in mods}
        unknown = want - known
        if unknown:
            sys.exit(f"--only: unknown module(s) {sorted(unknown)}; "
                     f"available: {sorted(known)}")
        mods = [m for m in mods
                if m.__name__.split("bench_")[-1] in want]
    print("name,us_per_call,derived")
    failed = 0
    for m in mods:
        try:
            emit(m.run(smoke=args.smoke))
        except Exception:
            failed += 1
            print(f"{m.__name__},0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
