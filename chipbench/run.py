"""Run one benchmark cell on the chips of this machine.

    python3 -m chipbench.run --workload vitb-edp.search-rung --seed 7 \
        --seconds 20 --trace 0

From the repository root.  The last line of stdout is one JSON object:
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end
metrics with `--trace 0`, its per-layer metrics with `--trace 1`),
`device` and, traced, `breakdown`; `checks`, last, holds each number the
comparison with the reference read beside its limit, which also close
stderr.  Exits 1 without a result when JAX finds no TPU or fewer chips
than the cell asks for, and 2 when the program under test is absent.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chipbench: the program under test is not at {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from chipbench.spec import find_cell, load_benchmark
    cell = find_cell(load_benchmark(ROOT), args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX found platform "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    from chipbench.harness import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devs[:cell.chips], T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
