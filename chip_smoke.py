#!/usr/bin/env python3
"""Bring-up smoke test: the simulator's Study path on a TPU.

    python3 chip_smoke.py              # one chip, every phase but `mesh`
    python3 chip_smoke.py --chips 4    # four chips, the `mesh` phase only

Run from the repository root (it imports `src/repro`).  One process
drives the chip; each phase prints one line with its cells, replay
engine, and compile and run seconds (informational).  The last line of
stdout is `{"ok": true, "device": {...}}` when every phase passed;
otherwise the script exits 1 and prints no such line.

Phases:
  device   JAX's first device must be a TPU — never carries on on the CPU.
  paper    studies.edp_array_size at full size (12 ViT-base layers):
           every claim passes, no failed cell.
  user     64 designs (array 16..128 x SRAM 0.25..16 MB x ws/os) x
           {ResNet-18, ViT-base (12 layers), Qwen2-1.5B prefill at 4096
           tokens} x {fast, trace} on the default engine: every cell
           batched, none failed, engine "xla".
  oracle   a seeded sample of 8 of those trace cells through the per-op
           oracle (`force_fallback`): total/stall cycles and energy
           within 1e-3.
  pallas   the same trace cells on the replay megakernel: the engine
           resolves to "pallas" and the cycle columns match the xla
           frame within 1e-3.
  mesh     (--chips 4) the user study's trace cells sharded over a
           4-device mesh against the same cells on one device, within
           1e-3; prints each device's peak bytes.

Errors of cycle columns are relative to the reference cell's total
cycles (see `compare`); each phase also prints the largest error
relative to the column itself.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

TOL = 1e-3
CYCLE_COLUMNS = ("total_cycles", "compute_cycles", "stall_cycles")
ORACLE_COLUMNS = ("total_cycles", "stall_cycles", "energy_pj")
# trace cells the oracle re-runs per workload (8 in all)
ORACLE_SAMPLE = {"resnet18": 3, "vit-base": 3, "qwen2-1.5b-prefill": 2}

_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_):
    # lowering and backend compile of every jitted call (tracing nests,
    # so it stays in the run seconds)
    if event in ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration"):
        _compile_s[0] += secs


class PhaseFailed(Exception):
    pass


def run_phase(name, fn):
    """Run one phase; print its line (PASS or FAIL) with timings."""
    c0, t0 = _compile_s[0], time.perf_counter()
    try:
        info = fn()
    except Exception as e:  # noqa: BLE001 — report the phase, then stop
        print(f"phase {name}: FAIL {type(e).__name__}: {e}", flush=True)
        raise PhaseFailed(name) from e
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    print(f"phase {name}: PASS {info} compile_s={comp:.1f} "
          f"run_s={wall - comp:.1f}", flush=True)


def rel_err(a, b, scale) -> float:
    a, b = np.asarray(a, float), np.asarray(b, float)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(scale), 1.0),
                        initial=0.0))


def check_frame(res, what: str) -> None:
    if res.failed_cells:
        errs = "; ".join(f"{e['group']}: {e['error']}"
                         for e in res.meta.get("cell_errors", []))
        raise AssertionError(f"{what}: {len(res.failed_cells)} failed "
                             f"cells ({errs or 'non-finite metrics'})")
    if res.fraction_batched != 1.0:
        raise AssertionError(f"{what}: fraction_batched "
                             f"{res.fraction_batched} != 1.0")


def compare(a, b, columns, what: str) -> str:
    """Max error per column, relative to the reference cell's total
    cycles for cycle columns (a compute-bound cell's stall is a small
    difference of large f32 completion times, so its own magnitude is
    no yardstick) and to itself otherwise; fails past TOL.  Returns the
    worst error and, for information, the worst self-relative one."""
    worst = worst_self = 0.0
    for col in columns:
        scale = b["total_cycles"] if col.endswith("_cycles") else b[col]
        err = rel_err(a[col], b[col], scale)
        if not err <= TOL:
            raise AssertionError(f"{what}: {col} differs by {err:.3g} "
                                 f"relative (limit {TOL})")
        worst = max(worst, err)
        worst_self = max(worst_self, rel_err(a[col], b[col], b[col]))
    return f"max_rel_err={worst:.2e} max_self_rel_err={worst_self:.2e}"


def user_study():
    from repro.api import Study, preset_grid
    from repro.configs import get_config
    from repro.core.workloads import lm_ops, resnet18, vit_linear
    grid = preset_grid(array=[16, 32, 64, 128],
                       sram_mb=[0.25, 0.5, 1, 2, 4, 8, 12, 16],
                       dataflow=["ws", "os"])
    labels = [f"d{i}" for i in range(len(grid))]
    wls = {"resnet18": resnet18(),
           "vit-base": vit_linear(768, 12, 3072, prefix="vitb"),
           "qwen2-1.5b-prefill": lm_ops(get_config("qwen2-1.5b"),
                                        seq=4096, batch=1,
                                        mode="prefill")}
    return grid, labels, wls, lambda: (Study("user").designs(grid, labels)
                                       .workloads(wls))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the mesh phase, over four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the oracle's cell sample")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    print(f"phase device: platform={dev.platform} "
          f"kind={dev.device_kind} count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    from repro.api import Study, get_study

    grid, labels, wls, mk = user_study()
    ncells = len(grid) * len(wls)
    state = {}

    def paper():
        res = get_study("edp_array_size").run()
        claims = res.check_claims()
        check_frame(res, "edp_array_size")
        bad = [k for k, ok in claims.items() if not ok]
        if bad:
            raise AssertionError(f"claims failed: {bad}")
        return f"cells={len(res)} claims={len(claims)}/{len(claims)}"

    def user():
        res = mk().fidelity("fast", "trace").run()
        check_frame(res, "user study")
        if res.meta.get("engine") != "xla":
            raise AssertionError(f"engine {res.meta.get('engine')!r}")
        state["xla"] = res.filter(fidelity="trace")
        return f"cells={len(res)} engine={res.meta['engine']}"

    def oracle():
        rng = np.random.default_rng(args.seed)
        errs, n = [], 0
        for w, k in ORACLE_SAMPLE.items():
            pick = sorted(int(i) for i in
                          rng.choice(len(grid), size=k, replace=False))
            ref = (Study("oracle")
                   .designs([grid[i] for i in pick], [labels[i] for i in pick])
                   .workloads({w: wls[w]}).fidelity("trace")
                   .options(force_fallback=True).run())
            if ref.failed_cells or ref.fraction_batched != 0.0:
                raise AssertionError(f"oracle run of {w} is not per-op "
                                     f"or has failed cells")
            got = state["xla"].filter(workload=w,
                                      design=[labels[i] for i in pick])
            errs.append(f"{w}:[{compare(got, ref, ORACLE_COLUMNS, w)}]")
            n += len(ref)
        return f"cells={n} " + " ".join(errs)

    def pallas():
        res = mk().fidelity("trace").options(engine="pallas").run()
        check_frame(res, "pallas study")
        eng = res.meta.get("engine")
        if eng != "pallas":
            raise AssertionError(f"engine resolved to {eng!r}, not "
                                 f"the compiled kernel")
        errs = compare(res, state["xla"], CYCLE_COLUMNS, "pallas vs xla")
        return f"cells={len(res)} engine={eng} {errs}"

    def mesh():
        from repro.launch.mesh import auto_mesh
        m = auto_mesh((args.chips,), ("data",), devices=devs[:args.chips])
        # the sharded run first, so device 0's peak is its own share
        t0 = time.perf_counter()
        sharded = mk().fidelity("trace").run(mesh=m)
        t1 = time.perf_counter()
        check_frame(sharded, "mesh study")
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in m.devices.flat]
        print(f"mesh peak_bytes_in_use per device: {peaks}", flush=True)
        plain = mk().fidelity("trace").run()
        t2 = time.perf_counter()
        check_frame(plain, "one-device study")
        cols = [c for c in plain.column_names()
                if c not in ("design", "workload", "fidelity")]
        errs = compare(sharded, plain, cols, "mesh vs one device")
        return (f"cells={len(sharded)} devices={m.size} "
                f"engine={sharded.meta.get('engine')} {errs} "
                f"mesh_wall_s={t1 - t0:.1f} one_device_wall_s={t2 - t1:.1f}")

    phases = ([("mesh", mesh)] if args.chips > 1 else
              [("paper", paper), ("user", user), ("oracle", oracle),
               ("pallas", pallas)])
    print(f"user study: {len(grid)} designs x {len(wls)} workloads "
          f"({ncells} cells per fidelity)", flush=True)
    try:
        for name, fn in phases:
            run_phase(name, fn)
    except PhaseFailed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
