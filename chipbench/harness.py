"""One run of one cell: set-up, the measured window, the per-layer record
and the comparison that decides `correct`.

The window drives the user's entry point,
`Study(...).designs(...).workloads(...).fidelity(...).options(...).run()`,
with whole Studies back to back; it starts no Study that it expects to
end after `seconds`.  Set-up runs one Study of the cell's own shapes, so
every program is compiled (or loaded from the persistent cache) before
the window starts.
"""
from __future__ import annotations

import math
import sys
import tempfile
import time
from typing import Dict, List

from . import check, designs as dz, tracing
from .spec import Cell, reader

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Backend compiles as (host clock at the event, seconds)."""

    def __init__(self):
        self.events: List[tuple] = []

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), secs))

    def between(self, lo: float, hi: float) -> List[float]:
        return [s for t, s in self.events if lo <= t <= hi]


def cells_per_s(done: int, window_s: float) -> float:
    """Completed cells over the time from the window's start to the end of
    its last Study."""
    return done / window_s if window_s > 0 else 0.0


def fidelities(mix: Dict) -> tuple:
    """The fidelities of a mix's Studies: `"fidelity"` is one name or a
    list of them."""
    fid = mix["fidelity"]
    return (fid,) if isinstance(fid, str) else tuple(fid)


class Workload:
    """The cell's configuration and mix turned into Studies of the
    program: Study `k` draws its designs from `(seed, k)` and runs each
    at every fidelity of the mix, `n_cells` cells in all.

    A mix with a `mesh` key (`{"shape": [4], "axes": ["data"]}`) runs
    each Study over a mesh of the run's `devices`, whose size has to be
    the cell's `chips`; a mix without one runs each Study as a user on
    one device does, with no mesh, even where the cell holds more chips
    (a whole host, for steadiness)."""

    def __init__(self, cell: Cell, seed: int, devices):
        from repro.api import Study
        from repro.core.accelerator import AcceleratorConfig
        from repro.core.energy import ERT
        from repro.core.workloads import Op
        from repro.trace.generator import TraceSpec
        self.Study, self.Config = Study, AcceleratorConfig
        cfg, mix = cell.config, cell.mix
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.ops = [Op(str(g[0]), int(g[1]), int(g[2]), int(g[3]),
                       float(g[4])) for g in cfg["gemms"]]
        self.ert = ERT(**cfg["ert"])
        self.spec = TraceSpec(**cfg["trace_spec"])
        self.fidelities = fidelities(mix)
        self.engine = mix["engine"]
        self.n_cells = len(mix["slots"]) * len(self.fidelities)
        self._picks: Dict[int, list] = {}
        self.mesh = None
        if "mesh" in mix:
            from repro.launch.mesh import auto_mesh
            shape = tuple(int(n) for n in mix["mesh"]["shape"])
            if math.prod(shape) != cell.chips or len(devices) != cell.chips:
                raise ValueError(
                    f"{cell.name}: a mesh of shape {shape} over "
                    f"{len(devices)} devices for a cell of {cell.chips} "
                    f"chips")
            self.mesh = auto_mesh(shape, tuple(mix["mesh"]["axes"]),
                                  devices=list(devices))

    def picks(self, k: int) -> list:
        if k not in self._picks:
            self._picks[k] = dz.draw(self.cfg, self.mix, self.seed, k)
        return self._picks[k]

    def run(self, k: int):
        picks = self.picks(k)
        study = (self.Study(f"chipbench-{k}")
                 .designs([self.Config.from_dict(d) for d in picks],
                          [f"d{j}" for j in range(len(picks))])
                 .workloads({self.cfg["workload"]: self.ops})
                 .fidelity(*self.fidelities)
                 .options(ert=self.ert, engine=self.engine,
                          trace_spec=self.spec))
        if self.mesh is None:
            return study.run()
        return study.run(mesh=self.mesh)


def answered(frame, picks) -> List[tuple]:
    """(design, row) of each answered cell of a frame, matched by the
    design label the Study was given."""
    out = []
    ok = frame.ok()
    for r in ok.rows():
        out.append((picks[int(str(r["design"])[1:])], r))
    return out


def tally(frames, n_cells: int, engine: str):
    """(attempted, bad, answered cells) over the window's Study frames,
    each given with its design picks and expected to hold `n_cells`
    cells: a failed or missing cell counts as attempted and not
    answered."""
    attempted, bad, cells = 0, 0, []
    for frame, picks in frames:
        attempted += n_cells
        bad += check.count_bad(frame, n_cells, engine)
        cells += answered(frame, picks)
    return attempted, bad, cells


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _window(wl: Workload, seconds: float):
    """Whole Studies back to back: (Study index and frame of each,
    window start, end of the last Study, seconds of each Study)."""
    import jax
    frames, study_s, last = [], [], 0.0
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        k = 0
        # start no Study expected (from the one before) to end too late
        while not k or time.perf_counter() - start + last <= seconds:
            s0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(tracing.STUDY):
                frames.append((k, wl.run(k)))
            end = time.perf_counter()
            last = end - s0
            study_s.append(last)
            k += 1
    return frames, start, end, study_s


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> Dict:
    """The result line's object (see run.py) of one run."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # cache every program, however fast it compiled: a later run's set-up
    # then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    comp = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(comp)

    wl = Workload(cell, seed, devices)
    t0 = time.perf_counter()
    warm = wl.run(dz.WARMUP)
    t_warm = time.perf_counter() - t0
    log(f"set-up: warm-up Study of {len(warm)} cells in {t_warm:.2f} s "
        f"(engine {warm.meta.get('engine')})")
    # draw the window's Studies before it starts (more, if it reaches
    # further, are drawn from the same seed on demand)
    for k in range(int(math.ceil(seconds / max(t_warm, 1e-3))) + 2):
        wl.picks(k)

    red = None
    if trace:
        with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as d:
            with tracing.capture(d):
                frames, start, end, study_s = _window(wl, seconds)
            t1 = time.perf_counter()
            red = tracing.reduce(tracing.load(d))
            log(f"trace reduced in {time.perf_counter() - t1:.2f} s")
    else:
        frames, start, end, study_s = _window(wl, seconds)
    setup_s = start - t_start
    window_s = end - start
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    attempted, bad, cells = tally([(res, wl.picks(k)) for k, res in frames],
                                  wl.n_cells, wl.engine)
    done = len(cells)
    compiles = [[t - start, secs] for t, secs in comp.events]
    window_compiles = len(comp.between(start, end))
    log(f"window: {len(frames)} Studies, {done} of {attempted} cells in "
        f"{window_s:.3f} s (Studies of {', '.join(f'{t:.3f}' for t in study_s)}"
        f" s); {window_compiles} compiles in the window")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if red is not None:
        device["busy_s"] = sum(red["busy_s"]) / len(red["busy_s"])
        device["window_s"] = red["window_s"]
    # everything the run measured, for the per-layer readers
    record = {"cells": done, "attempted": attempted, "studies": len(frames),
              "study_s": study_s, "window_s": window_s, "setup_s": setup_s,
              "warmup_s": t_warm, "compiles": compiles,
              "window_compiles": window_compiles,
              "setup_compile_s": sum(comp.between(-math.inf, start)),
              "device": device, "trace": red}
    out: Dict = {}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            out["breakdown"] = {"device_ops": tracing.ranked(red["op_s"]),
                                "idle_gaps": tracing.ranked(red["gap_s"])}
    else:
        e2e = {"cells_per_s": cells_per_s(done, window_s),
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    t1 = time.perf_counter()
    pick = check.draw_sample([row["total_cycles"] for _, row in cells],
                             cell.mix["check_sample"], seed)
    values = {"bad_cells": bad,
              **check.readings(cells, pick, cell.config)}
    checks, correct = check.judge(values, cell.limits)
    log(f"reference compared in {time.perf_counter() - t1:.2f} s")
    return {"correct": correct, "attempted": attempted,
            "failed": attempted - done, "metrics": metrics,
            "device": device, **out, "checks": checks}
