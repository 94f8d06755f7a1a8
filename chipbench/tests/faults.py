"""Faults planted under the timed path, for the tests of `correct`, and
the small cells (`tiny`) they are planted in.

Each fault is a context manager that breaks one thing the program
produces while a run of the harness drives it; the Study's compiled
sweep programs are dropped on entry and exit so that the broken code is
the code that runs."""
import contextlib
import dataclasses

import numpy as np

from chipbench import spec


def _program(mix, slot):
    """The widest sweep program of the mix that a slot's design joins: a
    fast program does not split by DRAM, a trace program does."""
    from chipbench import designs as dz
    from chipbench.harness import fidelities
    if "fast" in fidelities(mix):
        return slot["dataflow"], slot["layout_banks"]
    return dz.flavor(slot)


def shares_a_program(mix):
    """Whether two of the mix's designs share a sweep program."""
    programs = [_program(mix, s) for s in mix["slots"]]
    return len(set(programs)) < len(programs)


def tiny(name, designs):
    """Cell `name` on its first four GEMMs and its first `designs` slots,
    or as many more as it takes for two of them to share a sweep program
    (so that half of a program's batch can be left out)."""
    cell = spec.find_cell(spec.load_benchmark(), name)
    cell.config["gemms"] = cell.config["gemms"][:4]
    slots = cell.mix["slots"]
    while (designs < len(slots)
           and not shares_a_program(dict(cell.mix, slots=slots[:designs]))):
        designs += 1
    cell.mix["slots"] = slots[:designs]
    cell.mix["check_sample"] = 16
    return cell


@contextlib.contextmanager
def _patched(module, name, make):
    from repro.api import simulator
    real = getattr(module, name)
    saved = dict(simulator._SWEEP_FN_CACHE)
    simulator._SWEEP_FN_CACHE.clear()
    setattr(module, name, make(real))
    try:
        yield
    finally:
        setattr(module, name, real)
        simulator._SWEEP_FN_CACHE.clear()
        simulator._SWEEP_FN_CACHE.update(saved)


@contextlib.contextmanager
def state_unchanged(fidelity):
    """The DRAM model never advances at any of the mix's fidelities
    (`fidelity` is one name or a list of them)."""
    from chipbench.harness import fidelities
    with contextlib.ExitStack() as stack:
        for fid in fidelities({"fidelity": fidelity}):
            stack.enter_context(_no_stall(fid))
        yield


def _no_stall(fidelity):
    """The replay (trace) or the stall stage (fast) hands back no stall."""
    if fidelity == "trace":
        from repro.core import dram

        def make(real):
            def broken(*a, **k):
                r = real(*a, **k)
                return dataclasses.replace(
                    r, stall_cycles=r.stall_cycles * 0.0)
            return broken
        return _patched(dram, "replay_requests", make)
    from repro.core import stages

    def make(real):
        def broken(*a, **k):
            s = real(*a, **k)
            return dict(s, stall_cycles=s["stall_cycles"] * 0.0)
        return broken
    return _patched(stages, "traced_op_stats", make)


def half_batch(fidelity=None):
    """Half of each group's designs left out; they read the mean of the
    rest."""
    from repro.api import study

    def make(real):
        def broken(cfgs, *a, **k):
            h = max(1, len(cfgs) // 2)
            out = real(list(cfgs[:h]), *a, **k)
            return {c: np.concatenate([v, np.full(len(cfgs) - h, v.mean())])
                    for c, v in out.items()}
        return broken
    return _patched(study, "_sweep_batched", make)


def half_designs(fidelity=None):
    """Half of each Study's designs left out: their cells read the mean
    of the rest's at the same fidelity.  The form of `half_batch` for a
    mix whose every sweep program holds one design."""
    from repro.api import study

    def make(real):
        def broken(self, plan, indices=None, **k):
            keep = {label for label, _ in
                    self._designs[:max(1, len(self._designs) // 2)]}
            sel = None if indices is None else set(indices)
            want = [c.index for c in plan.cells
                    if sel is None or c.index in sel]
            results, executed, hits = real(
                self, plan, [i for i in want if plan.cells[i].design in keep],
                **k)
            for i in want:
                if i not in results:
                    peers = [r for j, r in results.items()
                             if plan.cells[j].fidelity
                             == plan.cells[i].fidelity]
                    results[i] = {m: float(np.mean([r[m] for r in peers]))
                                  for m in peers[0]}
            return results, executed, hits
        return broken
    return _patched(study.Study, "_execute_cells", make)


def answer_altered(fidelity=None):
    """Every cell's energy 1 % off where the sweep kernel produces it."""
    from repro.api import simulator

    def make(real):
        def broken(counts, ert):
            e = real(counts, ert)
            return dict(e, total=e["total"] * 1.01)
        return broken
    return _patched(simulator, "energy_pj", make)


def no_exchange(fidelity=None):
    """On a mesh, each device's share of a replay block is never
    exchanged: the block's output is one device's share repeated, so
    every row carries that device's stalls."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def make(real):
        def broken(f, *, mesh, out_specs, **k):
            def local(*a):
                return jnp.tile(f(*a), mesh.size)
            return real(local, mesh=mesh, out_specs=P(), **k)
        return broken
    return _patched(jax, "shard_map", make)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}
# faults that exist only across chips
MESH_FAULTS = {"no_exchange": no_exchange}


def for_cell(mix):
    """The faults a cell without a mesh can have: where no two designs
    share a sweep program there is no half of a program's batch to leave
    out, and half of the Study's designs are left out in its place."""
    if shares_a_program(mix):
        return dict(FAULTS)
    return {k: (half_designs if k == "half_batch" else v)
            for k, v in FAULTS.items()}
