"""The Study path's profiler names (repro.spans): the host spans a traced
Study emits, with the `sweep` span's counts, and the device scopes and
program names its sweep programs carry."""
import dataclasses
import glob
import os
import re

import jax
import pytest
from jax.profiler import ProfileData

from repro import spans
from repro.api import Study, preset_grid
from repro.api.simulator import _batched_design_fn, _sweep_inputs
from repro.core.accelerator import LayoutConfig
from repro.core.energy import DEFAULT_ERT
from repro.core.workloads import Op
from repro.trace import TraceSpec

OPS = [Op("a", 64, 128, 96), Op("b", 96, 64, 128, count=2.0)]
SPEC = TraceSpec(cap=256)


def _designs():
    """Three designs in one flavor: the first two differ only in SIMD
    lanes, so they share one demand stream."""
    a, c = preset_grid(array=[16, 32])
    core = dataclasses.replace(a.cores[0],
                               simd_lanes=2 * a.cores[0].simd_lanes)
    return {"a": a, "b": a.with_(cores=(core,)), "c": c}


def _host_events(log_dir):
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    pd = ProfileData.from_file(path)
    return [(ev.name, dict(ev.stats)) for p in pd.planes
            if p.name.startswith("/host:") for ln in p.lines
            for ev in ln.events]


def test_a_traced_study_emits_every_host_span(tmp_path):
    study = (Study().designs(_designs()).workloads({"w": OPS})
             .fidelity("trace").options(trace_spec=SPEC))
    study.run()                  # compiles outside the profiler
    with jax.profiler.trace(str(tmp_path / "trace")):
        res = study.run(cache=str(tmp_path / "cache"))
    assert res.fraction_batched == 1.0
    evs = _host_events(str(tmp_path / "trace"))
    assert set(spans.HOST_SPANS) <= {n for n, _ in evs}
    plan = study.plan()
    (run,) = [a for n, a in evs if n == spans.STUDY_RUN]
    assert run == {"cells": len(plan.cells), "groups": len(plan.groups)}
    (sweep,) = [a for n, a in evs if n == spans.SWEEP]
    assert sweep["program"] == "sweep_trace_ws_ch2_bw19p2"
    assert sweep["designs"] == len(plan.groups[0].cells) == 3
    # designs a and b share their stream: two streams per op
    assert sweep["streams"] == 2 * len(OPS)
    assert (sweep["blocks"], sweep["block"]) == (1, 4)
    assert sweep["layout_rows"] == 0           # no layout stage


def _layout_rows(cfgs, ops):
    _, _, counts = _sweep_inputs(cfgs, ops, cfgs[0].dataflow,
                                 cfgs[0].memory.word_bytes, DEFAULT_ERT,
                                 None, None, None, "xla", 0)
    return counts["layout_rows"]


def _with_layout(cfgs, banks=32):
    return [c.with_(layout=LayoutConfig(enabled=True, num_banks=banks))
            for c in cfgs]


@pytest.mark.parametrize("case", ["pairs", "no_layout", "screen"])
def test_the_sweep_span_counts_layout_curves(case):
    """`layout_rows`: the layout slowdown curves a program computes, one
    per distinct (array rows, op stride) pair; 0 without layout."""
    from repro.core.workloads import vit_base_linear
    if case == "screen":
        # the screen's shape: 48 ViT-base GEMMs, all at N = 197 tokens,
        # on arrays 32/64/128
        cfgs = _with_layout(preset_grid(array=[32, 64, 128, 64, 32],
                                        sram_mb=[1.0, 4.0]))
        assert _layout_rows(cfgs, vit_base_linear()) == 3
        return
    # strides 128 and 64 (op c repeats a's), arrays 16 and 32
    ops = OPS + [Op("c", 32, 128, 16)]
    cfgs = list(_designs().values())
    if case == "no_layout":
        assert _layout_rows(cfgs, ops) == 0
    else:
        assert _layout_rows(_with_layout(cfgs), ops) == 2 * 2


def _lowered_hlo(cfgs, fidelity):
    dram = cfgs[0].dram if fidelity == "trace" else None
    fn, args, _ = _sweep_inputs(cfgs, OPS, cfgs[0].dataflow,
                                cfgs[0].memory.word_bytes, DEFAULT_ERT,
                                None, dram, SPEC, "xla", 0)
    return fn, fn.lower(*args).as_text(dialect="hlo", debug_info=True)


def _scoped(hlo, scope):
    # a scope entered under a transform reads e.g. `vmap(generate)`
    return re.search(rf'op_name="([^"]*/)?(\w+\()*{scope}\)*/',
                     hlo) is not None


def test_a_trace_program_is_named_and_scoped():
    fn, hlo = _lowered_hlo(list(_designs().values()), "trace")
    assert fn.__name__ == "sweep_trace_ws_ch2_bw19p2"
    assert "HloModule jit_sweep_trace_ws_ch2_bw19p2" in hlo
    for scope in spans.DEVICE_SCOPES:
        assert _scoped(hlo, scope), scope


def test_a_fast_program_carries_stages_only():
    fn, hlo = _lowered_hlo(list(_designs().values()), "fast")
    assert "HloModule jit_sweep_fast_ws" in hlo
    assert _scoped(hlo, spans.STAGES)
    for scope in set(spans.DEVICE_SCOPES) - {spans.STAGES}:
        assert not _scoped(hlo, scope), scope


@pytest.mark.parametrize("dataflow, kw, name", [
    ("os", {}, "sweep_fast_os"),
    ("is", dict(layout=LayoutConfig(enabled=True, num_banks=64)),
     "sweep_fast_is_lay64"),
    ("ws", dict(mesh_shape=(2, 2), with_sparsity=True, noc="mesh"),
     "sweep_fast_ws_2x2_sparse_mesh"),
])
def test_program_names_follow_the_flavor(dataflow, kw, name):
    assert _batched_design_fn(dataflow, 1, DEFAULT_ERT, **kw).__name__ \
        == name


def test_the_layout_curves_run_in_the_stages_scope():
    """The layout stage's sort and scatter-add (the curve table) carry the
    `stages` scope, so a device trace does not read them as unscoped."""
    _, hlo = _lowered_hlo(_with_layout(list(_designs().values())), "fast")
    ops = [ln for ln in hlo.splitlines()
           if re.search(r"= s32\[[\d,]*\][^ ]* (sort|scatter)\(", ln)]
    assert len(ops) == 2
    for ln in ops:
        assert _scoped(ln, spans.STAGES), ln
