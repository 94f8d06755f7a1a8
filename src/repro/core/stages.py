"""The simulation pipeline as explicit, pluggable stages.

One GEMM op flows through (paper Fig. 1, left to right):

    mapping -> partition -> sparsity -> sram -> dram -> layout -> energy

Each stage is a small object with `apply(ctx)` mutating an `OpContext`;
`build_pipeline(fidelity)` selects concrete stages (today fidelity switches
the DRAM stage between the first-order bandwidth-overlap model and the
cycle-accurate lax.scan model; new fidelities or subsystems plug in here
rather than forking the engine). `repro.core.engine.simulate_op`,
`simulate_network` and the traced DSE path are all thin wrappers over this
module, so there is exactly one copy of the mapping/traffic math.

The traced twins (`traced_gemm_stats`, `traced_vector_stats`,
`traced_energy_counts`) run the *same* dataflow/energy functions on jnp
arrays, which is what lets `repro.api.Simulator.sweep` vmap/pjit thousands
of design points per call.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .accelerator import (AcceleratorConfig, LayoutConfig, MemoryConfig,
                          SparsityConfig)
from . import dataflow as dfm
from .dram import simulate_dram, tile_prefetch_trace
from .energy import DEFAULT_ERT, ERT, action_counts, action_counts_raw, energy_pj
from .layout import streaming_layout_extra, streaming_window_extra
from .multicore import best_multicore, best_multicore_cycles_model
from .sparsity import (sparse_compute_cycles, sparse_compute_cycles_model,
                       storage_bytes_model, storage_report)
from .workloads import Op

FIDELITIES = ("fast", "cycle", "trace")

_DRAM_REQ_CAP = 16384     # cycle-fidelity request cap per op (scaled beyond)


@dataclasses.dataclass
class OpContext:
    """Mutable working state threaded through the stage pipeline.

    Per-instance quantities (comp, stall, traffic) are for ONE instance of
    the op; the energy/finalize stage multiplies by `op.count`.
    """
    cfg: AcceleratorConfig
    op: Op
    ert: ERT
    sp: SparsityConfig
    # mapping / partition / sparsity
    comp: float = 0.0
    scheme: str = "single"
    util: float = 0.0
    sparse_info: Optional[Dict[str, float]] = None
    filter_shrink: float = 1.0
    # traffic
    sram: Optional[Dict[str, float]] = None
    dram: Optional[Dict[str, float]] = None
    dram_elems: float = 0.0
    dram_bytes: float = 0.0           # per instance
    stall: float = 0.0
    dram_stats: Optional[Dict[str, float]] = None
    layout_extra: float = 0.0
    noc_extra: float = 0.0            # per instance (repro.noc NocStage)
    noc_stats: Optional[Dict[str, float]] = None
    # finalized totals (x op.count)
    compute_total: float = 0.0
    stall_total: float = 0.0
    noc_total: float = 0.0
    layout_total: float = 0.0
    total: float = 0.0
    sram_reads: float = 0.0
    sram_writes: float = 0.0
    dram_bytes_total: float = 0.0
    energy_total: float = 0.0
    energy_by_action: Optional[Dict[str, float]] = None


class Stage:
    """A pipeline stage. Subclasses set `name` and implement `apply`."""
    name = "stage"

    def apply(self, ctx: OpContext) -> None:
        raise NotImplementedError


class CoreStage(Stage):
    """A stage whose model depends on one core's geometry. `core_index`
    selects the core a heterogeneous mesh is analyzed through; every
    core-dependent stage in one pipeline shares the same index so the
    report describes an actual core, not a mix."""

    def __init__(self, core_index: int = 0):
        self.core_index = core_index

    def core(self, ctx: OpContext):
        return ctx.cfg.cores[self.core_index]


class MappingStage(CoreStage):
    """Single-core dataflow mapping: analytical compute cycles + PE
    utilization (SCALE-Sim v2 runtime equations)."""
    name = "mapping"

    def apply(self, ctx: OpContext) -> None:
        op, core, df = ctx.op, self.core(ctx), ctx.cfg.dataflow
        ctx.comp = float(dfm.compute_cycles(df, op.M, op.N, op.K,
                                            core.rows, core.cols))
        ctx.scheme = "single"
        ctx.util = float(dfm.pe_utilization(df, op.M, op.N, op.K,
                                            core.rows, core.cols))


class PartitionStage(Stage):
    """Multi-core partitioning: pick the best spatial/spatio-temporal
    split over the core grid (skipped for single-core or sparse runs,
    matching the paper's feature composition)."""
    name = "partition"

    def apply(self, ctx: OpContext) -> None:
        if ctx.sp.enabled or ctx.cfg.num_cores <= 1:
            return
        op = ctx.op
        mc = best_multicore(ctx.cfg, op.M, op.N, op.K)
        ctx.comp = mc.cycles
        ctx.scheme = f"{mc.scheme}({mc.Pr}x{mc.Pc})"
        ctx.util = min(1.0, op.M * op.N * op.K / max(
            1.0, sum(c.num_pes for c in ctx.cfg.cores) * mc.cycles))


class SparsityStage(CoreStage):
    """N:M weight sparsity: compressed-stream compute cycles + storage
    report; records the filter-traffic shrink applied downstream."""
    name = "sparsity"

    def apply(self, ctx: OpContext) -> None:
        if not ctx.sp.enabled:
            return
        op, core, cfg = ctx.op, self.core(ctx), ctx.cfg
        ctx.comp = float(sparse_compute_cycles(
            cfg.dataflow, op.M, op.N, op.K, core.rows, core.cols, ctx.sp))
        ctx.sparse_info = storage_report(op.M, op.K, ctx.sp,
                                         cfg.memory.word_bytes)
        ctx.scheme = "single"
        ctx.util = min(1.0, op.M * op.N * op.K / max(
            1.0, core.num_pes * ctx.comp * ctx.sp.m / max(ctx.sp.n, 1)))
        ctx.filter_shrink = (ctx.sparse_info["total_bytes"]
                             / max(ctx.sparse_info["original_bytes"], 1.0))


class SramStage(CoreStage):
    """Aggregate SRAM demand counts; sparse filters stream compressed."""
    name = "sram"

    def apply(self, ctx: OpContext) -> None:
        op, core, cfg = ctx.op, self.core(ctx), ctx.cfg
        sram = dfm.sram_traffic(cfg.dataflow, op.M, op.N, op.K,
                                core.rows, core.cols)
        if ctx.filter_shrink != 1.0:
            sram["filter_reads"] = sram["filter_reads"] * ctx.filter_shrink
        ctx.sram = sram


class DramStage(CoreStage):
    """Capacity-based DRAM traffic shared by all fidelities; subclasses
    supply the stall model. The analyzed core comes from `core_index` —
    heterogeneous meshes model a specific member instead of silently
    modeling core 0."""
    name = "dram"

    def apply(self, ctx: OpContext) -> None:
        op, cfg = ctx.op, ctx.cfg
        core = self.core(ctx)
        dram = dfm.dram_traffic(cfg.dataflow, op.M, op.N, op.K,
                                core.rows, core.cols, cfg.memory)
        if ctx.filter_shrink != 1.0:
            dram["dram_filter"] = dram["dram_filter"] * ctx.filter_shrink
        ctx.dram = dram
        ctx.dram_elems = float(dram["dram_ifmap"] + dram["dram_filter"]
                               + dram["dram_ofmap_writes"]
                               + dram["dram_ofmap_reads"])
        ctx.dram_bytes = ctx.dram_elems * cfg.memory.word_bytes
        self.stalls(ctx)

    def stalls(self, ctx: OpContext) -> None:
        raise NotImplementedError


class FastDramStage(DramStage):
    """First-order stall: double-buffered transfer time vs compute.

    Operates on per-instance bytes; `op.count` scaling happens once in the
    finalize stage (the old engine divided by count here as well, silently
    double-discounting stalls for repeated ops)."""
    name = "dram[fast]"

    def stalls(self, ctx: OpContext) -> None:
        bw = ctx.cfg.dram.bandwidth_bytes_per_cycle * ctx.cfg.dram.channels
        ctx.stall = float(dfm.dram_stall_cycles_simple(
            ctx.dram_bytes, ctx.comp, bw))


class CycleDramStage(DramStage):
    """Cycle-accurate (Ramulator-like) DRAM: tile-prefetch trace through
    banked channels with finite queues, folded + scaled beyond the
    request cap. `engine` selects the replay engine (core.replay)."""
    name = "dram[cycle]"

    def __init__(self, core_index: int = 0, engine: Optional[str] = None):
        super().__init__(core_index)
        self.engine = engine

    def stalls(self, ctx: OpContext) -> None:
        cfg = ctx.cfg
        gran = 512
        n_req = max(1, int(ctx.dram_bytes) // gran)
        scale = max(1.0, n_req / _DRAM_REQ_CAP)
        n_sim = min(n_req, _DRAM_REQ_CAP)
        folds = max(1, int(np.ceil(n_sim / 32)))
        t, a, w = tile_prefetch_trace(n_sim * gran // folds, folds,
                                      ctx.comp / max(folds, 1) / scale, gran)
        res = simulate_dram(t, a, w, cfg.dram, gran, engine=self.engine)
        ctx.stall = float(res.stall_cycles) * scale
        ctx.dram_stats = dict(
            row_hits=int(res.row_hits), row_misses=int(res.row_misses),
            row_conflicts=int(res.row_conflicts),
            throughput_Bpc=float(res.throughput),
            mean_latency=float(jnp.mean(res.latency)),
            scaled_by=scale)


class TraceDramStage(DramStage):
    """Trace fidelity: the demand-request stream is synthesized from the
    mapping itself (`repro.trace` — tile schedule, double-buffered
    prefetch deadlines, per-dataflow operand walks, layout-aware
    addresses) and replayed through the cycle-accurate DRAM scan. Unlike
    `CycleDramStage`'s synthetic linear prefetch, row-buffer statistics
    here respond to dataflow, tiling and layout."""
    name = "dram[trace]"

    def __init__(self, core_index: int = 0, spec=None,
                 engine: Optional[str] = None):
        super().__init__(core_index)
        if spec is None:
            from ..trace.generator import DEFAULT_SPEC
            spec = DEFAULT_SPEC
        self.spec = spec
        self.engine = engine

    def stalls(self, ctx: OpContext) -> None:
        from ..trace.generator import gemm_trace_stats
        op, cfg = ctx.op, ctx.cfg
        core = self.core(ctx)
        dram = ctx.dram
        res = gemm_trace_stats(
            cfg.dataflow, op.M, op.N, op.K, core.rows, core.cols, ctx.comp,
            dram["dram_ifmap"], dram["dram_filter"],
            dram["dram_ofmap_writes"], dram["dram_ofmap_reads"],
            cfg.dram, cfg.memory.word_bytes, self.spec,
            engine=self.engine)
        ctx.stall = float(res["stall_cycles"])
        ctx.dram_stats = dict(
            row_hits=int(res["row_hits"]), row_misses=int(res["row_misses"]),
            row_conflicts=int(res["row_conflicts"]),
            row_hit_rate=float(res["row_hit_rate"]),
            throughput_Bpc=float(res["throughput_Bpc"]),
            mean_latency=float(res["mean_latency"]),
            scaled_by=float(res["scaled_by"]))


class LayoutStage(CoreStage):
    """On-chip bank-conflict slowdown on the streaming operand. Runs the
    shared static-shape model (`layout.streaming_layout_extra`) so the
    batched sweep kernel reproduces this stage bit-for-bit."""
    name = "layout"

    def apply(self, ctx: OpContext) -> None:
        cfg, op = ctx.cfg, ctx.op
        if not cfg.layout.enabled:
            return
        core = self.core(ctx)
        ctx.layout_extra = float(streaming_layout_extra(
            cfg.layout, core.rows, ctx.comp, max(1, op.N),
            cfg.memory.word_bytes, r_cap=core.rows))


class EnergyStage(Stage):
    """Finalize: x op.count, action counts, ERT energy lookup."""
    name = "energy"

    def apply(self, ctx: OpContext) -> None:
        op, cfg = ctx.op, ctx.cfg
        ctx.compute_total = ctx.comp * op.count
        ctx.stall_total = ctx.stall * op.count
        ctx.noc_total = ctx.noc_extra * op.count
        ctx.layout_total = ctx.layout_extra * op.count
        ctx.total = (ctx.compute_total + ctx.stall_total + ctx.noc_total
                     + ctx.layout_total)
        sram = ctx.sram
        ctx.sram_reads = float(sram["ifmap_reads"] + sram["filter_reads"]
                               + sram["ofmap_reads"]) * op.count
        ctx.sram_writes = float(sram["ofmap_writes"]) * op.count
        ctx.dram_bytes_total = ctx.dram_bytes * op.count
        counts = action_counts(
            cfg, cycles=ctx.compute_total, macs=op.macs,
            ifmap_reads=float(sram["ifmap_reads"]) * op.count,
            filter_reads=float(sram["filter_reads"]) * op.count,
            ofmap_writes=float(sram["ofmap_writes"]) * op.count,
            ofmap_reads=float(sram["ofmap_reads"]) * op.count,
            dram_bytes=ctx.dram_bytes_total,
            l2_reads=(ctx.dram_elems * op.count
                      if cfg.memory.l2_sram_bytes else 0.0))
        e = energy_pj(counts, ctx.ert)
        ctx.energy_total = float(e["total"])
        ctx.energy_by_action = {k: float(v) for k, v in e.items()
                                if k != "total"}


def build_pipeline(fidelity: str = "fast", *, core_index: int = 0,
                   trace_spec=None,
                   engine: Optional[str] = None) -> Tuple[Stage, ...]:
    """The canonical GEMM pipeline for a fidelity level.

    core_index: the core whose geometry every core-dependent stage
    (mapping, sparsity, sram, dram, layout) analyzes — heterogeneous
    meshes model one consistent member. trace_spec: optional
    `repro.trace.TraceSpec` for the trace fidelity. engine: DRAM replay
    engine for the cycle/trace stages (`core.replay.ENGINES`;
    None = default, i.e. the chunked bank-parallel replay).
    """
    if fidelity not in FIDELITIES:
        raise ValueError(f"fidelity must be one of {FIDELITIES}, "
                         f"got {fidelity!r}")
    if fidelity == "cycle":
        dram: DramStage = CycleDramStage(core_index, engine)
    elif fidelity == "trace":
        dram = TraceDramStage(core_index, trace_spec, engine)
    else:
        dram = FastDramStage(core_index)
    from ..noc.stage import NocStage    # lazy: noc depends on core.stages
    return (MappingStage(core_index), PartitionStage(),
            SparsityStage(core_index), SramStage(core_index),
            NocStage(core_index), dram, LayoutStage(core_index),
            EnergyStage())


def pipeline_engine(pipeline: Sequence[Stage]) -> str:
    """Resolved runtime replay-engine label of a pipeline's DRAM stage.

    '' for the fast model (it replays nothing); otherwise the label
    `core.replay.resolve_engine_runtime` gives for the stage's engine —
    including the off-TPU resolution of "pallas" to "pallas:twin" /
    "pallas:interpret", so reports record what actually ran, never the
    requested name.
    """
    from . import replay as _rp
    for s in pipeline:
        if isinstance(s, (CycleDramStage, TraceDramStage)):
            return _rp.resolve_engine_runtime(s.engine)
    return ""


def resolve_sparsity(cfg: AcceleratorConfig, op: Op) -> SparsityConfig:
    """Per-op N:M override (layer-wise sparsity ratios)."""
    sp = cfg.sparsity
    if op.sparsity_nm is not None:
        sp = SparsityConfig(enabled=True, n=op.sparsity_nm[0],
                            m=op.sparsity_nm[1], row_wise=sp.row_wise,
                            representation=sp.representation)
    return sp


def run_gemm_pipeline(cfg: AcceleratorConfig, op: Op,
                      pipeline: Sequence[Stage],
                      ert: ERT = DEFAULT_ERT) -> OpContext:
    ctx = OpContext(cfg=cfg, op=op, ert=ert, sp=resolve_sparsity(cfg, op))
    for stage in pipeline:
        stage.apply(ctx)
    return ctx


def run_vector(cfg: AcceleratorConfig, op: Op,
               ert: ERT = DEFAULT_ERT) -> OpContext:
    """Vector ops bypass the array pipeline and run on the SIMD unit.

    Like the gemm path, every component — cycles, traffic, action counts —
    scales linearly with `op.count`.
    """
    core = cfg.cores[0]
    wb = cfg.memory.word_bytes
    ctx = OpContext(cfg=cfg, op=op, ert=ert, sp=cfg.sparsity)
    cyc = float(dfm.simd_cycles(op.vector_elems, core.simd_lanes,
                                core.simd_latency)) * op.count
    elems = op.vector_elems * op.count
    ctx.comp = cyc
    ctx.compute_total = cyc
    ctx.total = cyc
    ctx.sram_reads = elems
    ctx.sram_writes = elems
    ctx.dram_bytes_total = elems * wb
    counts = action_counts(cfg, cycles=cyc, macs=0.0,
                           ifmap_reads=elems, filter_reads=0.0,
                           ofmap_writes=elems, ofmap_reads=0.0,
                           dram_bytes=ctx.dram_bytes_total)
    e = energy_pj(counts, ert)
    ctx.energy_total = float(e["total"])
    ctx.energy_by_action = {k: float(v) for k, v in e.items()
                            if k != "total"}
    return ctx


# --------------------------------------------------------------------------
# Traced twins: the same stage math on jnp arrays (vmap/pjit-safe).
# --------------------------------------------------------------------------

_NO_SPILL_BYTES = 1 << 62     # "infinite" psum SRAM: legacy traced semantics


def traced_memory(sram_elems, word_bytes=2, *, ifmap_elems=None,
                  filter_elems=None, ofmap_elems=None,
                  l2_bytes=0) -> MemoryConfig:
    """A MemoryConfig whose fields may be traced arrays. With only
    `sram_elems`, reproduces the legacy traced model: both operand SRAMs
    sized to sram_elems, psums never spill."""
    wb = word_bytes
    return MemoryConfig(
        ifmap_sram_bytes=(ifmap_elems if ifmap_elems is not None
                          else sram_elems) * wb,
        filter_sram_bytes=(filter_elems if filter_elems is not None
                           else sram_elems) * wb,
        ofmap_sram_bytes=(ofmap_elems * wb if ofmap_elems is not None
                          else _NO_SPILL_BYTES),
        l2_sram_bytes=l2_bytes, word_bytes=wb)


def traced_gemm_stats(dataflow: str, M, N, K, R, C, mem: MemoryConfig,
                      bw_bytes_per_cycle) -> Dict[str, jnp.ndarray]:
    """mapping + sram + dram(fast) stages, fully traced. Every argument
    except `dataflow` may be a jnp array; `mem` fields may be arrays."""
    comp = dfm.compute_cycles(dataflow, M, N, K, R, C)
    util = dfm.pe_utilization(dataflow, M, N, K, R, C)
    sram = dfm.sram_traffic(dataflow, M, N, K, R, C)
    dram = dfm.dram_traffic(dataflow, M, N, K, R, C, mem)
    dram_elems = (dram["dram_ifmap"] + dram["dram_filter"]
                  + dram["dram_ofmap_writes"] + dram["dram_ofmap_reads"])
    dram_bytes = dram_elems * mem.word_bytes
    stall = dfm.dram_stall_cycles_simple(dram_bytes, comp,
                                         bw_bytes_per_cycle)
    return dict(compute_cycles=comp, stall_cycles=stall,
                total_cycles=comp + stall, utilization=util,
                dram_bytes=dram_bytes, dram_elems=dram_elems, **sram)


def traced_vector_stats(elems, lanes, latency, word_bytes) -> Dict[str, jnp.ndarray]:
    """SIMD sidecar, traced (per instance; callers scale by count)."""
    cyc = dfm.simd_cycles(elems, lanes, latency)
    return dict(compute_cycles=cyc, dram_bytes=elems * word_bytes)


def traced_energy_counts(*, R, C, mem: MemoryConfig, cycles, macs,
                         ifmap_reads, filter_reads, ofmap_writes,
                         ofmap_reads, dram_bytes, l2_reads=0.0,
                         row_bytes: int = 64, pes=None,
                         dim32=None) -> Dict[str, jnp.ndarray]:
    """The energy stage's action counts with array-valued config fields;
    identical formulas to `energy.action_counts` (shared core). `mem` must
    carry real SRAM sizes (not the no-spill sentinel). pes/dim32 default
    to the single-core R x C values; multi-core designs pass the summed
    PE count and the mesh-wide max dimension (what `action_counts` derives
    from a concrete config)."""
    sram_kib = (mem.ifmap_sram_bytes + mem.filter_sram_bytes
                + mem.ofmap_sram_bytes) / 1024.0
    if pes is None:
        pes = R * C
    if dim32 is None:
        dim32 = jnp.maximum(R, C) / 32.0
    return action_counts_raw(
        pes=pes, dim32=dim32, sram_kib=sram_kib,
        word_bytes=mem.word_bytes, cycles=cycles, macs=macs,
        ifmap_reads=ifmap_reads, filter_reads=filter_reads,
        ofmap_writes=ofmap_writes, ofmap_reads=ofmap_reads,
        dram_bytes=dram_bytes, l2_reads=l2_reads, row_bytes=row_bytes)


# --------------------------------------------------------------------------
# The full-pipeline traced twin: mapping -> partition -> sparsity -> sram ->
# dram[fast] -> layout with every feature expressed as data (jnp.where) or
# a static kernel-flavor parameter — what lets `repro.api` batch arbitrary
# mixed dense/sparse/layout/multicore design grids in one jit/vmap.
# --------------------------------------------------------------------------

def traced_comp_traffic(dataflow: str, M, N, K, R, C, mem: MemoryConfig, *,
                        sparsity: Optional[Dict] = None,
                        multicore: Optional[Dict] = None):
    """Effective compute cycles + (shrunk) SRAM/DRAM traffic, traced.

    Mirrors the stage pipeline's feature composition exactly: the
    partition stage overrides single-core compute when the design has
    multiple cores, and the sparsity stage overrides both (paper
    semantics: sparse runs use the single-core compressed stream).

    sparsity:  {'en', 'n', 'm', 'rw'} traced arrays (en/rw are 0/1
               selectors — no Python branching on them) plus the static
               'representation' string.
    multicore: {'rows', 'cols', 'hops'} per-core arrays (core axis last,
               length Pr*Pc), traced 'nop' cycles-per-hop, and static
               'Pr'/'Pc' grid shape.

    Returns (comp, sram dict, dram dict, filter_shrink).
    """
    comp = dfm.compute_cycles(dataflow, M, N, K, R, C)
    if multicore is not None:
        comp = best_multicore_cycles_model(
            dataflow, M, N, K, multicore["rows"], multicore["cols"],
            multicore["hops"], multicore["nop"], multicore["Pr"],
            multicore["Pc"])
    shrink = jnp.float32(1.0)
    if sparsity is not None:
        en, n, m, rw = (sparsity["en"], sparsity["n"], sparsity["m"],
                        sparsity["rw"])
        comp_sp = sparse_compute_cycles_model(dataflow, M, N, K, R, C,
                                              n, m, rw, enabled=en)
        comp = jnp.where(en, comp_sp, comp)
        orig, _, _, total = storage_bytes_model(
            M, K, n, m, rw, sparsity["representation"], mem.word_bytes,
            enabled=en)
        shrink = total / jnp.maximum(orig, 1.0)
    sram = dfm.sram_traffic(dataflow, M, N, K, R, C)
    sram = dict(sram, filter_reads=sram["filter_reads"] * shrink)
    dram = dfm.dram_traffic(dataflow, M, N, K, R, C, mem)
    dram = dict(dram, dram_filter=dram["dram_filter"] * shrink)
    return comp, sram, dram, shrink


def traced_op_stats(dataflow: str, M, N, K, R, C, mem: MemoryConfig,
                    bw_bytes_per_cycle, *,
                    sparsity: Optional[Dict] = None,
                    multicore: Optional[Dict] = None,
                    layout_sd=None) -> Dict[str, jnp.ndarray]:
    """Traced twin of the full fast-fidelity gemm pipeline (per op
    instance; callers scale by count). `layout_sd`: each op's layout
    slowdown curve (`layout.streaming_slowdown_curve`; op axes leading,
    the window last), or None to skip the layout stage — layout on/off
    is a static kernel flavor (the Study plan groups enabled and disabled
    cells separately, so disabled groups pay nothing). See
    `traced_comp_traffic` for the sparsity/multicore parameter shapes."""
    comp, sram, dram, shrink = traced_comp_traffic(
        dataflow, M, N, K, R, C, mem, sparsity=sparsity,
        multicore=multicore)
    dram_elems = (dram["dram_ifmap"] + dram["dram_filter"]
                  + dram["dram_ofmap_writes"] + dram["dram_ofmap_reads"])
    dram_bytes = dram_elems * mem.word_bytes
    stall = dfm.dram_stall_cycles_simple(dram_bytes, comp,
                                         bw_bytes_per_cycle)
    extra = jnp.zeros_like(comp)
    if layout_sd is not None:
        extra = streaming_window_extra(layout_sd, comp)
    return dict(compute_cycles=comp, stall_cycles=stall,
                layout_extra_cycles=extra, dram_bytes=dram_bytes,
                dram_elems=dram_elems, filter_shrink=shrink, **sram)
