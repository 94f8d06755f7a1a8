"""Dataflow-aware DRAM demand-trace synthesis (SCALE-Sim's defining output).

Where `core.dram.tile_prefetch_trace` drives the cycle-accurate DRAM scan
with a *synthetic* linear stream, this module synthesizes the demand
request stream (issue cycle, address, is_write) directly from the mapping:

  1. the tile schedule — `map_gemm`/`fold_counts` give the fold grid
     (fr x fc tiles) and the per-tile compute window `comp / (fr * fc)`;
  2. a double-buffered prefetch scheduler — reads for tile t are posted in
     a burst at the start of tile t-1's compute window (both buffers are
     filled up front for tiles 0/1), so small request queues block the
     producer immediately while large queues absorb the burst (Fig. 10);
  3. per-dataflow operand walks — the order each operand region is
     traversed (stationary loads are sequential, streaming operands walk
     the reduction dim fastest, psum drains differ between OS and WS/IS);
  4. layout-aware addressing — `core.layout.operand_linear_index` maps
     walk coordinates through row/column-major or tiled DRAM layouts, so
     the same dataflow produces genuinely different row-buffer behavior
     per layout (the SCALE-Sim TPU validation axis).

Everything is fixed-shape and traced: a `TraceSpec.cap`-sized request
buffer with a `valid` mask and a real-valued `scale` (fold + scale beyond
the cap, the same trick `CycleDramStage` uses) makes the generators
vmappable, which is what lets `Simulator.sweep` batch trace-fidelity
design points instead of falling back to the per-op Python loop.

The stream is built region by region (ifmap, filter, ofmap reads, ofmap
writes) and then ordered by issue time with one batched stable sort
that carries the payload: ties keep stream order, exactly as
`np.argsort(kind="stable")`, and masked slots go last. A 4-way
binary-search merge of the regions' sorted runs gives the same order,
but on the TPU it runs as `while` loops of dynamic gathers; it took two
thirds of a ViT-base trace sweep's device time on a TPU v5e, where the
sort takes under 1 %.

Conservation contract: `sum(valid) * gran_bytes * scale` equals the
capacity-model byte total from `dataflow.dram_traffic` exactly — for
self-scaled streams. A caller-supplied common scale (the contention
path) quantizes each region's bytes to whole model requests, so tiny
cores sharing a big core's scale carry up to one request's worth
(`scale * gran_bytes`) of over-modeling per region.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core import dataflow as dfm
from ..core.accelerator import AcceleratorConfig, DramConfig
from ..core.dram import simulate_dram
from ..core.layout import operand_linear_index
from ..core.workloads import Op

# One address region per operand (ifmap / filter / ofmap). 32 MiB spacing
# keeps regions in disjoint DRAM rows while staying inside int32 with the
# per-core offsets of the contention path (which guards the <= 16-core
# limit of the 2^31 shared address space explicitly).
REGION_SPAN = 1 << 25
# Sort key for invalid (masked) slots. A Python float: a jnp scalar here
# would initialize a JAX backend (and take the chip) on import.
_BIG_T = 1e15
# Compressed streams are sampled in contiguous runs of this many granules
# (64 granules x 64 B = two 2 KiB DRAM rows) so layout-driven row-buffer
# locality survives stream compression.
_SAMPLE_RUN = 64


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """Static knobs of the trace generator (hashable: jit/vmap-safe).

    cap:          fixed request-buffer size; streams beyond it are folded
                  and the resulting stall rescaled (`scale`).
    gran_bytes:   bytes per demand request (DRAM burst granularity).
    layout:       DRAM-side operand layout — 'row' | 'col' | 'tiled'
                  (see core.layout.operand_linear_index) or 'strided'
                  (address = stream position * stride_elems, the
                  row-thrash stress pattern).
    """
    cap: int = 4096
    gran_bytes: int = 64
    layout: str = "row"
    tile_r: int = 32
    tile_c: int = 32
    stride_elems: int = 1

    def __post_init__(self):
        if self.cap < 1:
            raise ValueError(f"trace cap must be >= 1, got {self.cap}")
        if self.gran_bytes < 1:
            raise ValueError(
                f"gran_bytes must be >= 1, got {self.gran_bytes}")
        if self.layout not in ("row", "col", "tiled", "strided"):
            raise ValueError(
                "trace layout must be one of "
                f"('row', 'col', 'tiled', 'strided'), got {self.layout!r}")
        if self.tile_r < 1 or self.tile_c < 1:
            raise ValueError(
                f"trace tile must be >= 1x1, got "
                f"{self.tile_r}x{self.tile_c}")
        if self.stride_elems < 1:
            raise ValueError(
                f"stride_elems must be >= 1, got {self.stride_elems}")


# The one default spec shared by every entry point (per-op stage, batched
# sweep, contention) so spec=None means the same stream everywhere.
DEFAULT_SPEC = TraceSpec()

# Regions along the request-index axis (interleaving in *time* is done by
# the issue schedule + sort, not by this ordering).
R_IFMAP, R_FILTER, R_OFMAP_RD, R_OFMAP_WR = 0, 1, 2, 3

# Per (dataflow, region): does the fast (innermost) walk dim run down the
# operand's rows?  Operand shapes: X = K x N, W = M x K, O = M x N.
#   ws: X streams a column per cycle (k fast); W loads are sequential per
#       stationary fold (k fast along W's columns); psums drain m-fast.
#   is: X stationary load (k fast); W streams k-fast; outputs drain n-fast.
#   os: both operands stream k-fast; the stationary O drains n-fast
#       (row-major) at tile end.
_FAST_IS_ROW = {
    ("ws", R_IFMAP): True, ("ws", R_FILTER): False, ("ws", R_OFMAP_WR): True,
    ("is", R_IFMAP): True, ("is", R_FILTER): False, ("is", R_OFMAP_WR): False,
    ("os", R_IFMAP): True, ("os", R_FILTER): False, ("os", R_OFMAP_WR): False,
}


def _modmul(j, a, L):
    """mod(j * a, L) without forming the full product.

    Large-GEMM streams push j * a past 1e11, where float32's integer
    resolution (2^24) exceeds coordinate-sized moduli and a direct
    jnp.mod collapses the operand walk (inverting the layout-sensitive
    row-buffer statistics this module exists to produce). Splitting the
    exact small integer j into 6-bit halves keeps every intermediate
    near 64 * L, where f32 arithmetic is exact for dimension-sized L
    (< 2^18). For the strided layout's span-sized modulus (2^24) the
    residual rounding is up to ~64 elements of address noise — below
    the burst-count scale the stride statistics are measured at.
    """
    j_hi = jnp.floor(j / 64.0)
    j_lo = j - 64.0 * j_hi
    a1 = jnp.mod(a, L)
    a64 = jnp.mod(64.0 * a1, L)
    return jnp.mod(j_lo * a1 + j_hi * a64, L)


def _stream_in_region_order(dataflow: str, M, N, K, R, C, comp,
                            ifmap_elems, filter_elems, ofmap_write_elems,
                            ofmap_read_elems, word_bytes, spec, scale):
    """`gemm_request_stream`'s (t_issue, addr, is_write, valid, scale)
    before the sort: slots in region order (ifmap, filter, ofmap reads,
    ofmap writes), each region's issue times nondecreasing."""
    f32 = jnp.float32
    wb = word_bytes
    gran = spec.gran_bytes
    cap = spec.cap

    region_bytes = jnp.stack([f32(1.0) * ifmap_elems * wb,
                              f32(1.0) * filter_elems * wb,
                              f32(1.0) * ofmap_read_elems * wb,
                              f32(1.0) * ofmap_write_elems * wb])
    total_bytes = jnp.sum(region_bytes)
    n_total = total_bytes / gran                      # fractional requests
    if scale is None:
        n_model = jnp.minimum(f32(cap), jnp.maximum(1.0, jnp.ceil(n_total)))
        scale = n_total / n_model
    else:
        scale = f32(1.0) * scale
        n_model = jnp.minimum(
            f32(cap), jnp.maximum(1.0, jnp.ceil(
                n_total / jnp.maximum(scale, 1e-9))))

    # region boundaries in model-request units (sum == n_model when the
    # op picked its own scale)
    safe_scale = jnp.maximum(scale, 1e-9)
    r_model = region_bytes / gran / safe_scale        # (4,)
    edges = jnp.cumsum(r_model)
    starts = jnp.concatenate([jnp.zeros(1, f32), edges[:-1]])

    i = jnp.arange(cap, dtype=f32)
    valid = i < n_model
    region = jnp.sum((i[:, None] >= edges[None, :]).astype(jnp.int32),
                     axis=1)
    region = jnp.clip(region, 0, 3)
    j = jnp.maximum(0.0, i - starts[region])          # index within region

    # ---- operand walk -> coordinates -> layout -> address ------------------
    Mf, Nf, Kf = f32(1.0) * M, f32(1.0) * N, f32(1.0) * K
    rows_of = jnp.stack([Kf, Mf, Mf, Mf])             # X:KxN W:MxK O:MxN
    cols_of = jnp.stack([Nf, Kf, Nf, Nf])
    fast_is_row = jnp.asarray(
        [_FAST_IS_ROW[(dataflow, R_IFMAP)],
         _FAST_IS_ROW[(dataflow, R_FILTER)],
         _FAST_IS_ROW[(dataflow, R_OFMAP_WR)],       # spill reads walk like
         _FAST_IS_ROW[(dataflow, R_OFMAP_WR)]])      # the write-back stream

    rows_r = rows_of[region]
    cols_r = cols_of[region]
    fr_row = fast_is_row[region]
    fast_len = jnp.maximum(1.0, jnp.where(fr_row, rows_r, cols_r))
    slow_len = jnp.maximum(1.0, jnp.where(fr_row, cols_r, rows_r))

    # stream element position. The stream is compressed by `scale`; so
    # that row-buffer statistics stay meaningful under compression, the
    # model requests sample the real stream in contiguous runs of
    # _SAMPLE_RUN granules (run starts stride by step * _SAMPLE_RUN) —
    # the local DRAM-row locality the layout determines survives even
    # when one model request stands in for megabytes of real traffic.
    # At scale == 1 this degenerates to the exact uncompressed walk.
    # Coordinates are modular products via _modmul (a plain j * step
    # product overflows f32 integer resolution at LM scale).
    step = safe_scale * gran / wb                     # elements/request
    run = f32(_SAMPLE_RUN)
    j_b = jnp.floor(j / run)                          # run id
    j_i = j - run * j_b                               # granule within run
    g_el = f32(gran) / wb                             # elements/granule
    f = jnp.mod(_modmul(j_b, step * run, fast_len) + j_i * g_el, fast_len)
    lines = (_modmul(j_b, step * run / fast_len, slow_len)
             + j_i * g_el / fast_len)
    s = jnp.mod(jnp.floor(lines), slow_len)           # refetches wrap
    row = jnp.where(fr_row, f, s)
    col = jnp.where(fr_row, s, f)

    if spec.layout == "strided":
        # defined directly on the stream position (no run-sampling): the
        # stress pattern's contract is hit rate monotone in the stride,
        # which run-local contiguity would wash out
        idx = _modmul(j, step * spec.stride_elems, f32(REGION_SPAN // wb))
    else:
        idx = operand_linear_index(row, col, rows_r, cols_r,
                                   order=spec.layout,
                                   tile_r=spec.tile_r, tile_c=spec.tile_c)
        idx = jnp.mod(idx, f32(REGION_SPAN // wb))
    # exact integer address math from here on (channel/bank/row decode in
    # simulate_dram must not see float rounding). Spill reads share the
    # write-back stream's region — they read the same ofmap buffer, so a
    # spilled psum can row-hit the row its own write-back opened.
    addr_region = jnp.minimum(region, R_OFMAP_RD).astype(jnp.int32)
    addr = (addr_region * jnp.int32(REGION_SPAN)
            + jnp.floor(idx).astype(jnp.int32) * jnp.int32(wb))

    # ---- double-buffered prefetch schedule ---------------------------------
    Sr, Sc, T = dfm.map_gemm(dataflow, M, N, K)
    fr, fc = dfm.fold_counts(Sr, Sc, R, C)
    n_tiles = jnp.maximum(1.0, f32(1.0) * fr * fc)
    tile_cyc = jnp.maximum(1.0, f32(1.0) * comp / n_tiles / safe_scale)

    q = jnp.maximum(r_model[region] / n_tiles, 1e-9)  # requests/tile/region
    pos = j / q
    tau = jnp.clip(jnp.floor(pos), 0.0, n_tiles - 1.0)
    frac = jnp.clip(pos - tau, 0.0, 1.0)

    is_write = region == R_OFMAP_WR
    t_read = jnp.maximum(0.0, tau - 1.0) * tile_cyc   # prefetch burst at
    #                                                   window start
    if dataflow == "os":
        # stationary outputs drain in a burst when the tile retires
        t_write = (tau + 1.0) * tile_cyc
    else:
        # ws/is psum write-backs interleave with the streaming compute
        t_write = (tau + frac) * tile_cyc
    t_spill = (tau + frac) * tile_cyc                 # psum read-backs
    t = jnp.where(is_write, t_write,
                  jnp.where(region == R_OFMAP_RD, t_spill, t_read))

    return t, addr, is_write, valid, scale


@partial(jax.jit, static_argnames=("dataflow", "word_bytes", "spec"))
def gemm_request_stream(dataflow: str, M, N, K, R, C, comp,
                        ifmap_elems, filter_elems, ofmap_write_elems,
                        ofmap_read_elems, word_bytes: int = 2,
                        spec: TraceSpec = TraceSpec(), scale=None):
    """Synthesize the demand-request stream for one GEMM op.

    M/N/K/R/C/comp and the four region element counts (from
    `dataflow.dram_traffic`, after any sparsity shrink) may be traced
    arrays; `dataflow`, `word_bytes` and `spec` are static.

    scale: optional stream-compression factor override. The multi-core
    contention path passes one common scale so every core's stream is
    compressed coherently; by default the op picks its own.

    Returns (t_issue, addr, is_write, valid, scale) — arrays of shape
    (spec.cap,), sorted by issue time, plus the scalar compression factor
    (model stall * scale estimates the real stall).
    """
    t, addr, is_write, valid, scale = _stream_in_region_order(
        dataflow, M, N, K, R, C, comp, ifmap_elems, filter_elems,
        ofmap_write_elems, ofmap_read_elems, word_bytes, spec, scale)
    # ---- sort by issue time (invalid slots last) ---------------------------
    # One stable sort that carries the payload, with no gathers and no
    # loop. On a TPU v5e it orders a block of 336 streams in ~1 ms more
    # than generating them takes; the binary-search merge it replaced took
    # ~1.5 s, and sorting an index then gathering the payload ~60 ms.
    # `+ 0.0` maps -0.0 to +0.0, so the sort's total order ranks equal
    # times as `<` does.
    key = jnp.where(valid, t, _BIG_T) + 0.0
    _, t, addr, is_write, valid = lax.sort(
        (key, t, addr, is_write, valid), num_keys=1, is_stable=True)
    return t, addr, is_write, valid, scale


@partial(jax.jit, static_argnames=("dataflow", "dram_cfg", "word_bytes",
                                   "spec", "engine"))
def gemm_trace_stats(dataflow: str, M, N, K, R, C, comp,
                     ifmap_elems, filter_elems, ofmap_write_elems,
                     ofmap_read_elems, dram_cfg: DramConfig,
                     word_bytes: int = 2,
                     spec: TraceSpec = TraceSpec(),
                     engine: str = None) -> Dict[str, jnp.ndarray]:
    """Generate the op's trace and run it through the cycle-accurate DRAM
    replay. Fully traced (vmappable over ops and design points). engine
    selects the replay engine (`core.replay.ENGINES`; None = default)."""
    t, addr, w, valid, scale = gemm_request_stream(
        dataflow, M, N, K, R, C, comp, ifmap_elems, filter_elems,
        ofmap_write_elems, ofmap_read_elems, word_bytes, spec)
    res = simulate_dram(t, addr, w, dram_cfg, spec.gran_bytes, valid=valid,
                        engine=engine)
    nval = jnp.maximum(1.0, jnp.sum(valid).astype(jnp.float32))
    refs = jnp.maximum(1, res.row_hits + res.row_misses + res.row_conflicts)
    return dict(
        stall_cycles=res.stall_cycles * scale,
        row_hits=res.row_hits, row_misses=res.row_misses,
        row_conflicts=res.row_conflicts,
        row_hit_rate=res.row_hits / refs,
        mean_latency=jnp.sum(res.latency) / nval,
        throughput_Bpc=res.throughput,
        bytes_modeled=res.bytes_moved * scale,
        scaled_by=scale)


# --------------------------------------------------------------------------
# Convenience (eager) entry points over an AcceleratorConfig
# --------------------------------------------------------------------------

def _op_regions(cfg: AcceleratorConfig, op: Op, core_index: int = 0):
    core = cfg.cores[core_index]
    dram = dfm.dram_traffic(cfg.dataflow, op.M, op.N, op.K,
                            core.rows, core.cols, cfg.memory)
    comp = dfm.compute_cycles(cfg.dataflow, op.M, op.N, op.K,
                              core.rows, core.cols)
    return core, comp, dram


def trace_op(cfg: AcceleratorConfig, op: Op, spec: TraceSpec = TraceSpec(),
             core_index: int = 0) -> Tuple[jnp.ndarray, ...]:
    """(t_issue, addr, is_write, valid, scale) for one op on `cfg`."""
    core, comp, dram = _op_regions(cfg, op, core_index)
    return gemm_request_stream(
        cfg.dataflow, op.M, op.N, op.K, core.rows, core.cols, comp,
        dram["dram_ifmap"], dram["dram_filter"], dram["dram_ofmap_writes"],
        dram["dram_ofmap_reads"], cfg.memory.word_bytes, spec)


def trace_op_stats(cfg: AcceleratorConfig, op: Op,
                   spec: TraceSpec = TraceSpec(),
                   core_index: int = 0,
                   engine: str = None) -> Dict[str, jnp.ndarray]:
    """Row-buffer / stall statistics of one op's generated trace."""
    core, comp, dram = _op_regions(cfg, op, core_index)
    return gemm_trace_stats(
        cfg.dataflow, op.M, op.N, op.K, core.rows, core.cols, comp,
        dram["dram_ifmap"], dram["dram_filter"], dram["dram_ofmap_writes"],
        dram["dram_ofmap_reads"], cfg.dram, cfg.memory.word_bytes, spec,
        engine=engine)
