"""repro.trace: dataflow-aware demand-trace generation + shared-DRAM
contention. Covers the ISSUE-2 contracts: byte conservation against
`dram_traffic`, layout/stride sensitivity of row-buffer statistics,
OS-vs-WS write-stream shape, vmappability, and the valid-mask semantics
of `simulate_dram`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram_traffic, simulate_dram, tpu_like_config
from repro.core.accelerator import (AcceleratorConfig, CoreConfig,
                                    DramConfig, MemoryConfig)
from repro.core.dataflow import map_gemm, unmap_gemm
from repro.core.dram import linear_trace
from repro.core.multicore import simulate_multicore_contention
from repro.core.workloads import Op, vit_base_linear
from repro.trace import (TraceSpec, gemm_trace_stats, trace_op,
                         trace_op_stats)
from repro.trace.generator import (R_OFMAP_WR, REGION_SPAN, _op_regions,
                                   _stream_in_region_order,
                                   gemm_request_stream)

SPEC = TraceSpec(cap=2048)


def _cfg(df="ws", sram_mb=0.5):
    return tpu_like_config(array=32, dataflow=df, sram_mb=sram_mb)


# ---- conservation ----------------------------------------------------------

@pytest.mark.parametrize("df", ["ws", "is", "os"])
def test_request_byte_conservation(df):
    """sum(valid) * gran * scale == dram_traffic byte total, exactly."""
    cfg = _cfg(df)
    op = Op("g", 384, 1500, 640)
    t, a, w, v, scale = trace_op(cfg, op, SPEC)
    dram = dram_traffic(df, op.M, op.N, op.K, 32, 32, cfg.memory)
    expect = float(sum(dram.values())) * cfg.memory.word_bytes
    got = float(jnp.sum(v)) * SPEC.gran_bytes * float(scale)
    assert got == pytest.approx(expect, rel=1e-5)


def test_stream_sorted_and_fixed_shape():
    t, a, w, v, scale = trace_op(_cfg(), Op("g", 256, 512, 256), SPEC)
    assert t.shape == a.shape == w.shape == v.shape == (SPEC.cap,)
    tv = np.asarray(t)[np.asarray(v)]
    assert (np.diff(tv) >= 0).all()
    assert a.dtype == jnp.int32 and (np.asarray(a) >= 0).all()


# ---- ordering: one stable sort, bit-identical to the merge it replaced -----

@jax.jit
def _frozen_merge_sort_order(key, region):
    """The stream ordering the generator used before its stable sort,
    frozen here as the contract: a 4-way stable merge of the regions'
    sorted runs, each element's position its within-region offset plus
    a binary-search count per other region (`<=` against earlier
    regions, `<` against later ones)."""
    cap = key.shape[-1]
    ii = jnp.arange(cap, dtype=jnp.int32)
    rank = jnp.zeros(key.shape, jnp.int32)
    for r in range(4):
        s = jnp.searchsorted(region, r, side="left").astype(jnp.int32)
        e = jnp.searchsorted(region, r + 1, side="left").astype(jnp.int32)
        seg = jnp.where(ii < s, -jnp.inf, jnp.where(ii >= e, jnp.inf, key))
        lo = jnp.searchsorted(seg, key, side="left").astype(jnp.int32) - s
        hi = jnp.searchsorted(seg, key, side="right").astype(jnp.int32) - s
        n_r = e - s
        contrib = jnp.where(region == r, ii - s,
                            jnp.where(region > r, jnp.clip(hi, 0, n_r),
                                      jnp.clip(lo, 0, n_r)))
        rank = rank + contrib
    return jnp.zeros(key.shape, jnp.int32).at[rank].set(ii,
                                                        unique_indices=True)


_unordered_stream = jax.jit(_stream_in_region_order,
                            static_argnums=(0, 11, 12))

# (M, N, K, array, fills the cap): the four ViT-base GEMMs at arrays 32
# and 128, a large GEMM, and a small one that leaves a masked tail
_ORDER_GEMMS = ([(op.M, op.N, op.K, arr, True)
                 for op in vit_base_linear()[:4] for arr in (32, 128)]
                + [(4096, 1536, 4608, 64, True), (32, 48, 40, 32, False)])


@pytest.mark.parametrize("gemm", _ORDER_GEMMS,
                         ids=[f"{m}x{n}x{k}-a{a}"
                              for m, n, k, a, _ in _ORDER_GEMMS])
@pytest.mark.parametrize("layout", ["row", "col", "tiled", "strided"])
@pytest.mark.parametrize("df", ["ws", "is", "os"])
def test_stream_order_is_stable_argsort_and_old_merge(df, layout, gemm):
    """The sorted stream is the region-order stream permuted by
    np.argsort(kind="stable") of its masked issue times — the order the
    merge gave and the reference uses — bit for bit, ties included."""
    M, N, K, arr, fills = gemm
    cfg = tpu_like_config(array=arr, dataflow=df, sram_mb=4.0)
    spec = TraceSpec(layout=layout, stride_elems=3)
    core, comp, dram = _op_regions(cfg, Op("g", M, N, K))
    args = (df, M, N, K, core.rows, core.cols, comp, dram["dram_ifmap"],
            dram["dram_filter"], dram["dram_ofmap_writes"],
            dram["dram_ofmap_reads"], cfg.memory.word_bytes, spec)
    got = [np.asarray(x) for x in gemm_request_stream(*args)[:4]]
    t, a, w, v, _ = _unordered_stream(*args, None)
    t, a, w, v = (np.asarray(x) for x in (t, a, w, v))
    assert bool(v.all()) == fills and v[0]

    key = np.where(v, t, 1e15)
    region = np.where(w, R_OFMAP_WR, a // REGION_SPAN)
    assert (np.diff(region) >= 0).all()
    order = np.argsort(key, kind="stable")
    merged = np.asarray(_frozen_merge_sort_order(jnp.asarray(key),
                                                 jnp.asarray(region)))
    np.testing.assert_array_equal(merged, order)
    for g, x in zip(got, (t, a, w, v)):
        assert g.dtype == x.dtype
        assert g.tobytes() == x[order].tobytes()

    # ties: equal valid issue times in two regions, which only the
    # stability rule orders
    tv, rv = t[v], region[v]
    assert any(np.isin(tv[rv == r], tv[rv != r]).any() for r in range(4))


def test_stream_ordering_is_one_sort_without_loops():
    """The ordering step lowers to one sort and no while loop: on a TPU
    v5e the binary-search merge's gather loops took ~1.5 s per block of
    336 streams, the sort ~1 ms."""
    cfg = tpu_like_config(array=32, dataflow="ws", sram_mb=4.0)
    core, comp, dram = _op_regions(cfg, Op("g", 2304, 197, 768))
    hlo = gemm_request_stream.lower(
        "ws", 2304, 197, 768, core.rows, core.cols, comp,
        dram["dram_ifmap"], dram["dram_filter"], dram["dram_ofmap_writes"],
        dram["dram_ofmap_reads"], cfg.memory.word_bytes,
        TraceSpec()).as_text()
    assert hlo.count("stablehlo.sort") == 1
    assert "stablehlo.while" not in hlo


# ---- layout / stride sensitivity -------------------------------------------

def test_layouts_change_row_buffer_behavior():
    """Row/column-major and tiled layouts must produce genuinely
    different row-buffer statistics for the same dataflow walk."""
    cfg = _cfg("ws")
    op = Op("g", 384, 1500, 640)
    rates = {lay: float(trace_op_stats(cfg, op,
                                       TraceSpec(cap=2048, layout=lay)
                                       )["row_hit_rate"])
             for lay in ("row", "col", "tiled")}
    assert len({round(r, 4) for r in rates.values()}) == 3
    # ws streams X down columns: column-major storage is the friendly one
    assert rates["col"] > rates["row"]


def test_layout_sensitivity_survives_compression():
    """LM-scale ops compress the stream by ~1e6; the contiguous-run
    sampling must keep layout-driven row-buffer ordering (col-major stays
    row-local, row-major thrashes) instead of collapsing to f32 rounding
    artifacts of the huge stream positions."""
    cfg = _cfg("ws")
    op = Op("g", 4096, 32768, 8192)
    rates = {lay: float(trace_op_stats(cfg, op,
                                       TraceSpec(cap=2048, layout=lay)
                                       )["row_hit_rate"])
             for lay in ("row", "col")}
    assert rates["col"] > rates["row"] + 0.05


def test_row_hit_rate_monotone_in_stride():
    cfg = _cfg("ws")
    op = Op("g", 384, 1500, 640)
    rates = [float(trace_op_stats(
        cfg, op, TraceSpec(cap=2048, layout="strided", stride_elems=s)
        )["row_hit_rate"]) for s in (1, 4, 16, 64)]
    assert all(a >= b - 1e-9 for a, b in zip(rates, rates[1:]))
    assert rates[0] > rates[-1]                 # and strictly falls overall


# ---- dataflow-dependent stream shape ---------------------------------------

def test_write_stream_shape_os_vs_ws():
    """OS drains the stationary output in per-tile bursts; WS writes back
    psums interleaved with the stream — the write issue-time shapes must
    differ."""
    burst = {}
    for df in ("ws", "os"):
        cfg = _cfg(df, sram_mb=0.25)
        t, a, w, v, _ = trace_op(cfg, Op("g", 128, 512, 256), SPEC)
        wt = np.asarray(t)[np.asarray(w & v)]
        assert wt.size > 100                    # both have real write streams
        burst[df] = wt.size / np.unique(wt).size   # writes per issue slot
    assert burst["os"] > 2 * burst["ws"]        # OS drains in tile bursts


def test_dataflows_produce_different_address_streams():
    op = Op("g", 384, 1500, 640)
    addrs = {df: np.asarray(trace_op(_cfg(df), op, SPEC)[1])
             for df in ("ws", "os")}
    assert not np.array_equal(addrs["ws"], addrs["os"])


# ---- vmappability (the sweep-batching contract) ----------------------------

def test_generator_vmaps_over_gemm_dims():
    cfg = _cfg("ws")
    spec = TraceSpec(cap=512)
    mem = cfg.memory

    def stats(M, N, K):
        dr = dram_traffic("ws", M, N, K, 32, 32, mem)
        comp = (2 * 32 + 32 + N - 2) * 1.0       # ws: T = N (single fold ok)
        return gemm_trace_stats("ws", M, N, K, 32, 32, comp,
                                dr["dram_ifmap"], dr["dram_filter"],
                                dr["dram_ofmap_writes"],
                                dr["dram_ofmap_reads"], cfg.dram,
                                mem.word_bytes, spec)

    M = jnp.asarray([128.0, 256.0, 384.0])
    N = jnp.asarray([512.0, 1024.0, 197.0])
    K = jnp.asarray([256.0, 640.0, 768.0])
    out = jax.vmap(stats)(M, N, K)
    assert out["stall_cycles"].shape == (3,)
    assert bool(jnp.all(jnp.isfinite(out["stall_cycles"])))
    assert bool(jnp.all(out["stall_cycles"] >= 0))


# ---- simulate_dram valid mask ----------------------------------------------

def test_simulate_dram_valid_mask_matches_unpadded():
    t, a, w = linear_trace(512, issue_gap=0.5)
    cfg = DramConfig(channels=2)
    full = simulate_dram(t, a, w, cfg)
    pad = 256
    tp = jnp.concatenate([t, jnp.full((pad,), 1e12)])
    ap = jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
    wp = jnp.concatenate([w, jnp.zeros((pad,), bool)])
    vp = jnp.arange(512 + pad) < 512
    masked = simulate_dram(tp, ap, wp, cfg, valid=vp)
    assert float(masked.stall_cycles) == pytest.approx(
        float(full.stall_cycles), abs=1e-3)
    assert int(masked.row_hits) == int(full.row_hits)
    assert int(masked.row_conflicts) == int(full.row_conflicts)
    assert float(masked.bytes_moved) == pytest.approx(
        float(full.bytes_moved))


# ---- mapping inverses -------------------------------------------------------

@pytest.mark.parametrize("df", ["ws", "is", "os"])
def test_unmap_gemm_inverts_map_gemm(df):
    M, N, K = 384, 1500, 640
    assert unmap_gemm(df, *map_gemm(df, M, N, K)) == (M, N, K)


# ---- multi-core shared-DRAM contention -------------------------------------

_MEM = MemoryConfig(ifmap_sram_bytes=1 << 17, filter_sram_bytes=1 << 17,
                    ofmap_sram_bytes=1 << 17)


def _mesh_cfg(channels):
    return AcceleratorConfig(cores=(CoreConfig(rows=32, cols=32),),
                             mesh_rows=2, mesh_cols=1, memory=_MEM,
                             dram=DramConfig(channels=channels))


def test_contention_shared_channels_inflates_stalls():
    r = simulate_multicore_contention(_mesh_cfg(2), 512, 2048, 1024,
                                      spec=TraceSpec(cap=1024))
    for iso, shr in zip(r.per_core_stall_isolated, r.per_core_stall_shared):
        assert shr >= iso - 1e-6
    assert sum(r.per_core_stall_shared) > 1.05 * sum(
        r.per_core_stall_isolated)
    assert all(f >= 1.0 for f in r.stall_inflation)
    assert r.makespan_shared >= r.makespan_isolated


def test_contention_private_channels_equals_isolated():
    r = simulate_multicore_contention(_mesh_cfg(2), 512, 2048, 1024,
                                      private_channels=True,
                                      spec=TraceSpec(cap=1024))
    for iso, shr in zip(r.per_core_stall_isolated, r.per_core_stall_shared):
        assert shr == pytest.approx(iso, rel=1e-6)
    assert r.makespan_shared == pytest.approx(r.makespan_isolated, rel=1e-6)


def test_contention_nop_offsets_respected():
    cores = (CoreConfig(rows=32, cols=32, nop_hops=0),
             CoreConfig(rows=32, cols=32, nop_hops=4))
    cfg = AcceleratorConfig(cores=cores, mesh_rows=2, mesh_cols=1,
                            memory=_MEM, dram=DramConfig(channels=2))
    r = simulate_multicore_contention(cfg, 512, 2048, 1024,
                                      spec=TraceSpec(cap=1024))
    assert len(r.per_core_stall_shared) == 2
    assert r.row_hits + r.row_misses + r.row_conflicts > 0


def test_trace_spec_rejects_nonsense_fields():
    """TraceSpec is the static (hashable) half of the trace kernels —
    a zero cap or unknown layout must fail at construction, not as a
    shape error inside a jitted sweep."""
    with pytest.raises(ValueError, match="cap"):
        TraceSpec(cap=0)
    with pytest.raises(ValueError, match="gran_bytes"):
        TraceSpec(gran_bytes=0)
    with pytest.raises(ValueError, match="layout"):
        TraceSpec(layout="diagonal")
    with pytest.raises(ValueError, match="tile"):
        TraceSpec(tile_r=0)
    with pytest.raises(ValueError, match="tile"):
        TraceSpec(tile_c=-2)
    with pytest.raises(ValueError, match="stride_elems"):
        TraceSpec(stride_elems=0)
    TraceSpec()  # defaults stay valid
