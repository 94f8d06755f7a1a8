"""The `Simulator` facade: one session object over the stage pipeline.

    sim = Simulator("paper-32", fidelity="fast")
    report = sim.run(resnet18())            # NetworkReport
    res = sim.sweep(configs, ops)           # batched DSE over a config grid

A Simulator binds (config, fidelity, ERT) once; every entrypoint then runs
the same stage pipeline (`core/stages.py`). `sweep` is the batched path:
it stacks per-config scalars into arrays, vmaps the *traced* stage twins
over the design axis inside a single jit, and optionally shards the design
axis over a device mesh (reusing `launch/mesh.py` meshes) — this is how
thousands of design points per second are served from one process or a pod.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import spans
from ..core import dataflow as dfm
from ..core import stages as st
from ..core.accelerator import (AcceleratorConfig, DramConfig, MemoryConfig,
                                SparsityConfig)
from ..core.energy import DEFAULT_ERT, ERT, energy_pj
from ..core.layout import streaming_slowdown_table
from ..core.engine import (_ENERGY_GROUPS, NetworkReport, OpResult,
                           simulate_network, simulate_op)
from ..core.workloads import PAPER_WORKLOADS, Op
from .presets import get_preset

ConfigLike = Union[AcceleratorConfig, dict, str]
WorkloadLike = Union[Sequence[Op], str]


def as_config(c: ConfigLike) -> AcceleratorConfig:
    """Preset name | nested dict | AcceleratorConfig -> AcceleratorConfig."""
    if isinstance(c, AcceleratorConfig):
        return c
    if isinstance(c, str):
        return get_preset(c)
    if isinstance(c, dict):
        return AcceleratorConfig.from_dict(c)
    raise TypeError(f"cannot build AcceleratorConfig from {type(c)!r}")


def as_workload(w: WorkloadLike) -> List[Op]:
    """Op sequence or paper-workload name ('resnet18', 'vit_base', ...)."""
    if isinstance(w, str):
        if w not in PAPER_WORKLOADS:
            raise KeyError(f"unknown workload {w!r}; "
                           f"available: {sorted(PAPER_WORKLOADS)}")
        return PAPER_WORKLOADS[w]()
    return list(w)


@dataclasses.dataclass
class SweepResult:
    """Per-design-point totals over one workload (arrays of shape (n,))."""
    configs: List[AcceleratorConfig]
    total_cycles: np.ndarray
    compute_cycles: np.ndarray
    stall_cycles: np.ndarray
    dram_bytes: np.ndarray
    energy_pj: np.ndarray
    utilization: np.ndarray
    batched: bool = True          # False when the python fallback ran
    # resolved runtime replay-engine label of the sweep's DRAM replay
    # ('' for fidelities that replay nothing) — see NetworkReport.engine
    engine: str = ""

    @property
    def edp(self) -> np.ndarray:
        return self.energy_pj * 1e-9 * self.total_cycles

    def __len__(self) -> int:
        return len(self.configs)

    def argbest(self, objective: str = "edp") -> int:
        key = dict(edp=self.edp, latency=self.total_cycles,
                   cycles=self.total_cycles, energy=self.energy_pj)
        return int(np.argmin(key[objective]))

    def best(self, objective: str = "edp") -> AcceleratorConfig:
        return self.configs[self.argbest(objective)]


# Every AcceleratorConfig is traceable: sparsity (layer-wise and expected
# row-wise), layout bank-conflict slowdown and the multi-core partition all
# run inside the sweep kernel (core/stages.py traced twins), with the core
# grid shape / layout fields / sparse representation as static kernel
# flavors. The per-op engine remains reachable for 'cycle' fidelity,
# custom evaluators, and the Study `force_fallback` oracle mode that the
# differential parity suite exercises (tests/test_sweep_parity.py).


class Simulator:
    """Unified simulation session: config + fidelity + ERT, one pipeline.

    fidelity: 'fast' (first-order DRAM stalls, traceable/batchable),
    'cycle' (lax.scan DRAM timing over a synthetic prefetch stream) or
    'trace' (dataflow-aware generated demand traces through the same
    timing model — batchable like 'fast': the `repro.trace` generators
    are fixed-shape and vmappable).

    trace_spec: optional `repro.trace.TraceSpec` shared by the per-op
    pipeline and the batched sweep (so both paths agree bit-for-bit on
    the generated streams).

    core_index: the core a heterogeneous mesh is analyzed through — every
    core-dependent stage (mapping, sparsity, sram, dram, layout) models
    this member.

    engine: DRAM replay engine for the cycle/trace fidelities —
    None (default: the chunked bank-parallel replay, `core.replay`),
    "xla", "pallas", or "reference" (the original per-request scan).
    """

    def __init__(self, config: ConfigLike = "paper-32", *,
                 fidelity: str = "fast", ert: ERT = DEFAULT_ERT,
                 trace_spec=None, core_index: int = 0,
                 engine: Optional[str] = None):
        from ..core import replay as _rp
        if fidelity not in st.FIDELITIES:
            raise ValueError(f"fidelity must be one of {st.FIDELITIES}")
        self.config = as_config(config)
        self.fidelity = fidelity
        self.ert = ert
        self.core_index = core_index
        self.engine = _rp.resolve_engine(engine)
        if trace_spec is None and fidelity == "trace":
            from ..trace.generator import DEFAULT_SPEC
            trace_spec = DEFAULT_SPEC
        self.trace_spec = trace_spec
        self.pipeline = st.build_pipeline(fidelity, core_index=core_index,
                                          trace_spec=trace_spec,
                                          engine=self.engine)

    @classmethod
    def from_preset(cls, name: str, *, fidelity: str = "fast",
                    ert: ERT = DEFAULT_ERT, trace_spec=None,
                    core_index: int = 0, engine: Optional[str] = None,
                    **kw) -> "Simulator":
        return cls(get_preset(name, **kw), fidelity=fidelity, ert=ert,
                   trace_spec=trace_spec, core_index=core_index,
                   engine=engine)

    def with_(self, **config_fields) -> "Simulator":
        """New session with dataclass fields replaced on the config."""
        return Simulator(self.config.with_(**config_fields),
                         fidelity=self.fidelity, ert=self.ert,
                         trace_spec=self.trace_spec,
                         core_index=self.core_index,
                         engine=self.engine)

    def stage_names(self) -> List[str]:
        return [s.name for s in self.pipeline]

    # ---- single-config entrypoints ----------------------------------------
    def run_op(self, op: Op) -> OpResult:
        return simulate_op(self.config, op, dram_fidelity=self.fidelity,
                           ert=self.ert, pipeline=self.pipeline)

    def run(self, workload: WorkloadLike) -> NetworkReport:
        return simulate_network(self.config, as_workload(workload),
                                dram_fidelity=self.fidelity, ert=self.ert,
                                pipeline=self.pipeline)

    def run_lm(self, model_cfg, *, seq: int, batch: int, mode: str,
               cache_len: Optional[int] = None) -> NetworkReport:
        """Model one step of an LM architecture (repro.configs ModelConfig)
        on this accelerator — the co-simulation entrypoint shared by the
        train/serve/dryrun drivers and examples."""
        from ..core.workloads import lm_ops
        return self.run(lm_ops(model_cfg, seq=seq, batch=batch, mode=mode,
                               cache_len=cache_len))

    def seconds(self, cycles: float) -> float:
        """Accelerator cycles -> wall seconds at this config's clock."""
        return cycles / (self.config.clock_ghz * 1e9)

    @staticmethod
    def wave_cost(prefill_rep: NetworkReport, decode_rep: NetworkReport,
                  gen_len: int) -> tuple:
        """(cycles, pJ) for one serving wave: a prefill plus gen_len - 1
        decode steps (the first generated token comes out of prefill)."""
        steps = max(gen_len - 1, 0)
        return (prefill_rep.total_cycles + decode_rep.total_cycles * steps,
                prefill_rep.energy_pj + decode_rep.energy_pj * steps)

    # ---- batched sweep -----------------------------------------------------
    def sweep(self, configs: Sequence[ConfigLike], workload: WorkloadLike,
              *, mesh: Optional[jax.sharding.Mesh] = None,
              force_fallback: bool = False) -> SweepResult:
        """Simulate `workload` on every config; one jitted/vmapped call per
        static kernel flavor (dataflow, word_bytes, core grid, layout,
        sparse representation[, dram]) group.

        .. deprecated:: `sweep` is now a thin wrapper over a one-workload
           `repro.api.study.Study` — the one execution path for
           designs x workloads x fidelity studies. Prefer building a
           `Study` for new code (cross-product axes, columnar result
           frame, on-disk cell cache); this wrapper stays so existing
           call sites keep working (parity: tests/test_api.py).

        mesh: shard the design axis over a device mesh (launch/mesh.py);
        the grid is padded to a multiple of mesh.size.
        Every config batches at 'fast' and 'trace' fidelity — sparsity,
        layout and multi-core partitioning are evaluated inside the
        kernel; only 'cycle' fidelity runs through the per-op engine.
        force_fallback: run every cell through the per-op engine oracle
        instead (the differential-parity reference; tests only).
        """
        from .study import Study
        cfgs = [as_config(c) for c in configs]
        if not cfgs:                     # pre-Study contract: empty grid
            empty = np.zeros(0)          # -> empty result, not an error
            return SweepResult(configs=[], batched=True,
                               **{k: empty for k in
                                  ("total_cycles", "compute_cycles",
                                   "stall_cycles", "dram_bytes",
                                   "energy_pj", "utilization")})
        frame = (Study()
                 .designs(cfgs)
                 .workloads({"workload": as_workload(workload)})
                 .fidelity(self.fidelity)
                 .options(ert=self.ert, engine=self.engine,
                          trace_spec=self.trace_spec,
                          core_index=self.core_index,
                          force_fallback=force_fallback)
                 .run(mesh=mesh))
        return SweepResult(
            configs=cfgs,
            batched=bool(np.all(frame["batched"] > 0)),
            engine=str(frame.meta.get("engine", "")),
            **{k: frame[k] for k in ("total_cycles", "compute_cycles",
                                     "stall_cycles", "dram_bytes",
                                     "energy_pj", "utilization")})


# Compiled sweep kernels persist for the life of the process, keyed by the
# static pipeline flavor (dataflow, word size, ERT, DramConfig, TraceSpec,
# replay engine, stream sharing) — NOT per Simulator instance, so a fresh
# `Simulator(...)` rerunning the same grid reuses the jitted executable
# instead of re-tracing. Unbounded on purpose: entries are tiny relative
# to their retrace cost and the key space is the set of distinct pipeline
# flavors a process actually sweeps.
_SWEEP_FN_CACHE: Dict[tuple, object] = {}

# Requests one device replays per block of a trace sweep (512 streams at
# the default 4096-request cap).  The XLA replay driver holds ~0.8 KB of
# intermediates per request, so a block stays near 1.6 GB of device
# memory however many (design, op) streams the sweep replays.
_REPLAY_BLOCK_REQUESTS = 1 << 21


def _replay_blocks(n_pairs: int, cap: int, n_dev: int = 1) -> tuple:
    """(streams a replay block holds, blocks) for `n_pairs` streams of
    `cap` requests split over `n_dev` devices."""
    per_dev = max(1, _REPLAY_BLOCK_REQUESTS // cap)
    blk = min(per_dev, -(-n_pairs // n_dev)) * n_dev
    return blk, -(-n_pairs // blk)


def _program_name(dataflow: str, dram: Optional[DramConfig], engine: str,
                  mesh_shape: tuple, layout, with_sparsity: bool,
                  noc: Optional[str]) -> str:
    """The sweep program's name, as the device trace shows it (`jit_`
    prefixed): fidelity, dataflow, then what else sets the flavor apart,
    e.g. `sweep_trace_os_ch2_bw19p2_lay64`."""
    parts = ["sweep", "fast" if dram is None else "trace", dataflow]
    if dram is not None:
        bw = f"{dram.bandwidth_bytes_per_cycle:g}".replace(".", "p")
        parts += [f"ch{dram.channels}", f"bw{bw}"]
        if engine != "xla":
            parts.append(engine.replace(":", "_"))
    if mesh_shape != (1, 1):
        parts.append("x".join(map(str, mesh_shape)))
    if layout is not None:
        parts.append(f"lay{layout.num_banks}")
    if with_sparsity:
        parts.append("sparse")
    if noc is not None:
        parts.append(noc)
    return "_".join(parts)


def _batched_design_fn(dataflow: str, word_bytes: int, ert: ERT,
                       dram: Optional[DramConfig] = None, spec=None,
                       engine: Optional[str] = None,
                       mesh_shape: tuple = (1, 1),
                       layout=None, r_cap: int = 0,
                       representation: str = "ellpack_block",
                       with_sparsity: bool = False,
                       noc: Optional[str] = None,
                       device_mesh: Optional[jax.sharding.Mesh] = None):
    """Jitted (vmap over designs) sweep kernel, cached module-wide (see
    `_SWEEP_FN_CACHE`) so repeated sweeps — benchmark loops, serving
    traffic, new Simulator sessions — reuse the compiled executable.

    Every config feature is either data (sparsity n/m/row-wise/enabled,
    per-core geometry and NoP hops) vmapped over the design axis, or a
    static kernel flavor baked into the cache key: `mesh_shape` (the
    core grid — sweeps group by core count the way they group by
    dataflow), `layout` (on/off plus the LayoutConfig bank/port/step
    fields shaping the conflict model; None skips the layout math
    entirely — the plan groups enabled and disabled cells separately),
    `r_cap` (static bound on array rows for the layout window) and the
    sparse metadata `representation`.  With layout on, the program
    computes the layout slowdown curve once per distinct (array rows, op
    stride) pair (`lay`: the pairs' rows and strides, the `lay_row`
    design column and each op's stride index) and every design and op
    reads its own; the curve does not depend on the compute window, so
    this is exact.  `device_mesh` (a device mesh of
    more than one device, or None) splits the trace replay over devices.

    With `dram` set (trace fidelity), the first-order stall is replaced by
    the cycle-accurate stall of each op's generated demand trace.  The
    demand stream of a design is fully determined by (array geometry,
    memory sizing, sparsity, core grid) — the *effective* compute window
    and the compressed filter traffic feed the prefetch scheduler — so
    the sweep generates and replays one stream per unique `sdesign` row
    and gathers per-design stalls through `smap` (designs that differ
    only in bandwidth/SIMD/energy terms share the replay).  The address
    decode (`decode_requests`) is hoisted out of the per-design closure:
    the grouped sweep guarantees a common (streams, ops, cap) shape, so
    the whole address batch decodes in one call before the replay vmap.
    """
    from ..core import replay as _rp
    engine = _rp.resolve_engine(engine)
    # key on the *runtime-resolved* label ("pallas" -> "pallas:twin" /
    # "pallas:interpret" off-TPU), not the requested name: a "pallas"
    # sweep must never alias an "xla" cache entry, and the label in the
    # key matches what result metadata reports
    runtime = _rp.resolve_engine_runtime(engine)
    key = (dataflow, word_bytes, ert, dram, spec, runtime, mesh_shape,
           layout, r_cap, representation, with_sparsity, noc, device_mesh)
    cached = _SWEEP_FN_CACHE.get(key)
    if cached is not None:
        return cached
    if dram is not None:
        from ..core.dram import decode_requests, replay_requests
        from ..trace.generator import DEFAULT_SPEC, gemm_request_stream
        spec = spec or DEFAULT_SPEC
    Pr, Pc = mesh_shape
    num_cores = Pr * Pc
    n_dev = 1 if device_mesh is None else device_mesh.size

    def _mem(d):
        return MemoryConfig(ifmap_sram_bytes=d["if_b"],
                            filter_sram_bytes=d["f_b"],
                            ofmap_sram_bytes=d["o_b"],
                            l2_sram_bytes=d["l2_b"], word_bytes=word_bytes)

    def _features(d, ov, on, om):
        """The traced feature dicts of one design (static structure,
        traced values) for `stages.traced_comp_traffic`. Per-op N:M
        overrides (`Op.sparsity_nm`) mirror `stages.resolve_sparsity`:
        the op's n:m wins and forces the sparsity stage on."""
        sp = mc = None
        if with_sparsity:
            sp = dict(en=jnp.maximum(d["sp_en"], ov),
                      n=jnp.where(ov > 0, on, d["sp_n"]),
                      m=jnp.where(ov > 0, om, d["sp_m"]),
                      rw=d["sp_rw"], representation=representation)
        if num_cores > 1:
            mc = dict(rows=d["mc_R"], cols=d["mc_C"], hops=d["mc_hops"],
                      nop=d["nop"], Pr=Pr, Pc=Pc)
        return sp, mc

    @jax.named_scope(spans.GENERATE)
    def _op_streams(d, M, N, K, ov, on, om):
        """Generated demand streams for every gemm op of one design,
        driven by the *effective* compute window and the sparsity-shrunk
        DRAM traffic (what the per-op TraceDramStage sees)."""
        mem, R, C = _mem(d), d["R"], d["C"]
        sp, mc = _features(d, ov, on, om)
        comp, _, dr, _ = st.traced_comp_traffic(
            dataflow, M, N, K, R, C, mem, sparsity=sp, multicore=mc)

        def per_op(m, n, k, comp_, di, dfl, dow, dor):
            return gemm_request_stream(dataflow, m, n, k, R, C, comp_,
                                       di, dfl, dow, dor, word_bytes, spec)

        return jax.vmap(per_op)(M, N, K, comp, dr["dram_ifmap"],
                                dr["dram_filter"], dr["dram_ofmap_writes"],
                                dr["dram_ofmap_reads"])

    def _trace_stalls(sdesign, smap, M, N, K, ov, on, om):
        """(designs, ops) cycle-accurate stalls: one replay per unique
        (stream design, op) pair, decode hoisted out of the per-pair
        closure.  The pairs replay in blocks of at most
        `_REPLAY_BLOCK_REQUESTS` requests per device (one `lax.map` step
        each), which bounds device memory whatever the grid and workload
        size; on a device mesh each block's pairs split over the devices."""
        n_ops = M.shape[0]
        n_pairs = next(iter(sdesign.values())).shape[0] * n_ops
        blk, n_blk = _replay_blocks(n_pairs, spec.cap, n_dev)

        @jax.named_scope(spans.REPLAY)
        def _replay(t, fb, ch, row, wbit, val):
            return replay_requests(t, fb, ch, row, wbit, val, dram,
                                   spec.gran_bytes, engine=engine,
                                   ).stall_cycles

        def replay_block(pairs, sdesign, M, N, K, ov, on, om):
            def one(p):
                d = {k: v[p // n_ops] for k, v in sdesign.items()}
                o = [x[p % n_ops][None] for x in (M, N, K, ov, on, om)]
                return tuple(x[0] for x in _op_streams(d, *o))

            t, addr, wbit, val, scale = jax.vmap(one)(pairs)
            with jax.named_scope(spans.DECODE):         # one flat decode
                fb, ch, row = decode_requests(addr, dram)
            if engine in ("xla", "pallas"):
                # batch-native: the block goes through one chunk scan
                # ("xla") or one megakernel launch with the streams on
                # the Pallas grid ("pallas") — never a vmapped per-stream
                # replay, and "pallas" never silently rides the "xla"
                # driver (replay_decoded resolves it to the megakernel on
                # TPU or its interpret/twin form off-TPU)
                stall = _replay(t, fb, ch, row, wbit, val)
            else:
                stall = jax.vmap(_replay)(t, fb, ch, row, wbit, val)
            return stall * scale

        if n_dev > 1:
            from jax.sharding import PartitionSpec as P
            axes = P(tuple(device_mesh.axis_names))
            replay_block = jax.shard_map(
                replay_block, mesh=device_mesh,
                in_specs=(axes,) + (P(),) * 7, out_specs=axes,
                check_vma=False)
        # padding pairs repeat the last one; their stalls are dropped
        pairs = jnp.minimum(jnp.arange(n_blk * blk, dtype=jnp.int32),
                            n_pairs - 1).reshape(n_blk, blk)
        stall = jax.lax.map(
            lambda p: replay_block(p, sdesign, M, N, K, ov, on, om), pairs)
        return stall.reshape(-1)[:n_pairs].reshape(-1, n_ops)[smap]

    @jax.named_scope(spans.STAGES)
    def one_design(d, M, N, K, cnt, ov, on, om, velems, vcnt, trace_stall,
                   curves=None):
        mem = _mem(d)
        R, C = d["R"], d["C"]
        sp, mc = _features(d, ov, on, om)
        sd = None
        if curves is not None:
            # each op's curve: the design's row of the program's table,
            # at the op's stride
            table, op_stride = curves
            sd = table[d["lay_row"].astype(jnp.int32)][op_stride]
        s = st.traced_op_stats(dataflow, M, N, K, R, C, mem, d["bw"],
                               sparsity=sp, multicore=mc, layout_sd=sd)
        stall_per_op = s["stall_cycles"] if trace_stall is None else \
            trace_stall
        comp_t = s["compute_cycles"] * cnt
        stall_t = stall_per_op * cnt
        lay_t = s["layout_extra_cycles"] * cnt
        dram_t = s["dram_bytes"] * cnt
        macs = M * N * K * cnt
        if num_cores > 1:
            pes = jnp.sum(d["mc_R"] * d["mc_C"])
            dim32 = jnp.max(jnp.maximum(d["mc_R"], d["mc_C"])) / 32.0
        else:
            pes = R * C
            dim32 = jnp.maximum(R, C) / 32.0
        counts = st.traced_energy_counts(
            R=R, C=C, mem=mem, cycles=comp_t, macs=macs,
            ifmap_reads=s["ifmap_reads"] * cnt,
            filter_reads=s["filter_reads"] * cnt,
            ofmap_writes=s["ofmap_writes"] * cnt,
            ofmap_reads=s["ofmap_reads"] * cnt,
            dram_bytes=dram_t,
            l2_reads=jnp.where(d["l2_b"] > 0, s["dram_elems"] * cnt, 0.0),
            pes=pes, dim32=dim32)
        e = energy_pj(counts, ert)

        # SIMD sidecar (empty arrays contribute zero); like run_vector,
        # every component scales with count
        v = st.traced_vector_stats(velems, d["lanes"], d["lat"], word_bytes)
        vcyc = v["compute_cycles"] * vcnt
        vdram = v["dram_bytes"] * vcnt
        vel_t = velems * vcnt
        vcounts = st.traced_energy_counts(
            R=R, C=C, mem=mem, cycles=vcyc, macs=jnp.zeros_like(vcyc),
            ifmap_reads=vel_t, filter_reads=jnp.zeros_like(vel_t),
            ofmap_writes=vel_t, ofmap_reads=jnp.zeros_like(vel_t),
            dram_bytes=vdram, pes=pes, dim32=dim32)
        ve = energy_pj(vcounts, ert)
        energy = jnp.sum(e["total"]) + jnp.sum(ve["total"])
        # the grouped-energy column schema shared with NetworkReport
        # (engine._ENERGY_GROUPS) — the Study frame reports these per cell
        groups = {g: sum(jnp.sum(e[a]) + jnp.sum(ve[a]) for a in acts)
                  for g, acts in _ENERGY_GROUPS.items()}

        # routed-NoP plane (repro.noc): flit/credit contention on each
        # op's memory traffic toward the MC at core 0. `noc` (the
        # topology kind) is a static flavor fixing the routing tree; the
        # link parameters are traced design columns. Sparse ops gate to
        # zero like the partition stage (single-core compressed stream).
        noc_cols = {}
        noc_stall_sum = 0.0
        if noc is not None and num_cores > 1:
            from ..noc.router import noc_delay_model
            from ..noc.traffic import allreduce_cycles, memory_flits
            gate = ((1.0 - jnp.maximum(d["sp_en"], ov)) if with_sparsity
                    else jnp.ones_like(M))
            flits = (memory_flits(s["dram_bytes"], num_cores,
                                  d["noc_flit"])[..., None]
                     * jnp.ones(num_cores, jnp.float32))   # (ops, cores)
            ns = noc_delay_model(noc, Pr, Pc, flits, d["noc_bw"],
                                 d["noc_flit"], d["noc_buf"], d["nop"],
                                 s["compute_cycles"])
            ar = allreduce_cycles(noc, Pr, Pc, M * N * word_bytes,
                                  d["noc_bw"], d["noc_flit"], d["noc_buf"],
                                  d["nop"])
            noc_stall_sum = jnp.sum(ns["stall"] * gate * cnt)
            noc_cols = dict(
                noc_stall_cycles=noc_stall_sum,
                noc_link_util=jnp.max(ns["link_util"] * gate),
                allreduce_cycles=jnp.sum(ar * gate * cnt))

        comp = jnp.sum(comp_t) + jnp.sum(vcyc)
        stall = jnp.sum(stall_t)
        lay_sum = jnp.sum(lay_t)
        dram_b = jnp.sum(dram_t) + jnp.sum(vdram)
        total = comp + stall + lay_sum + noc_stall_sum
        util = jnp.minimum(1.0, jnp.sum(macs)
                           / jnp.maximum(1.0, pes * total))
        return dict(total_cycles=total, compute_cycles=comp,
                    stall_cycles=stall, dram_bytes=dram_b,
                    energy_pj=energy, utilization=util, **groups,
                    **noc_cols)

    def fn(design, sdesign, smap, M, N, K, cnt, ov, on, om, velems, vcnt,
           lay):
        curves = None
        if layout is not None:
            # one slowdown curve per distinct (array rows, op stride) of
            # the group, shared by every design and op that has it
            with jax.named_scope(spans.STAGES):
                curves = (streaming_slowdown_table(
                    layout, lay["rows"], lay["strides"], word_bytes,
                    r_cap=r_cap), lay["op_stride"])
        one = functools.partial(one_design, curves=curves)
        if dram is not None:
            stall = _trace_stalls(sdesign, smap, M, N, K,
                                  ov, on, om)          # (designs, ops)
            return jax.vmap(one, in_axes=(0,) + (None,) * 9 + (0,))(
                design, M, N, K, cnt, ov, on, om, velems, vcnt, stall)
        return jax.vmap(
            functools.partial(one, trace_stall=None),
            in_axes=(0,) + (None,) * 9)(
                design, M, N, K, cnt, ov, on, om, velems, vcnt)

    fn.__name__ = fn.__qualname__ = _program_name(
        dataflow, dram, runtime, mesh_shape, layout, with_sparsity, noc)
    return _SWEEP_FN_CACHE.setdefault(key, jax.jit(fn))


def _pow2_cap(n: int) -> int:
    """Smallest power of two >= n (static layout-window row bound —
    bucketed so similar grids share one compiled kernel)."""
    cap = 1
    while cap < n:
        cap *= 2
    return cap


def _sweep_batched(cfgs: Sequence[AcceleratorConfig], ops: Sequence[Op],
                   dataflow: str, word_bytes: int, ert: ERT,
                   mesh: Optional[jax.sharding.Mesh],
                   dram: Optional[DramConfig] = None,
                   spec=None, engine: Optional[str] = None,
                   core_index: int = 0) -> Dict[str, np.ndarray]:
    """Stack config scalars, vmap the traced stages over the design axis.

    The caller (Study.plan) guarantees group-static flavor uniformity:
    every config shares dataflow, word_bytes, the core grid shape, the
    layout fields (when enabled) and the sparse representation.  One
    `sweep` span covers the call, with its steps as child spans.
    """
    with jax.profiler.TraceAnnotation(spans.SWEEP) as span:
        with jax.profiler.TraceAnnotation(spans.SWEEP_COLUMNS):
            fn, args, counts = _sweep_inputs(
                cfgs, ops, dataflow, word_bytes, ert, mesh, dram, spec,
                engine, core_index)
        span.set_metadata(program=fn.__name__, designs=len(cfgs), **counts)
        with jax.profiler.TraceAnnotation(spans.SWEEP_DISPATCH):
            res = fn(*args)
        with jax.profiler.TraceAnnotation(spans.SWEEP_FETCH):
            return {k: np.asarray(v, np.float64)[:len(cfgs)]
                    for k, v in res.items()}


def _sweep_inputs(cfgs, ops, dataflow, word_bytes, ert, mesh, dram, spec,
                  engine, core_index):
    """(the group's sweep program, its arguments, the replay's `streams`,
    `blocks` and `block`, and `layout_rows`, the layout slowdown curves
    the program computes): the host side of `_sweep_batched`."""
    n = len(cfgs)
    f32 = np.float32
    ci = core_index
    Pr, Pc = cfgs[0].mesh_rows, cfgs[0].mesh_cols
    num_cores = Pr * Pc
    if any((c.mesh_rows, c.mesh_cols) != (Pr, Pc) for c in cfgs):
        raise ValueError("sweep group mixes core-grid shapes")

    gemms = [o for o in ops if o.kind == "gemm"]
    vecs = [o for o in ops if o.kind == "vector"]
    with_sparsity = (any(c.sparsity.enabled for c in cfgs)
                     or any(o.sparsity_nm is not None for o in gemms))
    # layout on/off is a static kernel flavor: the plan key puts enabled
    # and disabled cells in different groups, so a group is all-or-none
    with_layout = cfgs[0].layout.enabled
    if any(c.layout.enabled != with_layout for c in cfgs):
        raise ValueError(
            "sweep group mixes layout-enabled and -disabled designs")
    layout_key = (dataclasses.replace(cfgs[0].layout, enabled=True)
                  if with_layout else None)
    representation = cfgs[0].sparsity.representation
    r_cap = (_pow2_cap(max(c.cores[ci].rows for c in cfgs))
             if with_layout else 0)

    # Per-op N:M overrides must form a valid SparsityConfig with every
    # design's row_wise flag — mirrors stages.resolve_sparsity, which
    # raises on the per-op oracle path; without this the batched kernel
    # would silently compute what the oracle refuses (e.g. row-wise with
    # n > m/2, or an m past the expected-max grid bound).
    for o in gemms:
        if o.sparsity_nm is not None:
            for rw in {c.sparsity.row_wise for c in cfgs}:
                SparsityConfig(enabled=True, n=o.sparsity_nm[0],
                               m=o.sparsity_nm[1], row_wise=rw)

    # A design's demand stream is fully determined by (array geometry,
    # memory sizing, sparsity, core grid): replay one stream per unique
    # combination and let designs that differ only in bandwidth/SIMD/
    # energy/layout terms share it. The key carries only the fields that
    # feed the stream (not whole CoreConfig/SparsityConfig objects, whose
    # SIMD/seed fields would needlessly fragment the dedup).
    seen: Dict[tuple, int] = {}
    sidx: List[int] = []        # design index of each unique stream
    smap: List[int] = []        # design -> unique stream id
    for i, c in enumerate(cfgs):
        k = (tuple((k_.rows, k_.cols, k_.nop_hops) for k_ in c.cores),
             c.mesh_rows, c.mesh_cols, c.memory,
             (c.sparsity.enabled, c.sparsity.n, c.sparsity.m,
              c.sparsity.row_wise, c.sparsity.representation),
             c.nop_cycles_per_hop)
        if k not in seen:
            seen[k] = len(sidx)
            sidx.append(i)
        smap.append(seen[k])

    M = jnp.asarray([o.M for o in gemms], f32)
    N = jnp.asarray([o.N for o in gemms], f32)
    K = jnp.asarray([o.K for o in gemms], f32)
    cnt = jnp.asarray([o.count for o in gemms], f32)
    ov = jnp.asarray([0.0 if o.sparsity_nm is None else 1.0
                      for o in gemms], f32)
    on = jnp.asarray([1.0 if o.sparsity_nm is None else o.sparsity_nm[0]
                      for o in gemms], f32)
    om = jnp.asarray([1.0 if o.sparsity_nm is None else o.sparsity_nm[1]
                      for o in gemms], f32)
    velems = jnp.asarray([o.vector_elems for o in vecs], f32)
    vcnt = jnp.asarray([o.count for o in vecs], f32)

    cols = {
        "R": [c.cores[ci].rows for c in cfgs],
        "C": [c.cores[ci].cols for c in cfgs],
        "lanes": [c.cores[0].simd_lanes for c in cfgs],
        "lat": [c.cores[0].simd_latency for c in cfgs],
        "if_b": [c.memory.ifmap_sram_bytes for c in cfgs],
        "f_b": [c.memory.filter_sram_bytes for c in cfgs],
        "o_b": [c.memory.ofmap_sram_bytes for c in cfgs],
        "l2_b": [c.memory.l2_sram_bytes for c in cfgs],
        "bw": [c.dram.bandwidth_bytes_per_cycle * c.dram.channels
               for c in cfgs],
    }
    stream_keys = ["R", "C", "if_b", "f_b", "o_b", "l2_b"]
    if with_sparsity:
        cols["sp_en"] = [1.0 if c.sparsity.enabled else 0.0 for c in cfgs]
        cols["sp_n"] = [c.sparsity.n for c in cfgs]
        cols["sp_m"] = [c.sparsity.m for c in cfgs]
        cols["sp_rw"] = [1.0 if c.sparsity.row_wise else 0.0 for c in cfgs]
        stream_keys += ["sp_en", "sp_n", "sp_m", "sp_rw"]
    # routed-NoC flavor: the Study plan key groups by (enabled, topology),
    # so a group is uniform; validate against direct callers anyway
    noc_kind = (cfgs[0].noc.topology
                if cfgs[0].noc.enabled and num_cores > 1 else None)
    if any((c.noc.enabled and num_cores > 1, c.noc.topology if c.noc.enabled
            else None) != (noc_kind is not None, noc_kind) for c in cfgs):
        raise ValueError("sweep group mixes NoC topologies/enablement")
    if num_cores > 1:
        cols["mc_R"] = [[k.rows for k in c.cores] for c in cfgs]
        cols["mc_C"] = [[k.cols for k in c.cores] for c in cfgs]
        if noc_kind is not None:
            # per-core hop columns become routed latencies: dimension-
            # ordered hops to the MC at (0,0) replace the config offsets
            from ..noc.topology import routed_hop_counts
            routed = [float(h) for h in
                      routed_hop_counts(noc_kind, Pr, Pc)]
            cols["mc_hops"] = [list(routed) for _ in cfgs]
        else:
            cols["mc_hops"] = [[k.nop_hops for k in c.cores] for c in cfgs]
        cols["nop"] = [c.nop_cycles_per_hop for c in cfgs]
        stream_keys += ["mc_R", "mc_C", "mc_hops", "nop"]
    if noc_kind is not None:
        cols["noc_bw"] = [c.noc.link_bandwidth_bytes_per_cycle for c in cfgs]
        cols["noc_flit"] = [c.noc.flit_bytes for c in cfgs]
        cols["noc_buf"] = [c.noc.buffer_flits for c in cfgs]
    # The layout stage's per-cycle slowdown depends only on the array rows
    # and the op's stride (N), not on the design's compute window: the
    # program computes one curve per distinct (rows, stride) and each
    # design and op reads its own (`lay_row` design column, `op_stride`).
    lay = None
    if with_layout:
        rows = sorted({c.cores[ci].rows for c in cfgs})
        cols["lay_row"] = [rows.index(c.cores[ci].rows) for c in cfgs]
        # the stride as the kernel sees it: max(1, N) in float32, to int32
        op_n = np.maximum(f32(1.0), np.asarray([o.N for o in gemms], f32))
        strides, op_stride = np.unique(op_n.astype(np.int32),
                                       return_inverse=True)
        lay = dict(rows=jnp.asarray(rows, jnp.int32),
                   strides=jnp.asarray(strides, jnp.int32),
                   op_stride=jnp.asarray(op_stride.reshape(-1), jnp.int32))
    layout_rows = 0 if lay is None else len(rows) * len(strides)
    sdesign = smap_arr = None
    if dram is not None:
        sdesign = {k: jnp.asarray([cols[k][i] for i in sidx], f32)
                   for k in stream_keys}
    pad = 0
    if mesh is not None and mesh.size > 1:
        pad = (-n) % mesh.size
        for v in cols.values():
            v.extend([v[-1]] * pad)
        smap.extend([smap[-1]] * pad)
    if dram is not None:
        smap_arr = jnp.asarray(smap, jnp.int32)
    design = {k: jnp.asarray(v, f32) for k, v in cols.items()}
    if mesh is not None and mesh.size > 1:
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(tuple(mesh.axis_names)))
        design = {k: jax.device_put(v, sharding) for k, v in design.items()}

    device_mesh = mesh if mesh is not None and mesh.size > 1 else None
    fn = _batched_design_fn(dataflow, word_bytes, ert, dram, spec,
                            engine=engine, mesh_shape=(Pr, Pc),
                            layout=layout_key, r_cap=r_cap,
                            representation=representation,
                            with_sparsity=with_sparsity, noc=noc_kind,
                            device_mesh=device_mesh)
    streams = len(sidx) * len(gemms) if dram is not None else 0
    block = blocks = 0
    if streams:
        from ..trace.generator import DEFAULT_SPEC
        block, blocks = _replay_blocks(
            streams, (spec or DEFAULT_SPEC).cap,
            1 if device_mesh is None else device_mesh.size)
    return (fn, (design, sdesign, smap_arr, M, N, K, cnt, ov, on, om,
                 velems, vcnt, lay),
            dict(streams=streams, blocks=blocks, block=block,
                 layout_rows=layout_rows))
