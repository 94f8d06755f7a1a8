"""repro: SCALE-Sim v3 reproduction — a JAX-native, vectorizable
cycle-accurate systolic accelerator simulator plus the workload plane
(models/launchers) it analyzes end to end.

Public simulation API lives in `repro.api` (Simulator facade); the lower
stage/engine layer in `repro.core`. See DESIGN.md for the map.
"""
# Trace toolchain at the top level: the legacy synthetic generators from
# core.dram plus the dataflow-aware repro.trace subsystem.  Importing any
# repro module initializes no JAX backend (a backend would take the chip).
from .core.dram import (linear_trace, strided_trace,  # noqa: F401
                        tile_prefetch_trace)
from .trace import (TraceSpec, gemm_request_stream,  # noqa: F401
                    gemm_trace_stats, multicore_contention, trace_op,
                    trace_op_stats)

__all__ = [
    "TraceSpec", "gemm_request_stream", "gemm_trace_stats", "linear_trace",
    "multicore_contention", "strided_trace", "tile_prefetch_trace",
    "trace_op", "trace_op_stats",
]
