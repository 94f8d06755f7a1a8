"""Fused trace-replay kernels: the per-chunk math + the Pallas megakernel.

`chunkmath` is the megakernel's per-chunk replay step in the 2-D layout
Mosaic lowers, plus the fixed-point schedule (`iterate_fixed_point`)
that `core.replay.replay_decoded`'s XLA driver shares; `megakernel`
wraps the step in one `pallas_call` over a grid of streams.
"""
from .chunkmath import (ChunkState, ChunkTables, chunk_resolve,
                        chunk_tables, init_state, iterate_fixed_point)
from .megakernel import replay_megakernel

__all__ = [
    "ChunkState", "ChunkTables", "chunk_resolve", "chunk_tables",
    "init_state", "iterate_fixed_point", "replay_megakernel",
]
