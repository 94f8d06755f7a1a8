"""On-chip multi-bank data-layout modeling (paper Sec. VI).

The multi-bank SRAM is a 2D array: a "line" aggregates the same row index
across banks; each bank offers `ports_per_bank` concurrent line accesses per
cycle. A data layout assigns each tensor element a (line_id, col_id) via
nested-loop dimension orders; bank_id = col_id // bandwidth_per_bank.

Per-cycle slowdown (paper eq.): the bank needing the most distinct lines
relative to its ports sets the cycle's latency:

    slowdown = max_i ceil(distinct_lines(bank_i) / ports(bank_i))

`slowdown_per_cycle` is the vectorized oracle; kernels/conflict provides the
Pallas TPU kernel computing the same quantity.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .accelerator import LayoutConfig


def chw_ids(c, h, w, H: int, W: int, cfg: LayoutConfig,
            word_bytes: int = 2) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paper's (line_id, col_id, bank_id) for a CxHxW tensor layout."""
    c1, h1, w1 = cfg.c1_step, cfg.h1_step, cfg.w1_step
    line = (c // c1) * (-(-H // h1)) * (-(-W // w1)) \
        + (h // h1) * (-(-W // w1)) + (w // w1)
    col = (w % w1) * h1 * c1 + (h % h1) * c1 + (c % c1)
    bpb = max(1, cfg.line_bytes // word_bytes)   # elements per bank line
    bank = (col // bpb) % cfg.num_banks
    return line, col, bank


def flat_ids(flat_index, cfg: LayoutConfig, word_bytes: int = 2):
    """Row-major layout for 2D operand matrices: contiguous elements fill a
    line across banks, then move to the next line."""
    bpb = max(1, cfg.line_bytes // word_bytes)
    elems_per_line = bpb * cfg.num_banks
    line = flat_index // elems_per_line
    col = flat_index % elems_per_line
    bank = col // bpb
    return line, col, bank


@partial(jax.jit, static_argnames=("num_banks", "ports"))
def slowdown_per_cycle(line: jnp.ndarray, bank: jnp.ndarray,
                       num_banks: int, ports: int = 1) -> jnp.ndarray:
    """(cycles, k) line/bank ids -> per-cycle slowdown (>= 1).

    Distinct (bank, line) pairs per cycle are counted by sorting each cycle's
    keys and marking boundaries; per-bank distinct counts come from a one-hot
    segment sum. Matches kernels/conflict (Pallas) bit-exactly.
    """
    # int32-safe composite key: bank * (max_line + 1) + line
    stride = jnp.max(line) + 1
    key = bank.astype(jnp.int32) * stride + line.astype(jnp.int32)
    key = jnp.sort(key, axis=1)
    new = jnp.concatenate(
        [jnp.ones_like(key[:, :1], bool), key[:, 1:] != key[:, :-1]], axis=1)
    b = (key // stride).astype(jnp.int32)
    onehot = jax.nn.one_hot(b, num_banks, dtype=jnp.int32)
    counts = jnp.einsum("ck,ckb->cb", new.astype(jnp.int32), onehot)
    per_bank = -(-counts // ports)
    return jnp.maximum(1, per_bank.max(axis=1))


def streaming_access_pattern(R: int, n_cycles: int, lead_stride: int,
                             elem_stride: int = 1) -> jnp.ndarray:
    """Flat element indices accessed per cycle by a streaming operand port:
    cycle t reads R elements {t*lead_stride + r*elem_stride}."""
    t = jnp.arange(n_cycles)[:, None]
    r = jnp.arange(R)[None, :]
    return t * lead_stride + r * elem_stride


# Fixed per-op analysis window of the streaming slowdown model: every
# op is analyzed over at most this many cycles (the oracle previously
# sized the window to the op; a static window + validity mask keeps the
# model jit/vmap-safe with `comp` as traced data).
STREAM_WINDOW_CYCLES = 512


def _distinct_slowdown(line, bank, num_banks: int, ports: int):
    """Per-cycle slowdown from (cycles, k) line/bank ids — the same
    quantity as `slowdown_per_cycle`, computed with a scatter-add instead
    of a one-hot einsum so large vmapped batches don't materialize a
    (cycles, k, banks) intermediate. line/bank may be traced floats."""
    line = line.astype(jnp.int32)
    bank = bank.astype(jnp.int32)
    stride = jnp.max(line) + 1
    # lax.sort, not jnp.sort: the same stable sort, emitted in place so
    # it carries the caller's named scope (jnp.sort is a jitted call)
    key = jax.lax.sort(bank * stride + line, dimension=1)
    new = jnp.concatenate(
        [jnp.ones_like(key[:, :1], bool), key[:, 1:] != key[:, :-1]], axis=1)
    b = key // stride
    cyc = jnp.broadcast_to(jnp.arange(key.shape[0])[:, None], key.shape)
    counts = jnp.zeros((key.shape[0], num_banks), jnp.int32)
    counts = counts.at[cyc, b].add(new.astype(jnp.int32))
    per_bank = -(-counts // ports)
    return jnp.maximum(1, per_bank.max(axis=1))


def streaming_slowdown_curve(cfg: LayoutConfig, R, elem_stride,
                             word_bytes: int = 2, *, r_cap: int,
                             lead_stride: int = 1):
    """Per-cycle slowdown (int32, `STREAM_WINDOW_CYCLES` long) of a
    systolic streaming pattern: cycle t reads the R elements
    {t * lead_stride + r * elem_stride}.

    `R` and `elem_stride` may be traced scalars; the LayoutConfig fields,
    `word_bytes`, `lead_stride` and `r_cap` (static bound on R — rows
    beyond R are masked by duplicating the r=0 access, which adds no
    distinct (bank, line) pair) are static. The curve does not depend on
    the op's compute window, so a batch of ops that share (R, stride)
    shares one curve (the sweep kernel computes one per distinct pair).
    """
    t = jnp.arange(STREAM_WINDOW_CYCLES, dtype=jnp.int32)
    r = jnp.arange(r_cap, dtype=jnp.int32)
    # integer index grid: element offsets stay exact past f32's 2^24
    # (large-vocab GEMMs stream with strides in the 100k+ range)
    stride = jnp.asarray(elem_stride, jnp.int32)
    idx = t[:, None] * int(lead_stride) + r[None, :] * stride
    line, _, bank = flat_ids(idx, cfg, word_bytes)
    rvalid = r[None, :] < R
    line = jnp.where(rvalid, line, line[:, :1])
    bank = jnp.where(rvalid, bank, bank[:, :1])
    return _distinct_slowdown(line, bank, cfg.num_banks, cfg.ports_per_bank)


def streaming_slowdown_table(cfg: LayoutConfig, rows, strides,
                             word_bytes: int = 2, *, r_cap: int):
    """(len(rows), len(strides), window) int32: the slowdown curve of
    every (R, stride) pair of the distinct array rows and op strides."""
    def curve(R, stride):
        return streaming_slowdown_curve(cfg, R, stride, word_bytes,
                                        r_cap=r_cap)

    return jax.vmap(lambda R: jax.vmap(lambda n: curve(R, n))(strides))(rows)


def streaming_window_extra(sd, comp):
    """Extra cycles an op of `comp` compute cycles loses to bank
    conflicts, from its slowdown curve `sd` (`streaming_slowdown_curve`).

    Cycles past clip(floor(comp), 8, window) are masked out of the mean,
    reproducing the op-sized window of the eager model exactly; the sum
    is int32, so it is exact whatever the batch. `sd` may carry leading
    batch axes matching `comp`'s shape.
    """
    n_cyc = STREAM_WINDOW_CYCLES
    t = jnp.arange(n_cyc, dtype=jnp.int32)
    n_valid = jnp.clip(jnp.floor(jnp.minimum(1.0 * comp, n_cyc)), 8, n_cyc)
    total = jnp.sum(jnp.where(t < n_valid[..., None], sd, 0), axis=-1)
    return (total / n_valid - 1.0) * comp


def streaming_layout_extra(cfg: LayoutConfig, R, comp, elem_stride,
                           word_bytes: int = 2, *, r_cap: int = None,
                           lead_stride: int = 1):
    """Extra cycles a systolic streaming pattern loses to bank conflicts:
    the slowdown curve of (R, elem_stride) averaged over the op's window.

    The per-op form of the LayoutStage model; the batched sweep kernel
    composes the same two functions over its distinct (R, stride) curves,
    so both paths agree bit-for-bit. `R`, `comp` and `elem_stride` may be
    traced scalars; `r_cap` defaults to R.
    """
    if r_cap is None:
        r_cap = int(R)
    sd = streaming_slowdown_curve(cfg, R, elem_stride, word_bytes,
                                  r_cap=r_cap, lead_stride=lead_stride)
    return streaming_window_extra(sd, comp)


DRAM_LAYOUTS = ("row", "col", "tiled", "strided")


def operand_linear_index(row, col, rows, cols, order: str = "row",
                         tile_r: int = 32, tile_c: int = 32):
    """DRAM-side storage layout: operand element (row, col) of a
    rows x cols matrix -> linear element offset within its region.

    - 'row':   row-major (C order) — a streaming walk down a column is
               strided by `cols` elements (row-buffer hostile for large
               matrices).
    - 'col':   column-major (Fortran order) — the same walk is contiguous.
    - 'tiled': tile_r x tile_c blocks laid out row-major, row-major inside
               each block — the blocked layouts SCALE-Sim's trace studies
               compare against.

    All arguments may be traced jnp arrays except `order`/`tile_r`/`tile_c`
    (static), so `repro.trace` generators stay vmappable. ('strided' is
    synthesized directly from the stream position in the generator, not
    from coordinates, so it is not handled here.)
    """
    if order == "row":
        return row * cols + col
    if order == "col":
        return col * rows + row
    if order == "tiled":
        tiles_per_row = -(-cols // tile_c)
        tile_id = (row // tile_r) * tiles_per_row + (col // tile_c)
        return (tile_id * (tile_r * tile_c)
                + (row % tile_r) * tile_c + (col % tile_c))
    raise ValueError(f"unknown DRAM layout order {order!r}; "
                     f"known: {DRAM_LAYOUTS}")


@dataclasses.dataclass(frozen=True)
class LayoutResult:
    mean_slowdown: float
    max_slowdown: float
    extra_cycles: float


def evaluate_layout(cfg: LayoutConfig, R: int, n_cycles: int,
                    lead_stride: int, elem_stride: int = 1,
                    word_bytes: int = 2) -> LayoutResult:
    """Slowdown of a systolic streaming pattern under a flat layout.

    lead_stride/elem_stride describe how consecutive cycles / array rows map
    to operand addresses (dataflow-dependent): e.g. ws streams a column of X
    per cycle (elem_stride = N, lead_stride = 1 for row-major K x N).
    """
    idx = streaming_access_pattern(R, n_cycles, lead_stride, elem_stride)
    line, _, bank = flat_ids(idx, cfg, word_bytes)
    sd = slowdown_per_cycle(line, bank, cfg.num_banks, cfg.ports_per_bank)
    return LayoutResult(mean_slowdown=float(sd.mean()),
                        max_slowdown=float(sd.max()),
                        extra_cycles=float((sd - 1).sum()))
