"""Fused Pallas trace-replay megakernel.

One `pallas_call` replays a whole batch of decoded DRAM request streams:
designs/ops are flattened along the Pallas grid (one stream per grid
step), each stream's request arrays are staged into VMEM as a single
`(1, 1, npad)` block (the last two block dims equal the array's, which
is what Mosaic's tiling rule asks of a one-row block), and a `fori_loop`
walks the stream in `chunk`-lane windows — per-chunk order-only tables,
the fixed-point resolve, and the architectural state (bank
free/open-row, channel bus, in-flight rings, queue counters, per-core
shift) all live in registers/VMEM for the whole stream.  This replaces
the XLA driver's hoisted precompute + `lax.scan` with one kernel launch.

The per-chunk math is `kernels.replay.chunkmath`, written in the 2-D
layout Mosaic lowers (request rows, (C, C) masks, state columns).  On
TPU the chunk must be a multiple of 128 lanes.  Off-TPU the kernel runs
under `interpret=True` (the differential suite), at any chunk.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.accelerator import DramConfig
from ...core.dram import row_buffer_latency
from . import chunkmath as cm

LANES = 128
# What a v5e TensorCore's VMEM (128 MiB) leaves after Mosaic's own use.
_VMEM_CAP = 100 * 2 ** 20


def _megakernel_body(t_ref, fb_ref, ch_ref, row_ref, w_ref, v_ref, cid_ref,
                     done_ref, shift_ref, cnt_ref, *, cfg: DramConfig,
                     busy: float, C: int, nc: int,
                     max_passes: Optional[int], tol: float, n_cores: int,
                     n_qg: int):
    n_banks = cfg.channels * cfg.banks_per_channel
    state0 = cm.init_state(n_banks=n_banks, ch_n=cfg.channels, n_qg=n_qg,
                           Qr=cfg.read_queue, Qw=cfg.write_queue,
                           n_cores=n_cores)
    open0 = -jnp.ones((n_banks, 1), jnp.int32)
    zero = jnp.zeros((1, C), jnp.int32)
    idx = cm._iota((1, C), 1)

    def chunk(i, carry):
        state, open_row, hits, misses, conflicts = carry
        sl = pl.ds(pl.multiple_of(i * C, C), C)
        t = t_ref[0, :, sl]
        fb = fb_ref[0, :, sl]
        ch = ch_ref[0, :, sl]
        row = row_ref[0, :, sl]
        w = w_ref[0, :, sl] != 0
        v = v_ref[0, :, sl] != 0
        cid = cid_ref[0, :, sl]

        tab = cm.chunk_tables(fb, ch, row, w, v, cid, cfg=cfg, busy=busy,
                              n_cores=n_cores, n_qg=n_qg)
        # classify: intra-chunk links are order-only; first-per-bank
        # requests consult the carried open-row view
        open_at = cm.colsum(tab.bank_oh, open_row)
        seen = jnp.where(tab.intra, tab.row_prev, open_at)
        lat, hit, empty = row_buffer_latency(cfg, seen, row)
        # per-lane counters: reduced once, after the last chunk
        hits = hits + (hit & v).astype(jnp.int32)
        misses = misses + (empty & v).astype(jnp.int32)
        conflicts = conflicts + ((~hit) & (~empty) & v).astype(jnp.int32)

        state, done = cm.chunk_resolve(
            state, tab, t, lat, w, v, cfg=cfg, busy=busy,
            max_passes=max_passes, tol=tol)

        upd = tab.bank_oh & (idx == tab.last_b)
        open_row = jnp.where(tab.last_b >= 0, cm.lanemax(upd, row, -1),
                             open_row)
        done_ref[0, :, sl] = done
        return (state, open_row, hits, misses, conflicts)

    state, _, hits, misses, conflicts = jax.lax.fori_loop(
        0, nc, chunk, (state0, open0, zero, zero, zero))

    # one vector store per output: lanes [hits, misses, conflicts, 0...]
    lane = cm._iota((1, LANES), 1)
    cnt = jnp.where(lane == 0, jnp.sum(hits, axis=1, keepdims=True), 0)
    cnt = jnp.where(lane == 1, jnp.sum(misses, axis=1, keepdims=True), cnt)
    cnt = jnp.where(lane == 2, jnp.sum(conflicts, axis=1, keepdims=True),
                    cnt)
    cnt_ref[0] = cnt
    ncp = shift_ref.shape[-1]
    eye = cm._iota((n_cores, ncp), 0) == cm._iota((n_cores, ncp), 1)
    shift_ref[0] = jnp.max(jnp.where(eye, state.shift, 0.0), axis=0,
                           keepdims=True)


def replay_megakernel(t_issue, flat_bank, ch, row, is_write, valid,
                      cfg: DramConfig, gran_bytes: int = 64, *,
                      chunk: Optional[int] = None,
                      max_passes: Optional[int] = None,
                      tol: float = 0.25, n_cores: int = 1, core_id=None,
                      per_channel_queues: bool = False,
                      interpret: bool = False):
    """Replay a (batched) decoded request stream in one fused kernel.

    Same contract and return dict as `core.replay.replay_decoded`:
    inputs are `(..., n)` with arbitrary leading batch dims (flattened
    onto the Pallas grid — one stream per grid step), `done` is raw
    per-request completion (0 where ~valid), plus per-request `latency`,
    per-core `shift`, and exact hit/miss/conflict counters.  Streams are
    padded with invalid requests to a whole number of chunks.
    """
    n = t_issue.shape[-1]
    batch = t_issue.shape[:-1]
    C = LANES if chunk is None else int(chunk)
    if C < 1 or (not interpret and C % LANES):
        raise ValueError(
            f"megakernel chunk must be a positive multiple of {LANES} "
            f"lanes when compiled, got {C}")
    n_qg = cfg.channels if per_channel_queues else 1
    busy = float(max(1.0, gran_bytes / cfg.bandwidth_bytes_per_cycle))
    passes = None if max_passes is None else max(1, int(max_passes))
    f32 = jnp.float32

    if core_id is None:
        core_id = jnp.zeros(t_issue.shape, jnp.int32)

    nc = max(1, -(-n // C))
    npad = nc * C
    pad = npad - n
    S = 1
    for b in batch:
        S *= int(b)

    def _prep(x, dtype):
        x = jnp.broadcast_to(jnp.asarray(x).astype(dtype), batch + (n,))
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros(batch + (pad,), dtype)], axis=-1)
        return x.reshape((S, 1, npad))

    ins = (_prep(t_issue, f32), _prep(flat_bank, jnp.int32),
           _prep(ch, jnp.int32), _prep(row, jnp.int32),
           _prep(is_write, jnp.int32), _prep(valid, jnp.int32),
           _prep(core_id, jnp.int32))

    ncp = -(-n_cores // LANES) * LANES
    # 8 streamed arrays, double-buffered, each (1, npad) block padded to
    # 8 sublanes in VMEM; plus the chunk's (C, C) tables
    vmem = 2 * 8 * 8 * 4 * npad + 64 * 4 * C * C + 2 ** 22
    if not interpret and vmem > _VMEM_CAP:
        raise ValueError(
            f"a {n}-request stream needs ~{vmem >> 20} MiB of VMEM in the "
            f"replay megakernel (cap {_VMEM_CAP >> 20} MiB); use "
            f"engine='xla' for streams this long")

    kern = functools.partial(
        _megakernel_body, cfg=cfg, busy=busy, C=C, nc=nc,
        max_passes=passes, tol=float(tol), n_cores=n_cores, n_qg=n_qg)

    def spec(width):
        return pl.BlockSpec((1, 1, width), lambda s: (s, 0, 0))

    done, shift, cnt = pl.pallas_call(
        kern,
        grid=(S,),
        in_specs=[spec(npad)] * 7,
        out_specs=[spec(npad), spec(ncp), spec(LANES)],
        out_shape=[jax.ShapeDtypeStruct((S, 1, npad), f32),
                   jax.ShapeDtypeStruct((S, 1, ncp), f32),
                   jax.ShapeDtypeStruct((S, 1, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=int(max(vmem, 32 * 2 ** 20))),
        interpret=interpret,
    )(*ins)

    done = done.reshape(batch + (npad,))[..., :n]
    vmask = jnp.broadcast_to(jnp.asarray(valid, bool), batch + (n,))
    ti = jnp.broadcast_to(jnp.asarray(t_issue).astype(f32), batch + (n,))
    rt = jnp.where(vmask, done - ti, 0.0)
    cnt = cnt.reshape(batch + (LANES,))
    return dict(done=done, latency=rt,
                shift=shift.reshape(batch + (ncp,))[..., :n_cores],
                hits=cnt[..., 0], misses=cnt[..., 1],
                conflicts=cnt[..., 2])
