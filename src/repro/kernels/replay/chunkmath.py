"""Per-chunk replay math of the fused trace-replay megakernel.

The megakernel (`kernels.replay.megakernel`) walks one decoded request
stream in chunks of C requests; this module is what it runs per chunk.
The XLA driver (`core.replay.replay_decoded`) computes the same model
with gathers and log-step scans, and both drivers share the fixed-point
schedule, `iterate_fixed_point`, so `max_passes`/`tol` mean the same
thing under every engine.

Layout (what Mosaic lowers; every value is 2-D):

  - a per-request vector is a *row* `(1, C)`: request index on lanes;
  - a pairwise table is `(C, C)` with the producer j on sublanes and the
    consumer i on lanes — `mask[j, i]` is True when request j feeds
    request i — so a masked reduction over axis 0 maps a producer
    column to a consumer row (`colmax`/`colsum`);
  - carried per-bank / per-channel / per-slot / per-core state is a
    *column* `(K, 1)`, gathered into rows through `(K, C)` one-hots and
    updated by lane reductions.

A row becomes a column through `flip` (an identity-masked lane max),
the one transpose form that lowers for any C.  No gathers, scatters,
sorts or rank-1 values appear anywhere.

Semantics (the reference per-request scan, `core.dram._reference_scan`):

  head      = ring[dir_idx % Q]       (in-flight window, per direction
                                       and — shared-DRAM — per channel)
  issue_ok  = max(t + shift, head)
  ready     = max(issue_ok, bank_free[bank])
  done      = max(ready + lat, bus_free[channel]) + busy
  shift    += max(0, issue_ok - (t + shift))   == running max of head - t

Within a chunk the serial recurrences are closed per fixed-point pass:
the channel chain as a weighted max-plus prefix (a masked column sum
builds the inclusive weight prefix W; the chain closes as
`colmax(mchan, s - W) + W`), the same-bank chain as a masked reduction
over the bank-latency prefix V, queue heads and previous same-bank
completions as one-hot gathers of the previous iterate.  The pass
operator is monotone from below and finalizes at least the first
not-yet-exact request per pass, so its least fixed point is the serial
result.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ... import spans
from ...core.accelerator import DramConfig
from ...core.dram import row_buffer_latency

# A plain Python float: module import may first happen inside a jit
# trace (lazy imports in core.replay), where creating a jnp scalar at
# module scope would leak a tracer into this global.
_NEG = float("-inf")
_INT_MIN = -(2 ** 31)


def _iota(shape, dim):
    """broadcasted_iota everywhere — 1-D iota does not lower on TPU."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def flip(x):
    """Row (1, n) <-> column (n, 1), exactly: an identity-masked max."""
    if x.dtype == jnp.bool_:
        return flip(x.astype(jnp.int32)) != 0
    n = max(x.shape)
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    low = _NEG if jnp.issubdtype(x.dtype, jnp.floating) else _INT_MIN
    return jnp.max(jnp.where(eye, x, low), axis=1 if x.shape[0] == 1 else 0,
                   keepdims=True)


def colmax(mask, x_col, fill=_NEG):
    """Per consumer (lane): max of the producer column over `mask`."""
    return jnp.max(jnp.where(mask, x_col, fill), axis=0, keepdims=True)


def colsum(mask, x_col):
    return jnp.sum(jnp.where(mask, x_col, 0), axis=0, keepdims=True)


def lanemax(mask, x_row, fill=_NEG):
    """Per state row (sublane): max of the request row over `mask`."""
    return jnp.max(jnp.where(mask, x_row, fill), axis=1, keepdims=True)


def _count(mask, axis):
    return jnp.sum(mask.astype(jnp.int32), axis=axis, keepdims=True)


class ChunkTables(NamedTuple):
    """Order-only per-chunk tables (no carried state involved).

    (C, C) masks are `mask[j, i]`: producer j (sublane) feeds consumer i
    (lane).  Request vectors are rows (1, C); per-state tables are
    (K, C) one-hots and (K, 1) columns.
    """
    mbank: jnp.ndarray      # (C, C) same-bank & valid-j & j <= i
    mchan: jnp.ndarray      # (C, C) same-channel & valid-j & j <= i
    mshift: jnp.ndarray     # (C, C) same-core & valid-j & j < i
    gprev: jnp.ndarray      # (C, C) one-hot pruned prev same-bank
    ghead: jnp.ndarray      # (C, C) one-hot in-chunk queue head src
    intra: jnp.ndarray      # (1, C) has a same-bank predecessor here
    row_prev: jnp.ndarray   # (1, C) its row (undefined where ~intra)
    W: jnp.ndarray          # (1, C) inclusive channel weight prefix
    bank_oh: jnp.ndarray    # (B, C) bank one-hot (valid only)
    chan_oh: jnp.ndarray    # (ch_n, C)
    core_oh: jnp.ndarray    # (n_cores, C)
    g_oh: jnp.ndarray       # (n_qg, C) queue-group one-hot
    qg: jnp.ndarray         # (1, C) queue group id
    rdx: jnp.ndarray        # (1, C) read index within (chunk, group)
    wdx: jnp.ndarray        # (1, C)
    nr: jnp.ndarray         # (n_qg, 1) reads per group in this chunk
    nw: jnp.ndarray         # (n_qg, 1)
    surv_r: jnp.ndarray     # (1, C) last writer of its ring slot
    surv_w: jnp.ndarray     # (1, C)
    last_b: jnp.ndarray     # (B, 1) chunk-local last request per bank
    last_c: jnp.ndarray     # (ch_n, 1)


def chunk_tables(fb, ch, row, w, v, cid, *, cfg: DramConfig, busy: float,
                 n_cores: int, n_qg: int) -> ChunkTables:
    """Everything about one chunk that depends only on stream order.
    Inputs are (1, C) rows: int32 `fb`/`ch`/`row`/`cid`, bool `w`/`v`."""
    C = fb.shape[-1]
    jj = _iota((C, C), 0)                # producer j (sublane)
    ii = _iota((C, C), 1)                # consumer i (lane)
    idx = _iota((1, C), 1)
    idx_c = _iota((C, 1), 0)
    low = jj <= ii
    strict = jj < ii
    fb_c, ch_c, cid_c, row_c = flip(fb), flip(ch), flip(cid), flip(row)
    v_c, w_c = flip(v), flip(w)

    same_bank = fb_c == fb
    mbank = same_bank & v_c & low
    prev = colmax(same_bank & v_c & strict, idx_c, -1)
    intra = prev >= 0
    prev_oh = (idx_c == prev) & intra
    row_prev = colmax(prev_oh, row_c, -1)
    lat_intra, _, _ = row_buffer_latency(
        cfg, jnp.where(intra, row_prev, -1), row)
    lat_intra = jnp.where(intra, lat_intra, 0).astype(jnp.float32)

    same_ch = ch_c == ch
    mchan = same_ch & v_c & low
    # channel max-plus edge: the bus burst, plus the row latency folded
    # in when the previous channel request sits on the same bank (bank
    # chains are subsequences of a channel chain, so contiguous
    # same-bank runs ride the channel closure)
    pin = colmax(same_ch & v_c & strict, idx_c, -1)
    pin_oh = (idx_c == pin) & (pin >= 0)
    linked = intra & (colmax(pin_oh, fb_c, -1) == fb)
    we = jnp.where(v, busy + jnp.where(linked, lat_intra, 0.0), 0.0)
    W = colsum(mchan, flip(we))
    # prune the iterated same-bank gather: links whose channel path
    # already outweighs their latency are provably dominated
    W_prev = colmax(prev_oh, flip(W), 0.0)
    prev_link = jnp.where(intra & (lat_intra + busy > W - W_prev), prev, -1)
    gprev = (idx_c == prev_link) & (prev_link >= 0)

    mshift = (cid_c == cid) & v_c & strict

    # queue groups + per-direction indices within (chunk, group)
    qg = ch if n_qg > 1 else jnp.zeros_like(fb)
    same_g = (ch_c == ch) if n_qg > 1 else jnp.ones((C, C), bool)
    rm, wm = v & ~w, v & w
    rm_c, wm_c = v_c & ~w_c, v_c & w_c
    rdx = _count(same_g & rm_c & strict, 0)
    wdx = _count(same_g & wm_c & strict, 0)
    g_oh = (_iota((n_qg, C), 0) == qg) & v
    nr = _count(g_oh & rm, 1)
    nw = _count(g_oh & wm, 1)

    # in-chunk queue-head source: the same-(group, direction) request
    # exactly Q back, when it falls inside this chunk
    Qr, Qw = cfg.read_queue, cfg.write_queue
    if Qr < C or Qw < C:
        rdx_c, wdx_c = flip(rdx), flip(wdx)
        eq_r = (rdx_c == rdx - Qr) & rm_c & rm & same_g
        eq_w = (wdx_c == wdx - Qw) & wm_c & wm & same_g
        # (a select between bool vectors does not lower: mask instead)
        ghead = (w & eq_w) | (~w & eq_r)
    else:
        ghead = jnp.zeros((C, C), bool)

    # ring survivors: a request is the last writer of its slot iff it is
    # among the last Q of its (group, direction) in the chunk
    nr_at = colsum(g_oh, nr)
    nw_at = colsum(g_oh, nw)
    surv_r = rm & (rdx + Qr >= nr_at)
    surv_w = wm & (wdx + Qw >= nw_at)

    ch_n = cfg.channels
    n_banks = ch_n * cfg.banks_per_channel
    bank_oh = (_iota((n_banks, C), 0) == fb) & v
    chan_oh = (_iota((ch_n, C), 0) == ch) & v
    core_oh = (_iota((n_cores, C), 0) == cid) & v
    last_b = lanemax(bank_oh, idx, -1)
    last_c = lanemax(chan_oh, idx, -1)

    return ChunkTables(
        mbank=mbank, mchan=mchan, mshift=mshift, gprev=gprev, ghead=ghead,
        intra=intra, row_prev=row_prev, W=W, bank_oh=bank_oh,
        chan_oh=chan_oh, core_oh=core_oh, g_oh=g_oh, qg=qg, rdx=rdx,
        wdx=wdx, nr=nr, nw=nw, surv_r=surv_r, surv_w=surv_w,
        last_b=last_b, last_c=last_c)


def iterate_fixed_point(one_pass, zero, *, cap: int, tol: float,
                        use_cond: bool):
    """The unified fixed-point contract, shared by every engine:

    seed `min(2, cap)` statically-unrolled passes; if the second pass
    still moved any completion by more than `tol` cycles, iterate a
    while_loop until converged, hard-capped at `cap` total passes
    (`max_passes` when the caller gave one, else C + 2 — each pass
    finalizes at least one request, so C passes always suffice).

    `use_cond=True` keeps the while_loop off the hot path behind a
    lax.cond (the XLA driver); the megakernel enters the while_loop
    directly (it runs zero iterations when converged — same semantics,
    and Mosaic prefers the single loop over a branched body).
    """
    if cap <= 1:
        return one_pass(zero)
    d0 = one_pass(zero)
    d1 = one_pass(d0)
    if cap <= 2:
        return d1

    def moved(a, b):
        # a max-reduce to a scalar (no bool reduction: Mosaic lowers
        # the f32 one)
        return jnp.max(b - a) > tol

    def cond_f(s):
        return jnp.logical_and(s[2] < cap, moved(s[0], s[1]))

    def body_f(s):
        return (s[1], one_pass(s[1]), s[2] + 1)

    @jax.named_scope(spans.ESCAPE)
    def _loop(dd):
        _, dn, _ = jax.lax.while_loop(cond_f, body_f,
                                      (dd[0], dd[1], jnp.int32(2)))
        return dn

    if not use_cond:
        return _loop((d0, d1))
    return jax.lax.cond(moved(d0, d1), _loop, lambda dd: dd[1], (d0, d1))


class ChunkState(NamedTuple):
    """Architectural state carried across chunks (one stream; columns)."""
    bank_free: jnp.ndarray   # (B, 1)
    bus_free: jnp.ndarray    # (ch_n, 1)
    ring_r: jnp.ndarray      # (n_qg * Qr, 1) in-flight read completions
    ring_w: jnp.ndarray      # (n_qg * Qw, 1)
    ir: jnp.ndarray          # (n_qg, 1) reads admitted so far
    iw: jnp.ndarray          # (n_qg, 1)
    shift: jnp.ndarray       # (n_cores, 1) queue backpressure


def init_state(*, n_banks: int, ch_n: int, n_qg: int, Qr: int, Qw: int,
               n_cores: int) -> ChunkState:
    f32 = jnp.float32
    return ChunkState(
        bank_free=jnp.zeros((n_banks, 1), f32),
        bus_free=jnp.zeros((ch_n, 1), f32),
        ring_r=jnp.zeros((n_qg * Qr, 1), f32),
        ring_w=jnp.zeros((n_qg * Qw, 1), f32),
        ir=jnp.zeros((n_qg, 1), jnp.int32),
        iw=jnp.zeros((n_qg, 1), jnp.int32),
        shift=jnp.zeros((n_cores, 1), f32))


def chunk_resolve(state: ChunkState, tab: ChunkTables, t, lat, w, v, *,
                  cfg: DramConfig, busy: float, max_passes: Optional[int],
                  tol: float):
    """Resolve one chunk's completion times against the carried state and
    advance the state.  `t`, `lat`, `w`, `v` are (1, C) rows; `lat` is
    the full per-request row-buffer latency (the caller classifies
    first-per-bank-in-chunk requests against its open-row view).

    Returns (new_state, done) — `done` is a (1, C) row, 0 where ~valid.
    """
    Qr, Qw = cfg.read_queue, cfg.write_queue
    C = t.shape[-1]
    f32 = jnp.float32
    lat = lat.astype(f32)

    # carried-state gathers as one-hot contractions (state column ->
    # request row)
    bank0 = colsum(tab.bank_oh, state.bank_free)
    bus0 = colsum(tab.chan_oh, state.bus_free)
    shift0 = colsum(tab.core_oh, state.shift)
    slot_r = tab.qg * Qr + (tab.rdx + colsum(tab.g_oh, state.ir)) % Qr
    slot_w = tab.qg * Qw + (tab.wdx + colsum(tab.g_oh, state.iw)) % Qw

    def ring_oh(ring, slot):
        return _iota((ring.shape[0], C), 0) == slot

    head0 = jnp.where(w, colsum(ring_oh(state.ring_w, slot_w), state.ring_w),
                      colsum(ring_oh(state.ring_r, slot_r), state.ring_r))
    intra_heads = Qr < C or Qw < C
    W = tab.W
    V = colsum(tab.mbank, flip(jnp.where(v, lat + busy, 0.0)))

    def heads(done_c):
        if intra_heads:
            head = jnp.maximum(head0, colmax(tab.ghead, done_c))
        else:
            head = head0
        g = jnp.where(v, head - t, _NEG)
        return head, g

    def issue_gate(done_c):
        head, g = heads(done_c)
        ss = jnp.maximum(shift0, colmax(tab.mshift, flip(g)))
        return jnp.maximum(t + ss, head)

    if not intra_heads:
        # heads and the shift gate do not depend on the iterate
        issue_ok0 = issue_gate(None)

    def one_pass(done):
        done_c = flip(done)
        issue_ok = issue_gate(done_c) if intra_heads else issue_ok0
        bankp = jnp.maximum(bank0, colmax(tab.gprev, done_c))
        # seed with the previous iterate so bank-raised completions of
        # other banks propagate down the channel chain across passes
        s = jnp.maximum(jnp.maximum(issue_ok, bankp) + lat + busy, done)
        u = jnp.maximum(colmax(tab.mchan, flip(jnp.where(v, s - W, _NEG)))
                        + W, bus0 + W)
        d = colmax(tab.mbank, flip(jnp.where(v, u - V, _NEG))) + V
        return jnp.where(v, d, 0.0)

    cap = (C + 2) if max_passes is None else max_passes
    done = iterate_fixed_point(one_pass, jnp.zeros(t.shape, f32),
                               cap=cap, tol=tol, use_cond=False)

    # final derived state (lane reductions: request row -> state column)
    _, g = heads(flip(done) if intra_heads else None)
    shift = jnp.maximum(state.shift, lanemax(tab.core_oh, g))

    idx = _iota((1, C), 1)
    bank_free = jnp.where(
        tab.last_b >= 0, lanemax(tab.bank_oh & (idx == tab.last_b), done,
                                 0.0), state.bank_free)
    bus_free = jnp.where(
        tab.last_c >= 0, lanemax(tab.chan_oh & (idx == tab.last_c), done,
                                 0.0), state.bus_free)

    def ring_write(ring, slot, surv):
        # slot k takes the done of its surviving writer, if any
        oh = ring_oh(ring, slot) & surv
        hit = jnp.max(oh.astype(jnp.int32), axis=1, keepdims=True) > 0
        return jnp.where(hit, lanemax(oh, done), ring)

    new_state = ChunkState(
        bank_free=bank_free, bus_free=bus_free,
        ring_r=ring_write(state.ring_r, slot_r, tab.surv_r),
        ring_w=ring_write(state.ring_w, slot_w, tab.surv_w),
        ir=state.ir + tab.nr, iw=state.iw + tab.nw, shift=shift)
    return new_state, done
