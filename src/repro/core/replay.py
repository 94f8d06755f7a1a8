"""Chunked bank-parallel DRAM replay (the trace-fidelity hot path).

`core.dram.simulate_dram` and `trace.contention.simulate_shared_dram`
originally replayed demand streams with a per-request `lax.scan` —
thousands of sequential steps, each a handful of dynamic `.at[fb]`
updates.  That serialization is what made trace-fidelity sweeps ~27x
slower than fast fidelity.  This module replays the same timing model in
fixed-size request chunks; inside a chunk everything is vectorized, and
the chunk loop carries only the true architectural state (per-bank
free time, per-channel bus time, in-flight rings, queue counters,
per-core shift) so chunk boundaries are invisible.

  order-only precompute (exact, hoisted out of the chunk scan)
    Row-buffer state is "last writer wins" per bank, so *everything
    about classification* — each request's previous-same-bank link, its
    row hit/empty/conflict class, and its access latency — depends only
    on stream order, never on timing.  In-chunk links are built in two
    exact levels (shifted compares + per-(bank, subblock) last
    occurrence); cross-chunk links come from a per-(bank, chunk)
    last-occurrence table prefixed over the chunk axis.  The whole
    stream is therefore classified in wide fused ops *before* the scan
    — no open-row carry remains — and the counters are bit-identical to
    the reference scan by construction.  Queue-slot indices, ring
    survivors, weighted channel prefixes and per-bank/per-channel last
    requests are likewise order-only and hoisted.

  chunk resolve (two exact closures + fixed point)
    Completion times obey
        done_i = max(max(issue_ok_i, bankdone_prev(i)) + lat_i,
                     done_prev_on_channel) + busy
    Per pass, the channel chain D_m = max(s_m, D_{m-1} + w_m) is closed
    exactly as a weighted max-plus prefix (D = W + cummax(s - W),
    W = cumsum(w), with the row-buffer lat of contiguous same-bank runs
    folded into the channel edge — a bank maps to exactly one channel,
    so bank chains live inside a channel's subsequence), and same-bank
    chains are closed by one masked (chunk, chunk) row reduction over
    the per-bank weighted prefix.  Queue backpressure `shift` is a
    per-core running max of (queue_head - t).  Each pass seeds the
    closures with the previous iterate (so bank-raised completions of
    other banks propagate down the channel chain), plus a pruned
    same-bank gather (links whose channel path already outweighs their
    lat are provably dominated and dropped) and intra-chunk queue heads
    when a queue is shorter than the chunk.

  fixed-point contract (identical under every chunked engine; see
  `kernels.replay.chunkmath.iterate_fixed_point`)
    Two statically-unrolled passes of the monotone closure operator;
    if the second pass still moved a completion by more than `tol`
    cycles (default 0.25) the iteration continues in a while_loop until
    converged, capped at `max_passes` total passes when given, else
    chunk + 2 (each pass finalizes at least the first not-yet-exact
    request, so the cap never binds).  `tol=0.0` reaches the exact
    fixed point under every engine — `simulate_shared_dram`'s
    private-channel decomposition invariant relies on that.

Bit-exactness: classification counts are exact.  Completion/stall times
agree with the reference scan up to f32 rounding (the closed-form
chains compute `s + W` where the scan repeatedly adds `busy`), which is
why the differential suite pins counts exactly and times to a tight
relative tolerance — and bit-for-bit when `busy` is exactly
representable.

Engines:
  "xla"       this scan driver: hoisted precompute + a `lax.scan` over
              chunks, tuned for XLA's strengths (take_along_axis
              gathers, log-step shift-reduce prefixes, no sorts or
              scatters).  Batch-native: leading batch dims (design
              grids, op batches) flow through the same ops, so a sweep
              replays a whole (designs, ops) stream batch in one scan.
              Default engine.
  "pallas"    the fused trace-replay megakernel
              (`kernels.replay.megakernel`): one `pallas_call`, streams
              flattened along the grid, the per-stream chunk loop and
              all architectural state resident in VMEM/registers, the
              chunk math expressed as masked one-hot contractions
              (`kernels.replay.chunkmath`).  Batch-native from day one.
              Off-TPU the compiled kernel is unavailable and dispatch
              *resolves* (never silently — see
              `resolve_engine_runtime`, whose label callers record in
              result metadata) to interpret mode (`interpret=True`: the
              literal kernel body on CPU, used by the differential
              suite) or to this module's XLA driver ("pallas:twin").
  "reference" the original per-request scan
              (`core.dram._reference_scan`), kept for differential
              testing and as the semantics oracle (1-D streams).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import spans
from .accelerator import DramConfig
from .dram import row_buffer_latency

ENGINES = ("xla", "pallas", "reference")
# The chunked scan driver stays the default engine; "pallas" resolves to
# the megakernel on TPU (and to this driver off-TPU — recorded, never
# silent).  Set to "reference" to restore the legacy per-request scan.
DEFAULT_ENGINE = "xla"
DEFAULT_CHUNK = 64
# Fixed-point stopping threshold (cycles): a pass that moves no completion
# by more than this ends the iteration.  tol=0.0 = exact fixed point.
DEFAULT_TOL = 0.25
_SUB = 16                     # subblock size for the prev-bank summaries


def resolve_engine(engine: Optional[str]) -> str:
    eng = DEFAULT_ENGINE if engine is None else engine
    if eng not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    return eng


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_engine_runtime(engine: Optional[str],
                           interpret: Optional[bool] = None) -> str:
    """The engine that will actually execute on this backend.

    "pallas" is a *request*; what runs depends on the runtime:
      - on TPU: the compiled megakernel        -> "pallas"
      - off-TPU, interpret=True: the literal kernel body under the
        Pallas interpreter (slow; the differential suite uses this to
        execute the megakernel on CPU)          -> "pallas:interpret"
      - off-TPU otherwise: this module's XLA scan driver
                                               -> "pallas:twin"
    The label is recorded in `NetworkReport.engine` / Study frames so a
    fallback is never silent.  "xla" and "reference" resolve to
    themselves.
    """
    eng = resolve_engine(engine)
    if eng != "pallas":
        return eng
    if interpret is True:
        return "pallas:interpret" if _default_interpret() else "pallas"
    if _default_interpret():
        return "pallas:twin"
    return "pallas"


def _shifted(x: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    """x shifted right by k along the last axis, filled with `fill`."""
    pad = [(0, 0)] * (x.ndim - 1) + [(k, 0)]
    return jnp.pad(x, pad, constant_values=fill)[..., :-k]


def _cummax(x: jnp.ndarray, *, exclusive: bool = False,
            fill=-jnp.inf) -> jnp.ndarray:
    """Running max along the last axis via log-step shift-reduce (fused
    pad/max chains instead of the generic associative-scan recursion)."""
    if exclusive:
        x = _shifted(x, 1, fill)
    n = x.shape[-1]
    k = 1
    while k < n:
        x = jnp.maximum(x, _shifted(x, k, fill))
        k *= 2
    return x


def _cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running sum along the last axis (log-step doubling)."""
    n = x.shape[-1]
    fill = 0 if jnp.issubdtype(x.dtype, jnp.integer) else 0.0
    k = 1
    while k < n:
        x = x + _shifted(x, k, fill)
        k *= 2
    return x


def _rmax(x: jnp.ndarray) -> jnp.ndarray:
    """Max-reduce the last axis via an explicit halving tree.  XLA:CPU
    lowers plain row reductions to reduce-window, which benches ~2x
    slower than this form on the hot shapes; max is idempotent, so an
    odd length just overlaps the middle element."""
    n = x.shape[-1]
    while n > 1:
        h = (n + 1) // 2
        x = jnp.maximum(x[..., :h], x[..., n - h:n])
        n = h
    return x[..., 0]


def _take(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Batched gather along the last axis."""
    return jnp.take_along_axis(x, idx, axis=-1)


def _take_guard(x: jnp.ndarray, idx: jnp.ndarray, default) -> jnp.ndarray:
    """Gather along the last axis; idx < 0 yields `default`."""
    got = _take(x, jnp.maximum(idx, 0))
    return jnp.where(idx >= 0, got, default)


# --------------------------------------------------------------------------
# Order-only stream precompute (wide fused ops, outside the scan).
# Per-chunk inputs are (nc, ..., C): the leading chunk axis is just
# another batch dim for the in-chunk tables, and the axis the global
# classification prefixes over.
# --------------------------------------------------------------------------

def _precompute_stream(t, fb, ch, row, w, v, cid, row_flat, v_flat, *,
                       cfg: DramConfig, busy: float, n_cores: int,
                       n_qg: int):
    C = t.shape[-1]
    nc = t.shape[0]
    f32 = jnp.float32
    ch_n = cfg.channels
    n_banks = ch_n * cfg.banks_per_channel
    Qr, Qw = cfg.read_queue, cfg.write_queue
    i_idx = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), fb.shape)
    r_mask = v & ~w
    w_mask = v & w
    qg = ch if n_qg > 1 else jnp.zeros_like(fb)

    # ---- previous same-bank link, two exact levels ------------------------
    # near links (closer than a subblock) by shifted compares; the same
    # shifted masks also accumulate the near part of the bank-closure
    # prefix Vr (filled in after lat exists, via the saved masks)
    prev_near = jnp.full(fb.shape, -1, jnp.int32)
    near_hits = []
    for k in range(1, _SUB):
        hitk = (_shifted(fb, k, -1) == fb) & _shifted(v, k, False)
        near_hits.append(hitk)
        prev_near = jnp.maximum(prev_near,
                                jnp.where(hitk, i_idx - k, -1))
    # far: per-(bank, subblock) last occurrence, prefixed over subblocks
    nsub = -(-C // _SUB)
    pad_c = nsub * _SUB - C

    def _sb(x, fill, red):
        if pad_c:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad_c)],
                        constant_values=fill)
        x = x.reshape(x.shape[:-1] + (nsub, _SUB))
        return _rmax(x) if red is jnp.max else jnp.sum(x, axis=-1)

    bank_oh = (jnp.arange(n_banks)[:, None] == fb[..., None, :]) & \
        v[..., None, :]                                     # (nc,...,B,C)
    marked = jnp.where(bank_oh, i_idx[..., None, :], -1)
    last_sb = _sb(marked, -1, jnp.max)                      # (...,B,nsub)
    prev_sb = _cummax(last_sb, exclusive=True, fill=-1)
    last_b = _rmax(last_sb)                                 # (nc,...,B)
    sb_idx = i_idx // _SUB

    def _from_sb(tbl):
        """tbl (..., B, nsub) -> per-request value at (fb_i, subblock_i):
        one flat gather over the fused (bank, subblock) axis."""
        flat = tbl.reshape(tbl.shape[:-2] + (n_banks * nsub,))
        return _take(flat, fb * nsub + sb_idx)

    prev_far = _from_sb(prev_sb)
    prev_bank = jnp.maximum(prev_near, prev_far)
    intra = prev_bank >= 0

    # ---- global classification (no scan, no open-row carry) --------------
    # cross-chunk links: a bank's last request before this chunk is an
    # exclusive running max of its per-chunk last occurrence (as global
    # stream positions) over the chunk axis
    cidx = jnp.reshape(jnp.arange(nc, dtype=jnp.int32),
                       (nc,) + (1,) * (fb.ndim - 1))
    last_b_g = jnp.where(last_b >= 0, cidx * C + last_b, -1)

    def _shift_c(x, k):
        # shift down the leading chunk axis (log-step cummax building
        # block; lax.cummax lowers to slow reduce-window on CPU)
        padn = [(k, 0)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, padn, constant_values=-1)[:-k]

    before = _shift_c(last_b_g, 1)
    k = 1
    while k < nc:
        before = jnp.maximum(before, _shift_c(before, k))
        k *= 2
    cross = _take(before, fb)                               # (nc,...,C)
    gprev = jnp.where(intra, cidx * C + prev_bank, cross)
    gp = jnp.moveaxis(gprev, 0, -2).reshape(row_flat.shape)
    seen = jnp.where(gp >= 0, _take(row_flat, jnp.maximum(gp, 0)), -1)
    lat_flat, hit, empty = row_buffer_latency(cfg, seen, row_flat)
    hits = jnp.sum(hit & v_flat, axis=-1)
    misses = jnp.sum(empty & v_flat, axis=-1)
    conflicts = jnp.sum((~hit) & (~empty) & v_flat, axis=-1)
    batch = row_flat.shape[:-1]
    lat = jnp.moveaxis(
        lat_flat.astype(f32).reshape(batch + (nc, C)), -2, 0)
    lat_intra = jnp.where(intra, lat, 0.0)

    # bank-closure prefix Vr_i = sum of (lat + busy) over same-bank
    # intra-linked j <= i, with the same near/far split (offsets cancel
    # within a bank); first-per-bank requests carry no in-chunk edge
    w_bank = jnp.where(v & intra, lat_intra + busy, 0.0)
    v_near = w_bank
    sb_pos = i_idx % _SUB
    for k in range(1, _SUB):
        ok = near_hits[k - 1] & (sb_pos >= k)
        v_near = v_near + jnp.where(ok, _shifted(w_bank, k, 0.0), 0.0)
    wsb = _sb(jnp.where(bank_oh, w_bank[..., None, :], 0.0), 0.0, jnp.sum)
    Vfar_sb = _cumsum(wsb) - wsb                            # exclusive
    Vr = v_near + _from_sb(Vfar_sb)

    # channel segments (thin, stacked over the few channels): weighted
    # edge prefixes fold the lat of contiguous same-bank runs into the
    # channel chain
    chan_oh = (jnp.arange(ch_n)[:, None] == ch[..., None, :]) & \
        v[..., None, :]                                     # (...,ch_n,C)
    pin = _cummax(jnp.where(chan_oh, i_idx[..., None, :], -1),
                  exclusive=True, fill=-1)
    fb_pin = _take(fb, jnp.maximum(pin, 0).reshape(
        pin.shape[:-2] + (ch_n * C,))).reshape(pin.shape)
    linked = chan_oh & (pin >= 0) & (fb_pin == fb[..., None, :])
    we = jnp.where(chan_oh,
                   busy + jnp.where(linked, lat_intra[..., None, :], 0.0),
                   0.0)
    chan_W = _cumsum(we)                                    # (...,ch_n,C)
    chan_last = _rmax(jnp.where(chan_oh, i_idx[..., None, :], -1))
    flatW = chan_W.reshape(chan_W.shape[:-2] + (ch_n * C,))
    W_all = _take(flatW, ch * C + i_idx)

    # Bank links whose channel path already outweighs their lat can never
    # dominate (completions grow by >= W_i - W_p along the path): prune
    # them from the iterated gather.  Exact — only provably-dominated
    # max() terms go; what survives feeds the next pass's channel
    # closure so bank-raised completions propagate into channel chains.
    W_prev = jnp.where(intra, _take(W_all, jnp.maximum(prev_bank, 0)), 0.0)
    prev_link = jnp.where(intra & (lat_intra + busy > W_all - W_prev),
                          prev_bank, -1)

    # ---- in-flight-window direction indices per queue group ---------------
    rdx = jnp.zeros_like(fb)
    wdx = jnp.zeros_like(fb)
    nr, nw = [], []
    for g in range(n_qg):
        rm = r_mask & (qg == g)
        d = _cumsum(rm.astype(jnp.int32)) - rm
        rdx = jnp.where(rm, d, rdx)
        nr.append(jnp.sum(rm, axis=-1))
        wm = w_mask & (qg == g)
        d = _cumsum(wm.astype(jnp.int32)) - wm
        wdx = jnp.where(wm, d, wdx)
        nw.append(jnp.sum(wm, axis=-1))
    nr = jnp.stack(nr, axis=-1)                             # (..., n_qg)
    nw = jnp.stack(nw, axis=-1)

    # intra-chunk queue-head sources exist only when a queue is shorter
    # than the chunk (src = request of the read/write Q back)
    src = jnp.full(fb.shape, -1, jnp.int32)
    if Qr < C or Qw < C:
        same_g = qg[..., None, :] == qg[..., :, None]
        eq_r = (rdx[..., None, :] == (rdx[..., :, None] - Qr)) & \
            r_mask[..., None, :] & r_mask[..., :, None] & same_g
        eq_w = (wdx[..., None, :] == (wdx[..., :, None] - Qw)) & \
            w_mask[..., None, :] & w_mask[..., :, None] & same_g
        eq = jnp.where(w[..., :, None], eq_w, eq_r)
        src = _rmax(jnp.where(eq, i_idx[..., None, :], -1))

    # ring survivors: for residue s0 = d %% Q, the surviving writer is the
    # request with the largest direction index d >= n_dir - Q (if any);
    # the slot it lands in is (s0 + idx0) %% Q — a rotation applied at
    # scan time with the carried queue counter.
    def survivors(mask, dix, ndir, Q):
        if Q >= C:
            # every chunk request survives (dix < C <= Q) and residues
            # are the direction indices themselves, which are monotone
            # over the masked subsequence — so the map residue -> source
            # is a searchsorted over the mask's running count, done as a
            # branchless binary search (log C thin gathers; never
            # materializes the (C, C) equality map)
            cs = _cumsum(mask.astype(jnp.int32))
            q = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                 mask.shape)
            pos = jnp.zeros_like(q)     # running #{i : cs_i <= q}
            step = 1
            while step < C:
                step *= 2
            step //= 2
            while step >= 1:
                nxt = pos + step
                val = _take(cs, jnp.minimum(nxt, C) - 1)
                pos = jnp.where((nxt <= C) & (val <= q), nxt, pos)
                step //= 2
            got = jnp.where(cs[..., -1:] > q, pos, -1)
            padq = [(0, 0)] * (got.ndim - 1) + [(0, Q - C)]
            return jnp.pad(got, padq, constant_values=-1)
        surv = mask & (dix + Q >= _take(ndir, qg))
        oh = (jnp.arange(Q)[:, None] == (dix % Q)[..., None, :]) & \
            surv[..., None, :]                              # (..., Q, C)
        return _rmax(jnp.where(oh, i_idx[..., None, :], -1))

    ring_src_r = jnp.stack(
        [survivors(r_mask & (qg == g), rdx, nr, Qr)
         for g in range(n_qg)], axis=-2)                    # (..., n_qg, Q)
    ring_src_w = jnp.stack(
        [survivors(w_mask & (qg == g), wdx, nw, Qw)
         for g in range(n_qg)], axis=-2)

    core_mask = jnp.stack([v & (cid == s) for s in range(n_cores)],
                          axis=-2)                          # (..., cores, C)
    pre = dict(
        lat=lat, prev_link=prev_link, Vr=Vr, chan_oh=chan_oh,
        chan_W=chan_W, chan_last=chan_last, last_b=last_b, qg=qg,
        rdx=rdx, wdx=wdx, src=src, nr=nr, nw=nw, ring_src_r=ring_src_r,
        ring_src_w=ring_src_w, core_mask=core_mask)
    return pre, hits, misses, conflicts


# --------------------------------------------------------------------------
# One chunk: carry-dependent resolve (runs inside the scan; batch-native)
# --------------------------------------------------------------------------

def _chunk_step(carry, x, *, cfg: DramConfig, busy: float,
                max_passes: Optional[int], tol: float, n_cores: int,
                n_qg: int):
    from ..kernels.replay.chunkmath import iterate_fixed_point

    (bank_free, bus_free, ring_r, ring_w, ir, iw, shift) = carry
    t, fb, w, v, cid, pre = x
    C = t.shape[-1]
    Qr, Qw = cfg.read_queue, cfg.write_queue
    f32 = jnp.float32
    neg = f32(-jnp.inf)
    i_idx = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), fb.shape)

    lat = pre["lat"]
    qg = pre["qg"]
    ir_g = ir[..., 0:1] if n_qg == 1 else _take(ir, qg)
    iw_g = iw[..., 0:1] if n_qg == 1 else _take(iw, qg)
    sl_r = (pre["rdx"] + ir_g) % Qr
    sl_w = (pre["wdx"] + iw_g) % Qw
    flat_rr = ring_r.reshape(ring_r.shape[:-2] + (n_qg * Qr,))
    flat_rw = ring_w.reshape(ring_w.shape[:-2] + (n_qg * Qw,))
    head0 = jnp.where(w, _take(flat_rw, qg * Qw + sl_w),
                      _take(flat_rr, qg * Qr + sl_r))
    head_src = pre["src"]
    prev_link = pre["prev_link"]
    Vr = pre["Vr"]
    chan_oh, chan_W = pre["chan_oh"], pre["chan_W"]
    core_mask = pre["core_mask"]
    bank0 = _take(bank_free, fb)
    shift0 = shift[..., 0:1] if n_cores == 1 else _take(shift, cid)
    bus_W = bus_free[..., None] + chan_W
    # bank-closure mask: order-only, rebuilt per step (cheap broadcast
    # compares; materializing it in the hoisted precompute would stream
    # (chunks, C, C) tensors through memory instead)
    jlt = jnp.arange(C, dtype=jnp.int32)
    mbank = (fb[..., None, :] == fb[..., :, None]) & v[..., None, :] & \
        (jlt[None, :] <= jlt[:, None])
    intra_heads = Qr < C or Qw < C

    def _issue_ok(done):
        # queue backpressure: heads (and hence shift and issue gates)
        # depend on `done` only when a queue is shorter than the chunk —
        # on realistic configs this whole block is pass-invariant and
        # hoists out of the fixed-point iteration
        if intra_heads:
            head = jnp.maximum(head0, _take_guard(done, head_src, neg))
        else:
            head = head0
        g = jnp.where(v, head - t, neg)
        if n_cores == 1:
            ss = jnp.maximum(shift0,
                             _cummax(g, exclusive=True))
        else:
            gs = jnp.where(core_mask, g[..., None, :], neg)
            ss_c = jnp.maximum(shift[..., None],
                               _cummax(gs, exclusive=True))
            ss = _take(ss_c.reshape(ss_c.shape[:-2] + (n_cores * C,)),
                       cid * C + i_idx)
        return jnp.maximum(t + ss, head), g

    if not intra_heads:
        issue_ok0, g0 = _issue_ok(None)

    def one_pass(done):
        if intra_heads:
            issue_ok, _ = _issue_ok(done)
        else:
            issue_ok = issue_ok0
        bankp = jnp.maximum(bank0, _take_guard(done, prev_link, neg))
        # seed the closures with the previous iterate: completions grow
        # by at least the channel edge weights, so done_j + (W_i - W_j)
        # is a true lower bound — this is how bank-raised completions of
        # *other* banks propagate down the channel chain across passes
        s_src = jnp.maximum(jnp.maximum(issue_ok, bankp) + lat + busy,
                            done)
        # channel closure: weighted max-plus prefix, stacked over the
        # few channels (thin log-step scans; un-stacked by a masked sum
        # over the short channel axis — cheaper than a gather)
        gg = jnp.where(chan_oh, s_src[..., None, :] - chan_W, neg)
        u_c = jnp.maximum(_cummax(gg) + chan_W, bus_W)
        u = jnp.sum(jnp.where(chan_oh, u_c, 0.0), axis=-2)
        # bank closure: one masked (C, C) row reduction (banks are many,
        # so the matrix contraction beats a per-bank stacked scan)
        d = _rmax(jnp.where(mbank, jnp.where(v, u - Vr, neg)[
            ..., None, :], neg)) + Vr
        return jnp.where(v, d, 0.0)

    done = iterate_fixed_point(
        one_pass, jnp.zeros(t.shape, f32),
        cap=(C + 2) if max_passes is None else max_passes,
        tol=tol, use_cond=True)

    # ---- final derived state + carry update (gathers only) ---------------
    if intra_heads:
        _, g = _issue_ok(done)
    else:
        g = g0
    shift = jnp.maximum(
        shift, _rmax(jnp.where(core_mask, g[..., None, :], neg)))

    lb = pre["last_b"]
    bank_free = jnp.where(lb >= 0, _take(done, jnp.maximum(lb, 0)),
                          bank_free)

    lc = pre["chan_last"]
    bus_free = jnp.where(lc >= 0, _take(done, jnp.maximum(lc, 0)),
                         bus_free)

    # rings: rotate the carry-free survivor map by the carried counter
    def ring_update(ring, ring_src, idx0, Q):
        s0 = (jnp.arange(Q) - idx0[..., None]) % Q          # (..., n_qg, Q)
        srcs = jnp.take_along_axis(ring_src, s0, axis=-1)
        flat = srcs.reshape(srcs.shape[:-2] + (n_qg * Q,))
        got = _take_guard(done, flat, 0.0).reshape(srcs.shape)
        return jnp.where(srcs >= 0, got, ring)

    ring_r = ring_update(ring_r, pre["ring_src_r"], ir, Qr)
    ring_w = ring_update(ring_w, pre["ring_src_w"], iw, Qw)
    ir = ir + pre["nr"]
    iw = iw + pre["nw"]

    new_carry = (bank_free, bus_free, ring_r, ring_w, ir, iw, shift)
    return new_carry, (done, jnp.where(v, done - t, 0.0))


# --------------------------------------------------------------------------
# Stream-level driver: hoisted precompute + scan over chunks
# --------------------------------------------------------------------------

def replay_decoded(t_issue, flat_bank, ch, row, is_write, valid,
                   cfg: DramConfig, gran_bytes: int = 64, *,
                   engine: str = "xla", chunk: Optional[int] = None,
                   max_passes: Optional[int] = None,
                   tol: float = DEFAULT_TOL, n_cores: int = 1,
                   core_id=None, per_channel_queues: bool = False,
                   interpret: Optional[bool] = None):
    """Chunked replay of a pre-decoded request stream.

    Batch-native under every chunked engine: inputs may carry leading
    batch dimensions (`(..., n)`) and the replay processes the whole
    batch in one chunk scan ("xla") or one fused kernel launch
    ("pallas") — this is how `Simulator.sweep` replays a (designs, ops)
    stream batch without a vmap wrapper.  Pure traced function (safe
    under jit/vmap; `cfg`, `gran_bytes` and the keyword knobs must be
    static in a jitted caller).  Returns a dict with the raw
    per-request completion times `done` (undefined where ~valid —
    callers substitute their engine's no-op value), per-request
    round-trip `latency`, the per-core backpressure `shift` (shape
    (..., n_cores)), and the exact row hit/empty/conflict counters.

    per_channel_queues selects the shared-DRAM semantics (per-channel
    in-flight rings, per-core shift) of `simulate_shared_dram`; the
    default matches `simulate_dram`'s single global ring pair.  tol is
    the fixed-point stopping threshold in cycles (0.0 = iterate to the
    exact fixed point); max_passes caps the per-chunk pass count under
    both chunked engines (None = chunk + 2, enough for any stream).

    engine="pallas" dispatches per `resolve_engine_runtime`: the fused
    megakernel on TPU (or, with interpret=True, the literal kernel body
    under the Pallas interpreter), this driver otherwise.
    """
    n = t_issue.shape[-1]
    batch = t_issue.shape[:-1]
    passes = None if max_passes is None else max(1, int(max_passes))

    if core_id is None:
        core_id = jnp.zeros(t_issue.shape, jnp.int32)

    if engine == "pallas":
        resolved = resolve_engine_runtime("pallas", interpret)
        if resolved != "pallas:twin":
            # on TPU this is always the compiled kernel: it runs or
            # raises (the megakernel picks its own lane-aligned chunk)
            from ..kernels.replay.megakernel import replay_megakernel
            return replay_megakernel(
                t_issue, flat_bank, ch, row, is_write, valid, cfg,
                gran_bytes, chunk=chunk, max_passes=passes, tol=float(tol),
                n_cores=n_cores, core_id=core_id,
                per_channel_queues=per_channel_queues,
                interpret=(resolved == "pallas:interpret"))
        # fall through, off-TPU only: the twin is this driver (same
        # model, same fixed-point contract; the megakernel is
        # differentially pinned to it and to the reference oracle)

    C = DEFAULT_CHUNK if chunk is None else int(chunk)
    C = max(1, min(C, max(n, 1)))
    ch_n, bk_n = cfg.channels, cfg.banks_per_channel
    Qr, Qw = cfg.read_queue, cfg.write_queue
    n_qg = ch_n if per_channel_queues else 1
    busy = float(max(1.0, gran_bytes / cfg.bandwidth_bytes_per_cycle))
    f32 = jnp.float32

    pad = (-n) % C
    nc = (n + pad) // C

    def _flat(x, fill, dtype):
        x = jnp.broadcast_to(jnp.asarray(x).astype(dtype), batch + (n,))
        if pad:
            x = jnp.concatenate(
                [x, jnp.full(batch + (pad,), fill, dtype)], axis=-1)
        return x

    def _chunked(x):
        # (..., nc*C) -> (nc, ..., C): the chunk axis leads for the scan
        return jnp.moveaxis(x.reshape(batch + (nc, C)), -2, 0)

    rowf = _flat(row, 0, jnp.int32)
    vf = _flat(valid, False, bool)
    xs = tuple(_chunked(x) for x in (
        _flat(t_issue, 0.0, f32), _flat(flat_bank, 0, jnp.int32),
        _flat(ch, 0, jnp.int32), rowf,
        _flat(is_write, False, bool), vf,
        _flat(core_id, 0, jnp.int32)))

    with jax.named_scope(spans.PRECOMPUTE):
        pre, hits, misses, conflicts = _precompute_stream(
            *xs, rowf, vf, cfg=cfg, busy=busy, n_cores=n_cores, n_qg=n_qg)

    carry0 = (jnp.zeros(batch + (ch_n * bk_n,), f32),
              jnp.zeros(batch + (ch_n,), f32),
              jnp.zeros(batch + (n_qg, Qr), f32),
              jnp.zeros(batch + (n_qg, Qw), f32),
              jnp.zeros(batch + (n_qg,), jnp.int32),
              jnp.zeros(batch + (n_qg,), jnp.int32),
              jnp.zeros(batch + (n_cores,), f32))

    step = functools.partial(
        _chunk_step, cfg=cfg, busy=busy, max_passes=passes,
        tol=float(tol), n_cores=n_cores, n_qg=n_qg)
    with jax.named_scope(spans.CHUNK_SCAN):
        carry, (done, rt) = jax.lax.scan(
            step, carry0, (xs[0], xs[1], xs[4], xs[5], xs[6], pre))

    def _unchunk(y):
        return jnp.moveaxis(y, 0, -2).reshape(batch + (nc * C,))[..., :n]

    return dict(done=_unchunk(done), latency=_unchunk(rt),
                shift=carry[6], hits=hits, misses=misses,
                conflicts=conflicts)
