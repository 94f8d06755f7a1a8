"""Plain reference of what one Study cell reports.

A straightforward, per-op re-statement of the simulator's documented
semantics (SCALE-Sim v3 analytic stages, the dataflow-aware DRAM demand
trace and the Ramulator-like per-request DRAM timing model), written in
numpy and Python loops.  It imports nothing of the program under test
and takes nothing the program has made: every input comes from the
configuration file and the design the benchmark drew.

`cell_metrics(design, gemms, fidelity, cfg, num=float)` returns the
frame columns of one (design, workload, fidelity) cell.  `num` is the
number type every value is carried in: Python `float` (float64) for the
reference, `ml_dtypes.bfloat16` for the control that computes the same
thing one precision below the configuration's float32.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

# one address region per operand, 32 MiB apart (generator contract)
REGION_SPAN = 1 << 25
SAMPLE_RUN = 64          # granules per contiguous run of a compressed stream

# ws: the ifmap walks down its rows, the filter along its columns and the
# psums drain row-fast; os: both operands stream k-fast and outputs drain
# column-fast.  Order: ifmap, filter, ofmap reads, ofmap writes.
FAST_IS_ROW = {"ws": (True, False, True, True),
               "is": (True, False, False, False),
               "os": (True, False, False, False)}

ENERGY_GROUPS = {
    "energy_mac_pj": ("mac_random", "mac_wire", "spad_read", "spad_write"),
    "energy_sram_pj": ("sram_read_random", "sram_read_repeat",
                       "sram_write_random", "sram_write_repeat",
                       "sram_idle_kib_cycles", "l2_read", "l2_write"),
    "energy_dram_pj": ("dram_bytes", "noc_byte_hops"),
    "energy_static_pj": ("mac_gated", "pe_leak"),
}
ERT_KEY = {"mac_random": "mac_random", "mac_wire": "mac_wire_per_dim32",
           "mac_gated": "mac_gated", "pe_leak": "pe_leak_per_cycle",
           "spad_read": "spad_read", "spad_write": "spad_write",
           "sram_read_random": "sram_read_random",
           "sram_read_repeat": "sram_read_repeat",
           "sram_write_random": "sram_write_random",
           "sram_write_repeat": "sram_write_repeat",
           "sram_idle_kib_cycles": "sram_idle_per_cycle",
           "l2_read": "l2_read", "l2_write": "l2_write",
           "dram_bytes": "dram_per_byte", "noc_byte_hops": "noc_per_byte_hop"}

COLUMNS = ("total_cycles", "compute_cycles", "stall_cycles", "dram_bytes",
           "energy_pj", "utilization", "edp") + tuple(ENERGY_GROUPS)


class _Num:
    """Array and scalar constructors in one number type."""

    def __init__(self, num):
        self.num = num
        self.dtype = np.float64 if num is float else np.dtype(num)

    def a(self, x) -> np.ndarray:
        return np.asarray(x, dtype=np.float64).astype(self.dtype)

    def s(self, x):
        return self.num(x)


def _mapping(df: str, M, N, K):
    """(Sr, Sc, T): the array's row dim, column dim and streamed dim."""
    return {"ws": (K, M, N), "is": (K, N, M), "os": (M, N, K)}[df]


def _op_model(df, M, N, K, R, C, mem, f: _Num):
    """Per-op compute cycles, SRAM accesses and DRAM traffic (elements)
    of the analytic model, vectorized over ops."""
    one = f.a(1.0)
    Sr, Sc, T = _mapping(df, M, N, K)
    fr = f.a(np.ceil(Sr / R))
    fc = f.a(np.ceil(Sc / C))
    comp = f.a((2 * R + C + T - 2) * fr * fc)
    WK, XK, O = f.a(M * K), f.a(K * N), f.a(M * N)
    if df == "ws":
        sram = dict(ifmap_reads=f.a(fc * XK), filter_reads=WK,
                    ofmap_writes=f.a(fr * O), ofmap_reads=f.a((fr - one) * O))
    elif df == "is":
        sram = dict(ifmap_reads=XK, filter_reads=f.a(fc * WK),
                    ofmap_writes=f.a(fr * O), ofmap_reads=f.a((fr - one) * O))
    else:
        sram = dict(ifmap_reads=f.a(fr * XK), filter_reads=f.a(fc * WK),
                    ofmap_writes=O, ofmap_reads=f.a(0 * O))
    wb = f.a(mem["word_bytes"])
    cap_if = np.maximum(one, f.a(mem["ifmap_sram_bytes"] / wb))
    cap_f = np.maximum(one, f.a(mem["filter_sram_bytes"] / wb))
    cap_o = np.maximum(one, f.a(mem["ofmap_sram_bytes"] / wb))
    # two loop orders over double-buffered SRAM, the cheaper one wins
    n_t = f.a(np.clip(np.floor(cap_if / np.maximum(K, one)), one, N))
    tot_a = f.a(XK + WK * f.a(np.ceil(N / n_t)))
    m_t = f.a(np.clip(np.floor(cap_f / np.maximum(K, one)), one, M))
    tot_b = f.a(WK + XK * f.a(np.ceil(M / m_t)))
    a_better = tot_a <= tot_b
    dram_x = f.a(np.where(a_better, XK, XK * f.a(np.ceil(M / m_t))))
    dram_w = f.a(np.where(a_better, WK * f.a(np.ceil(N / n_t)), WK))
    spill = f.a(np.where((df != "os") & (f.a(C * T) > cap_o),
                         (fr - one) * O, 0 * O))
    dram = dict(ifmap=dram_x, filter=dram_w, ofmap_writes=f.a(O + spill),
                ofmap_reads=spill)
    return comp, fr, fc, sram, dram


def request_stream(df, M, N, K, R, C, comp, dram, wb, spec, f: _Num):
    """One op's demand stream: (t_issue, addr, is_write, scale), sorted
    by issue time, at most `spec['cap']` model requests.

    Each model request stands for `scale` real ones; runs of SAMPLE_RUN
    granules sample the operand walk so that DRAM-row locality survives
    the compression; reads of tile t are posted at the start of tile
    t-1's window, psum writes interleave (ws/is) or drain at tile end
    (os)."""
    gran, cap = spec["gran_bytes"], spec["cap"]
    wbf = f.s(wb)
    region_bytes = f.a([dram["ifmap"] * wbf, dram["filter"] * wbf,
                        dram["ofmap_reads"] * wbf, dram["ofmap_writes"] * wbf])
    n_total = f.s(np.sum(region_bytes)) / f.s(gran)
    n_model = min(f.s(cap), max(f.s(1.0), f.s(np.ceil(n_total))))
    scale = f.s(n_total / n_model)
    r_model = f.a(region_bytes / f.s(gran) / scale)
    edges = f.a(np.cumsum(r_model))
    starts = f.a(np.concatenate([[0.0], edges[:-1]]))

    n_valid = int(n_model)
    i = f.a(np.arange(n_valid))
    region = np.clip(np.sum(i[:, None] >= edges[None, :], axis=1), 0, 3)
    j = np.maximum(f.a(0.0), f.a(i - starts[region]))

    rows_of = f.a([K, M, M, M])          # X: K x N, W: M x K, O: M x N
    cols_of = f.a([N, K, N, N])
    fast_row = np.asarray(FAST_IS_ROW[df])[region]
    one = f.a(1.0)
    fast_len = np.maximum(one, np.where(fast_row, rows_of[region],
                                        cols_of[region]))
    slow_len = np.maximum(one, np.where(fast_row, cols_of[region],
                                        rows_of[region]))
    step = f.s(scale * f.s(gran) / wbf)                # elements / request
    run = f.s(SAMPLE_RUN)
    j_b = f.a(np.floor(j / run))
    j_i = f.a(j - run * j_b)
    g_el = f.s(f.s(gran) / wbf)
    fpos = f.a(np.mod(f.a(np.mod(j_b * f.s(step * run), fast_len))
                      + j_i * g_el, fast_len))
    lines = f.a(np.mod(f.a(j_b * f.a(f.s(step * run) / fast_len)), slow_len)
                + f.a(j_i * g_el / fast_len))
    s = f.a(np.mod(np.floor(lines), slow_len))
    row = np.where(fast_row, fpos, s)
    col = np.where(fast_row, s, fpos)
    if spec["layout"] != "row":
        raise ValueError(f"no reference for DRAM layout {spec['layout']!r}")
    idx = f.a(np.mod(f.a(row * cols_of[region] + col),
                     f.s(REGION_SPAN // wb)))
    addr = (np.minimum(region, 2).astype(np.int64) * REGION_SPAN
            + np.floor(idx.astype(np.float64)).astype(np.int64) * wb)

    Sr, Sc, _ = _mapping(df, M, N, K)
    n_tiles = max(f.s(1.0), f.s(np.ceil(Sr / R) * np.ceil(Sc / C)))
    tile_cyc = max(f.s(1.0), f.s(comp / n_tiles / scale))
    q = np.maximum(f.a(r_model[region] / n_tiles), f.a(1e-9))
    pos = f.a(j / q)
    tau = f.a(np.clip(np.floor(pos), 0.0, n_tiles - one))
    frac = f.a(np.clip(pos - tau, 0.0, 1.0))
    is_write = region == 3
    t_read = f.a(np.maximum(f.a(0.0), tau - one) * tile_cyc)
    t_inter = f.a((tau + frac) * tile_cyc)
    t_write = f.a((tau + one) * tile_cyc) if df == "os" else t_inter
    t = np.where(is_write, t_write, np.where(region == 2, t_inter, t_read))
    order = np.argsort(t, kind="stable")
    return t[order], addr[order], is_write[order], scale


def replay_stall(t, addr, is_write, dram: Dict, gran: int, num) -> float:
    """Model stall cycles of one request stream: per-request bank, bus and
    in-flight queue timing, in stream order.

    Byte address -> burst b; channel b % channels; within the channel,
    bank = (r // bursts_per_row) % banks and row = r // (bursts_per_row
    * banks).  A request issues once the request Q back in its direction
    has completed; it waits for its bank, pays tCAS (open row), tRCD+tCAS
    (no open row) or tRP+tRCD+tCAS (other row open), then holds the
    channel bus for gran / bandwidth cycles.  Queue waits shift every
    later request; the stall is that shift plus the tail past the last
    issue's nominal completion."""
    chn, banks = dram["channels"], dram["banks_per_channel"]
    per_row = max(1, dram["row_bytes"] // dram["burst_bytes"])
    b = addr // dram["burst_bytes"]
    ch = (b % chn).tolist()
    r = b // chn
    fb = (np.asarray(ch) * banks + (r // per_row) % banks).tolist()
    rows = (r // (per_row * banks)).tolist()
    ts = [num(x) for x in np.asarray(t, np.float64).tolist()]
    writes = np.asarray(is_write).tolist()
    Qr, Qw = dram["read_queue"], dram["write_queue"]
    zero = num(0.0)
    busy = num(max(1.0, gran / dram["bandwidth_bytes_per_cycle"]))
    l_hit = num(dram["tCAS"])
    l_empty = num(dram["tRCD"] + dram["tCAS"])
    l_conf = num(dram["tRP"] + dram["tRCD"] + dram["tCAS"])
    bank_free = [zero] * (chn * banks)
    open_row = [-1] * (chn * banks)
    bus_free = [zero] * chn
    ring_r, ring_w = [zero] * Qr, [zero] * Qw
    ir = iw = 0
    shift = zero
    last = zero
    for ti, k, c, rw, w in zip(ts, fb, ch, rows, writes):
        t_eff = ti + shift
        head = ring_w[iw % Qw] if w else ring_r[ir % Qr]
        issue_ok = max(t_eff, head)
        ready = max(issue_ok, bank_free[k])
        o = open_row[k]
        lat = l_hit if o == rw else (l_empty if o < 0 else l_conf)
        done = max(ready + lat, bus_free[c]) + busy
        bank_free[k] = done
        bus_free[c] = done
        open_row[k] = rw
        if w:
            ring_w[iw % Qw] = done
            iw += 1
        else:
            ring_r[ir % Qr] = done
            ir += 1
        shift = shift + (issue_ok - t_eff)
        last = max(last, done)
    nominal = l_empty + busy
    tail = max(zero, last - (max(ts) + shift + nominal))
    return shift + tail


# cycles over which the layout stage averages a streaming slowdown
LAYOUT_WINDOW = 512


def layout_extra(R: int, comp, N: int, layout: Dict, wb: int,
                 f: _Num):
    """Extra cycles one op loses to SRAM bank conflicts.

    Cycle t of the stream reads the R elements t + r * N (r < R) of a
    row-major operand laid out flat: consecutive elements fill a line of
    `line_bytes / wb` elements in each of the banks, then the next line.
    A cycle takes as long as its bank with the most distinct lines needs
    (ceil(lines / ports), at least 1); the mean over the first
    clip(floor(comp), 8, 512) cycles, less one, times comp is the extra."""
    per_bank = max(1, layout["line_bytes"] // wb)
    per_line = per_bank * layout["num_banks"]
    ports = layout["ports_per_bank"]
    slow = []
    for t in range(LAYOUT_WINDOW):
        lines: Dict[int, set] = {}
        for r in range(int(R)):
            i = t + r * max(1, int(N))
            lines.setdefault((i % per_line) // per_bank, set()).add(
                i // per_line)
        slow.append(max(1, max(-(-len(v) // ports)
                               for v in lines.values())))
    n = int(min(max(np.floor(min(float(comp), LAYOUT_WINDOW)), 8),
                LAYOUT_WINDOW))
    mean = f.s(sum(slow[:n])) / f.s(n)
    return f.s((mean - f.s(1.0)) * f.s(comp))


def cell_metrics(design: Dict, gemms: Sequence[Sequence], fidelity: str,
                 cfg: Dict, num=float) -> Dict[str, float]:
    """The frame columns of one cell.  `design` holds rows, cols,
    dataflow and the memory, DRAM and layout sections; `cfg` is the
    configuration file (ERT, trace spec)."""
    f = _Num(num)
    df = design["dataflow"]
    mem = design["memory"]
    dram = design["dram"]
    layout = design["layout"]
    ert = cfg["ert"]
    spec = cfg["trace_spec"]
    wb = mem["word_bytes"]
    R, C = f.s(design["rows"]), f.s(design["cols"])
    M = f.a([g[1] for g in gemms])
    N = f.a([g[2] for g in gemms])
    K = f.a([g[3] for g in gemms])
    cnt = f.a([g[4] for g in gemms])
    comp, fr, fc, sram, traffic = _op_model(df, M, N, K, R, C, mem, f)
    dram_elems = f.a(traffic["ifmap"] + traffic["filter"]
                     + traffic["ofmap_writes"] + traffic["ofmap_reads"])
    dram_bytes = f.a(dram_elems * f.s(wb))
    if fidelity == "fast":
        bw = f.s(dram["bandwidth_bytes_per_cycle"] * dram["channels"])
        stall = np.maximum(f.a(0.0), f.a(dram_bytes / bw - comp))
    elif fidelity == "trace":
        stall = f.a(np.zeros(len(gemms)))
        memo: Dict[tuple, object] = {}
        for o in range(len(gemms)):
            key = (float(M[o]), float(N[o]), float(K[o]))
            if key not in memo:    # identical shapes give identical streams
                op_traffic = {k: v[o] for k, v in traffic.items()}
                t, addr, w, scale = request_stream(
                    df, M[o], N[o], K[o], R, C, comp[o], op_traffic, wb,
                    spec, f)
                memo[key] = replay_stall(t, addr, w, dram,
                                         spec["gran_bytes"], num) * scale
            stall[o] = memo[key]
    else:
        raise ValueError(f"no reference for fidelity {fidelity!r}")

    extra = f.a(np.zeros(len(gemms)))
    if layout["enabled"]:
        memo_l: Dict[tuple, object] = {}
        for o in range(len(gemms)):
            key = (float(comp[o]), float(N[o]))
            if key not in memo_l:
                memo_l[key] = layout_extra(R, comp[o], N[o], layout, wb, f)
            extra[o] = memo_l[key]

    comp_t = f.a(comp * cnt)
    stall_t = f.a(stall * cnt)
    extra_t = f.a(extra * cnt)
    dram_t = f.a(dram_bytes * cnt)
    macs = f.a(f.a(M * N) * K * cnt)
    pes = f.s(R * C)
    dim32 = f.s(max(R, C) / f.s(32.0))
    sram_kib = f.s((mem["ifmap_sram_bytes"] + mem["filter_sram_bytes"]
                    + mem["ofmap_sram_bytes"]) / 1024.0)
    util_e = f.a(np.clip(macs / np.maximum(f.a(1.0), f.a(pes * comp_t)),
                         0.0, 1.0))
    rf = f.s(1.0 - 1.0 / max(1, 64 // wb))
    reads = f.a((sram["ifmap_reads"] + sram["filter_reads"]
                 + sram["ofmap_reads"]) * cnt)
    writes = f.a(sram["ofmap_writes"] * cnt)
    pe_cyc = f.a(pes * comp_t)
    l2 = f.a(dram_elems * cnt) if mem["l2_sram_bytes"] > 0 else f.a(0 * cnt)
    counts = dict(
        mac_random=f.a(pe_cyc * util_e),
        mac_wire=f.a(f.a(pe_cyc * util_e) * dim32),
        mac_gated=f.a(pe_cyc * f.a(1.0 - util_e)),
        pe_leak=pe_cyc,
        spad_read=f.a(f.s(3.0) * macs),
        spad_write=f.a(f.a((sram["ifmap_reads"] + sram["filter_reads"])
                           * cnt) + macs),
        sram_read_random=f.a(reads * f.s(1.0 - rf)),
        sram_read_repeat=f.a(reads * rf),
        sram_write_random=f.a(writes * f.s(1.0 - rf)),
        sram_write_repeat=f.a(writes * rf),
        sram_idle_kib_cycles=f.a(comp_t * sram_kib),
        l2_read=l2, l2_write=f.a(0 * cnt),
        dram_bytes=dram_t, noc_byte_hops=f.a(0 * cnt))
    energy = {k: f.s(np.sum(f.a(v * f.s(ert[ERT_KEY[k]]))))
              for k, v in counts.items()}
    total_c = f.s(np.sum(comp_t))
    total_s = f.s(np.sum(stall_t))
    total = total_c + total_s + f.s(np.sum(extra_t))
    e_pj = f.s(sum(energy.values(), f.s(0.0)))
    out = dict(total_cycles=total, compute_cycles=total_c,
               stall_cycles=total_s, dram_bytes=f.s(np.sum(dram_t)),
               energy_pj=e_pj,
               utilization=min(f.s(1.0), f.s(np.sum(macs))
                               / max(f.s(1.0), f.s(pes * total))),
               edp=f.s(e_pj * f.s(1e-9)) * total)
    for g, acts in ENERGY_GROUPS.items():
        out[g] = f.s(sum((energy[a] for a in acts), f.s(0.0)))
    return {k: float(v) for k, v in out.items()}

