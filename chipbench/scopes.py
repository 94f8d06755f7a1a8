"""The device-scope, program and host-span split of a traced window.

    python3 -m chipbench.scopes --workload vitb-edp.search-rung --seed 7 \
        --seconds 10 --out scopes_out
    python3 -m chipbench.scopes --trace <file.xplane.pb[.gz]> --cells 16

The first runs the cell's set-up and one traced window as `chipbench.run
--trace 1` does (`--seconds 0`: one Study), keeps the trace (`slim`,
gzipped) under `--out`, and prints one JSON line: the three trace
metrics `run` reads, the nine of `per_layer`, and the breakdown with
`device_scopes`, `device_programs` and `idle_spans` beside `device_ops`
and `idle_gaps`.  The second reduces a trace already recorded.  Neither
is part of `chipbench.run`'s result line.

`reduce` reads what `jax.profiler.ProfileData` cannot: the `tf_op` stat of
each device operation's event metadata (the operation's `op_name`, which
carries the program's `jax.named_scope`s), through a small reader of the
XSpace protobuf's wire format.  It gives:

  scope_s      per scope, device self time (an operation's interval less
               what operations nested in it on its line cover), given to
               the innermost of SCOPES in its `op_name`; none: `unscoped`
  program_s    device time per `XLA Modules` name (the jitted program)
  span_idle_s  device-idle time inside the `study` spans, given to the
               innermost program span (SPANS) over it; none: `unspanned`
  span_args    the `sweep` spans' arguments, summed, and their count
Times are averaged over the devices, inside the last `window` span.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from . import tracing

# the program's names (src/repro/spans.py), repeated here: the benchmark
# runs against programs that have none of them
SCOPES = ("generate", "decode", "replay", "precompute", "chunk_scan",
          "escape", "stages")
SPANS = ("study.run", "study.plan", "study.cache", "study.fallback",
         "study.frame", "sweep", "sweep.columns", "sweep.dispatch",
         "sweep.fetch")
SWEEP_ARGS = ("designs", "streams", "blocks", "block")
UNSCOPED, UNSPANNED = "unscoped", "unspanned"
MODULES_LINE = "XLA Modules"
REPLAY = ("replay", "precompute", "chunk_scan", "escape")

# --- the XSpace wire format (tsl/profiler/protobuf/xplane.proto) ----------


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b: bytes, lo: int = 0, hi: Optional[int] = None
            ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    (lo, hi) span of `b` for a length-delimited field."""
    i, hi = lo, len(b) if hi is None else hi
    while i < hi:
        key, i = _varint(b, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wt in (1, 5):
            n = 8 if wt == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield key >> 3, v


def _str(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _tf_op(b: bytes, span, stat_names: Dict[int, str]) -> str:
    """The `tf_op` stat of one XEventMetadata, `op_name:op_type` trimmed
    to the op_name; '' when it has none."""
    for f, st in _fields(b, *span):
        if f != 5:
            continue
        mid, val = None, ""
        for g, v in _fields(b, *st):
            if g == 1:
                mid = v
            elif g == 5:
                val = _str(b, v)
            elif g == 7:
                val = stat_names.get(v, "")
        if stat_names.get(mid) == "tf_op":
            return val.rpartition(":")[0] if ":" in val else val
    return ""


def _id_name(b: bytes, span) -> Tuple[int, str]:
    """(id, name) of an XEventMetadata or XStatMetadata."""
    mid, name = 0, ""
    for f, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 2:
            name = _str(b, v)
    return mid, name


def device_lines(data: bytes, lines=(tracing.OPS_LINE, MODULES_LINE)
                 ) -> Dict[str, Dict[str, List[Tuple]]]:
    """Per `/device:TPU:*` plane, per line in `lines`, its events as
    (name, start_ns, end_ns, tf_op), with times as ProfileData gives
    them (line timestamp plus whole nanoseconds of offset)."""
    out: Dict[str, Dict[str, List[Tuple]]] = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, raw_lines, maps = "", [], []
        for g, v in _fields(data, *plane):
            if g == 2:
                name = _str(data, v)
            elif g == 3:
                raw_lines.append(v)
            elif g in (4, 5):                 # map<int64, metadata>
                maps.append((g, v))
        if not name.startswith(tracing.DEVICE_PREFIX):
            continue
        meta, stat_names = {}, {}
        for g, v in maps:
            for h, w in _fields(data, *v):
                if h == 2:
                    mid, mname = _id_name(data, w)
                    if g == 4:
                        meta[mid] = (mname, w)
                    else:
                        stat_names[mid] = mname
        out[name] = {}
        tf_ops: Dict[int, str] = {}
        for ln in raw_lines:
            lname, ts, evs = "", 0, []
            for g, v in _fields(data, *ln):
                if g == 2:
                    lname = _str(data, v)
                elif g == 3:
                    ts = _signed(v)
                elif g == 4:
                    evs.append(v)
            if lname not in lines:
                continue
            rows = []
            for ev in evs:
                mid = off = dur = 0
                for g, v in _fields(data, *ev):
                    if g == 1:
                        mid = v
                    elif g == 2:
                        off = v
                    elif g == 3:
                        dur = v
                if mid not in tf_ops:
                    tf_ops[mid] = (_tf_op(data, meta[mid][1], stat_names)
                                   if mid in meta else "")
                s = ts + off // 1000
                rows.append((meta[mid][0] if mid in meta else "", s,
                             s + dur // 1000, tf_ops[mid]))
            out[name][lname] = rows
    return out


# --- slimming a trace to what the reductions read -----------------------


def _varint_bytes(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    return bytes(out) + bytes([n])


def _ld(f: int, payload: bytes) -> bytes:
    return _varint_bytes(f << 3 | 2) + _varint_bytes(len(payload)) + payload


def _emit(b: bytes, f: int, v) -> bytes:
    """One field as `_fields` read it, encoded again."""
    if isinstance(v, int):
        return _varint_bytes(f << 3) + _varint_bytes(v)
    if isinstance(v, tuple):
        return _ld(f, b[v[0]:v[1]])
    return _varint_bytes(f << 3 | (1 if len(v) == 8 else 5)) + v


def slim(data: bytes) -> bytes:
    """The trace less what neither reduction reads: the `/host:metadata`
    plane (the programs' HLO), device lines but `XLA Ops` and `XLA
    Modules`, device events' stats, and of each device event's metadata
    all but its id, its name up to ` = ` and its `tf_op`."""
    out = []
    for f, plane in _fields(data):
        fields = list(_fields(data, *plane))
        name = next((_str(data, v) for g, v in fields if g == 2), "")
        if name == "/host:metadata":
            continue
        if f != 1 or not name.startswith(tracing.DEVICE_PREFIX):
            out.append(_emit(data, f, plane))
            continue
        tf_op = {mid for g, v in fields if g == 5
                 for h, w in _fields(data, *v) if h == 2
                 for mid, n in [_id_name(data, w)] if n == "tf_op"}
        body = []
        for g, v in fields:
            if g == 3:              # a line: kept if read, events bare
                line = list(_fields(data, *v))
                if next(_str(data, w) for h, w in line if h == 2) not in (
                        tracing.OPS_LINE, MODULES_LINE):
                    continue
                body.append(_ld(3, b"".join(
                    _ld(4, b"".join(_emit(data, q, y) for q, y in
                                    _fields(data, *w) if q != 4))
                    if h == 4 else _emit(data, h, w) for h, w in line)))
            elif g == 4:            # event metadata: id, name, tf_op
                (w,) = [w for h, w in _fields(data, *v) if h == 2]
                mid, n = _id_name(data, w)
                md = _emit(data, 1, mid) + _ld(2, n.split(" = ")[0].encode())
                md += b"".join(_emit(data, 5, st)
                               for q, st in _fields(data, *w) if q == 5
                               and dict(_fields(data, *st)).get(1) in tf_op)
                body.append(_ld(4, _emit(data, 1, mid) + _ld(2, md)))
            else:
                body.append(_emit(data, g, v))
        out.append(_ld(1, b"".join(body)))
    return b"".join(out)


# --- the reduction ---------------------------------------------------------


def innermost(path: str, names) -> Optional[str]:
    """The last of `names` among the components of an `op_name` path; a
    scope entered under a transform reads e.g. `vmap(generate)`."""
    for comp in reversed(path.split("/")):
        m = re.fullmatch(r"(?:[\w.-]*\()*([^()]*)\)*", comp)
        if m and m.group(1) in names:
            return m.group(1)
    return None


def owned(intervals: List[Tuple], lo: float, hi: float) -> List[Tuple]:
    """Segments (start, end, key) of [lo, hi] in which the innermost of
    nested (start, end, key) intervals is `key`: the self time of each
    interval, clipped to [lo, hi]."""
    out: List[Tuple] = []
    stack: List[Tuple] = []
    t = lo

    def advance(upto):          # the top of the stack owns [t, upto]
        nonlocal t
        a, b = max(t, lo), min(upto, hi)
        if stack and b > a:
            out.append((a, b, stack[-1][2]))
        t = max(t, upto)

    for iv in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= iv[0]:
            advance(stack[-1][1])
            stack.pop()
        advance(iv[0])
        stack.append(iv)
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return out


def _host_spans(pd) -> List[Tuple[str, float, float, Dict]]:
    out = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                out += [(ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         dict(ev.stats) if ev.name == "sweep" else {})
                        for ev in ln.events
                        if ev.name in SPANS + (tracing.WINDOW, tracing.STUDY)]
    return out


def reduce(data: bytes, pd=None) -> Optional[Dict]:
    """The numbers above from a serialized XSpace (`pd`, its ProfileData,
    when already loaded), or None when it holds no device plane or no
    `window` span."""
    from jax.profiler import ProfileData
    pd = pd if pd is not None else ProfileData.from_serialized_xspace(data)
    host = _host_spans(pd)
    windows = [(s, e) for n, s, e, _ in host if n == tracing.WINDOW]
    devices = device_lines(data)
    if not windows or not devices:
        return None
    lo, hi = windows[-1]
    studies = sorted((s, e) for n, s, e, _ in host
                     if n == tracing.STUDY and s >= lo and e <= hi)
    spans = [(s, e, n) for n, s, e, _ in host if n in SPANS]
    nd = len(devices)
    scope_s: Dict[str, float] = {}
    program_s: Dict[str, float] = {}
    span_idle: Dict[str, float] = {}
    for lines in devices.values():
        ops = [(s * 1e-9, e * 1e-9, innermost(op, SCOPES) or UNSCOPED)
               for _, s, e, op in lines.get(tracing.OPS_LINE, []) if e > s]
        for a, b, k in owned(ops, lo, hi):
            scope_s[k] = scope_s.get(k, 0.0) + (b - a) / nd
        for n, s, e, _ in lines.get(MODULES_LINE, []):
            d = max(0.0, min(e * 1e-9, hi) - max(s * 1e-9, lo))
            if d > 0:
                k = re.sub(r"\(\d+\)$", "", n)
                program_s[k] = program_s.get(k, 0.0) + d / nd
        merged = tracing.union([(s, e) for s, e, _ in ops])
        for s0, e0 in studies:
            idle = (e0 - s0) - tracing.covered(merged, s0, e0)
            for a, b, k in owned(spans, s0, e0):
                d = (b - a) - tracing.covered(merged, a, b)
                span_idle[k] = span_idle.get(k, 0.0) + d / nd
                idle -= d
            span_idle[UNSPANNED] = span_idle.get(UNSPANNED, 0.0) + idle / nd
    sweeps = [a for n, s, e, a in host if n == "sweep" and lo <= s <= hi]
    args = {k: sum(a.get(k, 0) for a in sweeps) for k in SWEEP_ARGS}
    return {"scope_s": scope_s, "program_s": program_s,
            "span_idle_s": span_idle,
            "span_args": dict(args, sweeps=len(sweeps))}


def per_layer(red: Dict, cells: int, studies: int) -> Dict[str, float]:
    """The nine per-layer metrics of a reduction, and what the split
    leaves over (`unscoped_ms_per_cell`, `unspanned_ms_per_study`): device
    milliseconds per completed cell by scope, device-idle milliseconds
    per Study by span.  Each is left out where the trace holds nothing
    for it to read (no scoped operation, no program span)."""
    sc, idle = red["scope_s"], red["span_idle_s"]
    out: Dict[str, float] = {}
    if cells and set(sc) & set(SCOPES):
        def dev(*ks):
            return sum(sc.get(k, 0.0) for k in ks) / cells * 1e3
        out.update(generate_ms_per_cell=dev("generate"),
                   decode_ms_per_cell=dev("decode"),
                   replay_ms_per_cell=dev(*REPLAY),
                   stages_ms_per_cell=dev("stages"),
                   unscoped_ms_per_cell=dev(UNSCOPED))
        if out["replay_ms_per_cell"] > 0:
            out["replay_escape_share"] = (dev("escape")
                                          / out["replay_ms_per_cell"])
    if studies and set(idle) & set(SPANS):
        def gap(*ks):
            return sum(idle.get(k, 0.0) for k in ks) / studies * 1e3
        out.update(columns_idle_ms_per_study=gap("sweep.columns"),
                   dispatch_idle_ms_per_study=gap("sweep.dispatch"),
                   fetch_idle_ms_per_study=gap("sweep.fetch"),
                   plan_frame_idle_ms_per_study=gap(
                       "study.run", "study.plan", "study.frame",
                       "study.cache", "study.fallback"),
                   unspanned_ms_per_study=gap(UNSPANNED))
    return out


# --- the command -----------------------------------------------------------


def result(data: bytes, cells: int, studies: int) -> Dict:
    """What the command prints for a serialized trace of a window that
    completed `cells` cells in `studies` Studies."""
    from jax.profiler import ProfileData
    from .spec import reader
    pd = ProfileData.from_serialized_xspace(data)
    red, sc = tracing.reduce(pd), reduce(data, pd)
    if red is None or sc is None:
        raise ValueError("the trace holds no device plane or no window")
    rec = {"cells": cells, "trace": dict(red, studies=studies)}
    metrics = {m: reader(m)(rec) for m in (
        "device_busy_ms_per_cell", "host_gap_ms_per_study",
        "device_idle_share")}
    metrics.update(per_layer(sc, cells, studies))
    return {"cells": cells, "studies": studies, "metrics": metrics,
            "span_args": sc["span_args"],
            "breakdown": {"device_ops": tracing.ranked(red["op_s"]),
                          "idle_gaps": tracing.ranked(red["gap_s"]),
                          "device_scopes": tracing.ranked(sc["scope_s"]),
                          "device_programs": tracing.ranked(sc["program_s"]),
                          "idle_spans": tracing.ranked(sc["span_idle_s"])}}


def capture(cell, seed: int, seconds: float, out_dir: str) -> Dict:
    """Set-up and one traced window of a cell, as `chipbench.run` makes
    them; the trace is kept, slimmed and gzipped, as
    `<out_dir>/<cell>.xplane.pb.gz`.
    (serialized trace, completed cells, Studies, seconds of each)."""
    import glob
    import tempfile
    import jax
    from repro.compile_cache import enable_compile_cache
    from . import designs as dz
    from . import harness
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    wl = harness.Workload(cell, seed, jax.devices()[:cell.chips])
    wl.run(dz.WARMUP)
    with tempfile.TemporaryDirectory(prefix="chipbench-scopes-") as d:
        with tracing.capture(d):
            frames, _, _, study_s = harness._window(wl, seconds)
        path = sorted(glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        with open(path, "rb") as f:
            data = f.read()
    _, _, cells = harness.tally([(res, wl.picks(k)) for k, res in frames],
                                wl.n_cells, wl.engine)
    os.makedirs(out_dir, exist_ok=True)
    with gzip.open(os.path.join(out_dir, f"{cell.name}.xplane.pb.gz"),
                   "wb") as f:
        f.write(slim(data))
    return {"data": data, "cells": len(cells), "studies": len(frames),
            "study_s": study_s}


def _capture_on_chip(args) -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    from .spec import find_cell, load_benchmark
    cell = find_cell(load_benchmark(root), args.workload)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chipbench.scopes: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    got = capture(cell, args.seed, args.seconds, args.out)
    out = result(got["data"], got["cells"], got["studies"])
    out["study_s"] = got["study_s"]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": cell.chips}
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", help="capture a window of this cell")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window length; 0 traces one Study")
    ap.add_argument("--out", default="scopes_out")
    ap.add_argument("--trace", help="reduce this recorded trace instead")
    ap.add_argument("--cells", type=int, default=0,
                    help="cells the recorded window completed")
    args = ap.parse_args(argv)
    if args.trace:
        opener = gzip.open if args.trace.endswith(".gz") else open
        with opener(args.trace, "rb") as f:
            data = f.read()
        from jax.profiler import ProfileData
        studies = tracing.reduce(
            ProfileData.from_serialized_xspace(data))["studies"]
        print(json.dumps(result(data, args.cells, studies)))
        return 0
    if not args.workload:
        ap.error("give --workload or --trace")
    return _capture_on_chip(args)


if __name__ == "__main__":
    sys.exit(main())
