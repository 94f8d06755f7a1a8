"""Profiler capture of the window and its reduction to busy, idle and
host-gap numbers.

The window runs under `jax.profiler` with the Python tracer off; the
benchmark marks it with a `window` span and each Study with a `study`
span (`jax.profiler.TraceAnnotation`), so host spans and device
operations share the profiler's clock.  `reduce` reads:

  busy_s           per device used, the union of its operations'
                   intervals inside the window; a device that ran no
                   operation (a cell that holds a host of chips for
                   steadiness and runs on one) is not among them
  idle_in_studies  per device, the part of the `study` spans in which no
                   operation ran on it
  op_s             time per operation name inside the window, every
                   name (`ranked` gives the breakdown's top ten)
  gap_s            idle time per host event: the longest idle
                   stretches of a device inside the window, each named
                   by the innermost host event that spans its middle;
                   the rest summed as one entry
  host_s           time per host event name inside the window
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

Interval = Tuple[float, float]

WINDOW, STUDY = "window", "study"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
NAMED_GAPS = 1000                # idle gaps per device named by host event
SHORT_GAPS = "shorter idle gaps"


@contextlib.contextmanager
def capture(log_dir: str) -> Iterator[None]:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield


def load(log_dir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(paths[-1])


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(merged: List[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] that the merged intervals cover."""
    tot = 0.0
    for s, e in merged[max(0, bisect.bisect_right(merged, (lo,)) - 1):]:
        if s >= hi:
            break
        tot += max(0.0, min(e, hi) - max(s, lo))
    return tot


def gaps(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that the merged intervals leave open."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _device_planes(pd) -> List:
    # the chips only: a TPU trace also holds e.g. "/device:CUSTOM:Megascale
    # Trace", which runs no operation
    return [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]


def _op_name(text: str) -> str:
    """`%while.400 = (s32[], ...) while(...)` -> `%while.400`."""
    return text.split(" = ", 1)[0]


def _op_events(plane) -> List[Tuple[str, float, float]]:
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
    return [(_op_name(ev.name), ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9)
            for ln in ops for ev in ln.events if ev.duration_ns > 0]


def _host_events(pd) -> List[Tuple[str, float, float]]:
    out = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                out += [(ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in ln.events if ev.duration_ns > 0]
    return out


def _name_points(host: List[Tuple[str, float, float]],
                 points: List[float]) -> List[str]:
    """For each time point, the name of the shortest host event that
    spans it (the `window` span aside)."""
    order = sorted(range(len(points)), key=points.__getitem__)
    ts = [points[i] for i in order]
    best = [("untraced host time", float("inf"))] * len(points)
    for name, s, e in host:
        if name == WINDOW:
            continue
        for k in range(bisect.bisect_left(ts, s), bisect.bisect_right(ts, e)):
            if e - s < best[order[k]][1]:
                best[order[k]] = (name, e - s)
    return [n for n, _ in best]


def ranked(d: Dict[str, float], top: int = 10) -> List[List]:
    """The `top` largest entries as [name, seconds], largest first."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
            [:top]]


def reduce(pd) -> Optional[Dict]:
    """The numbers above, or None when the trace holds no device plane or
    no `window` span."""
    host = _host_events(pd)
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    planes = _device_planes(pd)
    devices = [p for p in planes if _op_events(p)] or planes
    if not windows or not devices:
        return None
    lo, hi = windows[-1]
    studies = sorted((s, e) for n, s, e in host
                     if n == STUDY and s >= lo and e <= hi)
    busy, idle_st = [], []
    op_time: Dict[str, float] = {}
    gap_time: Dict[str, float] = {}
    host_time: Dict[str, float] = {}
    for n, s, e in host:
        d = max(0.0, min(e, hi) - max(s, lo))
        if d > 0:
            host_time[n] = host_time.get(n, 0.0) + d
    for plane in devices:
        evs = _op_events(plane)
        merged = union([(s, e) for _, s, e in evs])
        busy.append(covered(merged, lo, hi))
        idle_st.append(sum((e - s) - covered(merged, s, e)
                           for s, e in studies))
        for n, s, e in evs:
            d = max(0.0, min(e, hi) - max(s, lo))
            if d > 0:
                op_time[n] = op_time.get(n, 0.0) + d / len(devices)
        # name the longest gaps; the many short ones go in one entry
        open_ = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])
        names = _name_points(host, [0.5 * (s + e)
                                    for s, e in open_[:NAMED_GAPS]])
        names += [SHORT_GAPS] * (len(open_) - len(names))
        for n, (s, e) in zip(names, open_):
            gap_time[n] = gap_time.get(n, 0.0) + (e - s) / len(devices)

    return {"window_s": hi - lo, "devices": len(devices),
            "busy_s": busy, "idle_in_studies_s": idle_st,
            "studies": len(studies), "op_s": op_time, "gap_s": gap_time,
            "host_s": host_time}
