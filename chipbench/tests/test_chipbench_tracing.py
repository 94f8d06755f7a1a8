"""The reduction from a profiler trace to busy, idle and host-gap
numbers, and the per-layer readers on top of it."""
import glob
import gzip
import os

import pytest
from jax.profiler import ProfileData

from chipbench import spec, tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1_000_000                       # picoseconds per microsecond


def _events(meta, evs):
    md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                 f'name: "{n}" }} }}\n' for i, n in enumerate(meta, 1))
    ev = "".join(f"events {{ metadata_id: {meta.index(n) + 1} "
                 f"offset_ps: {s * US} duration_ps: {d * US} }}\n"
                 for n, s, d in evs)
    return md, ev


def _trace(host, device, idle_devices=0):
    hm, he = _events(sorted({n for n, _, _ in host}), host)
    dm, de = _events(sorted({n for n, _, _ in device}), device)
    idle = "".join(f'planes {{ id: {3 + i} name: "/device:TPU:{1 + i}" '
                   f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 }} }}\n'
                   for i in range(idle_devices))
    return ProfileData.from_text_proto(
        f'planes {{ id: 1 name: "/host:CPU" lines {{ id: 1 name: "python" '
        f'timestamp_ns: 0 {he} }} {hm} }}\n'
        f'planes {{ id: 2 name: "/device:TPU:0" lines {{ id: 1 '
        f'name: "XLA Ops" timestamp_ns: 0 {de} }} {dm} }}\n' + idle)


def test_union_covered_and_gaps():
    merged = tracing.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert merged == [(0, 3), (5, 9), (12, 13)]
    assert tracing.covered(merged, 2, 12.5) == pytest.approx(1 + 4 + 0.5)
    assert tracing.gaps(merged, 1, 14) == [(3, 5), (9, 12), (13, 14)]


def test_reduce_on_a_synthetic_trace():
    host = [("window", 0, 100), ("study", 10, 40), ("study", 50, 40),
            ("fetch", 35, 15)]
    device = [("fusion.1", 10, 15), ("fusion.2", 20, 5), ("while.3", 60, 20),
              ("copy.4", 95, 20)]
    red = tracing.reduce(_trace(host, device))
    us = 1e-6
    assert red["window_s"] == pytest.approx(100 * us)
    assert red["busy_s"] == [pytest.approx((15 + 20 + 5) * us)]
    # study 10..50 is busy 10..25; study 50..90 is busy 60..80
    assert red["idle_in_studies_s"] == [pytest.approx((25 + 20) * us)]
    assert red["studies"] == 2
    assert tracing.ranked(red["op_s"])[0] == ["while.3",
                                             pytest.approx(20 * us)]
    assert len(red["op_s"]) == 4 and len(tracing.ranked(red["op_s"], 2)) == 2
    assert red["host_s"]["fetch"] == pytest.approx(15 * us)
    gaps = red["gap_s"]
    # 25..60 is named by the innermost host event over its middle
    assert gaps == {"untraced host time": pytest.approx(10 * us),
                    "fetch": pytest.approx(35 * us),
                    "study": pytest.approx(15 * us)}
    rec = {"cells": 4, "trace": red}
    assert spec.reader("host_gap_ms_per_study")(rec) == pytest.approx(
        45 * us / 2 * 1e3)
    assert spec.reader("device_busy_ms_per_cell")(rec) == pytest.approx(
        40 * us / 4 * 1e3)
    assert spec.reader("device_idle_share")(rec) == pytest.approx(0.6)


def test_devices_that_ran_nothing_are_not_averaged_in():
    host = [("window", 0, 100), ("study", 10, 80)]
    device = [("fusion.1", 10, 30), ("while.2", 50, 20)]
    one = tracing.reduce(_trace(host, device))
    held = tracing.reduce(_trace(host, device, idle_devices=3))
    assert held["devices"] == one["devices"] == 1
    assert held["busy_s"] == one["busy_s"] == [pytest.approx(50e-6)]
    assert held["gap_s"] == one["gap_s"] and held["op_s"] == one["op_s"]
    rec = {"cells": 4, "trace": held}
    assert spec.reader("device_idle_share")(rec) == pytest.approx(0.5)
    # where no device ran anything, every device reads idle
    none = tracing.reduce(_trace(host, [], idle_devices=3))
    assert none["busy_s"] == [0.0] * 4


def test_readers_find_nothing_without_a_trace():
    rec = {"cells": 0, "trace": None, "window_compiles": 0,
           "setup_compile_s": 1.5}
    for name in ("host_gap_ms_per_study", "device_busy_ms_per_cell",
                 "device_idle_share"):
        assert spec.reader(name)(rec) is None
    assert spec.reader("window_compiles")(rec) == 0
    assert spec.reader("setup_compile_s")(rec) == 1.5


def _sweep_busy(intervals, lo, hi):
    """Busy time by a sweep line over interval edges (a second way of
    computing what `union` + `covered` compute)."""
    edges = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e in intervals
                      if e > lo and s < hi])
    busy, active, last = 0.0, 0, lo
    for t, d in edges:
        if active > 0:
            busy += t - last
        active += d
        last = t
    return busy


@pytest.fixture(scope="module")
def chip_trace():
    paths = glob.glob(os.path.join(DATA, "*.xplane.pb.gz"))
    if not paths:
        pytest.fail("no recorded chip trace under chipbench/tests/data")
    with gzip.open(paths[0]) as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_reduce_on_a_recorded_chip_trace(chip_trace):
    red = tracing.reduce(chip_trace)
    assert red is not None and red["devices"] >= 1
    host = tracing._host_events(chip_trace)
    lo, hi = [(s, e) for n, s, e in host if n == tracing.WINDOW][-1]
    studies = [(s, e) for n, s, e in host if n == tracing.STUDY]
    assert red["studies"] == len(studies) > 0
    assert red["window_s"] == pytest.approx(hi - lo)
    for plane, busy, idle in zip(tracing._device_planes(chip_trace),
                                 red["busy_s"], red["idle_in_studies_s"]):
        ivs = [(s, e) for _, s, e in tracing._op_events(plane)]
        assert busy == pytest.approx(_sweep_busy(ivs, lo, hi), rel=1e-9)
        assert 0 < busy < hi - lo
        assert idle == pytest.approx(sum(
            (e - s) - _sweep_busy(ivs, s, e) for s, e in studies), rel=1e-9)
    share = spec.reader("device_idle_share")({"trace": red, "cells": 1})
    assert 0 < share < 1
    assert sum(red["gap_s"].values()) <= (hi - lo) * (1 + 1e-9)


def test_only_tpu_planes_count_as_devices(chip_trace):
    names = [p.name for p in chip_trace.planes]
    assert any(n.startswith("/device:CUSTOM") for n in names)
    assert tracing.reduce(chip_trace)["devices"] == sum(
        n.startswith("/device:TPU:") for n in names)
