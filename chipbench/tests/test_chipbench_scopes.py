"""The raw XSpace reader and the scope, program and span split of
`chipbench.scopes`, on synthetic and recorded traces; and the existing
reduction of `chipbench.tracing`, pinned on the recorded trace."""
import gzip
import json
import os

import pytest
from jax.profiler import ProfileData

from chipbench import scopes, tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "fast_small.xplane.pb.gz")
US = 1_000_000                       # picoseconds per microsecond


# --- a small XSpace writer (the wire format the reader reads) -------------

def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _int(field, v):
    return _varint(field << 3) + _varint(v)


def _msg(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, lines, stats_of=lambda n: {}):
    """One XPlane: `lines` maps a line name to (event name, start us,
    duration us) events; `stats_of(event name)` gives the event's stats
    (strings and ints) and, for device planes, its metadata's `tf_op`."""
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    stat_ids = {}

    def stat(k, v):
        sid = stat_ids.setdefault(k, len(stat_ids) + 1)
        return _int(1, sid) + (_msg(5, v) if isinstance(v, str)
                               else _int(4, v))

    body = _int(1, 1) + _msg(2, name)
    for i, (lname, evs) in enumerate(lines.items()):
        line = _int(1, i + 1) + _msg(2, lname) + _int(3, 0)
        for n, s, d in evs:
            ev = (_int(1, names.index(n) + 1) + _int(2, s * US)
                  + _int(3, d * US))
            for k, v in stats_of(n).items():
                if k != "tf_op":
                    ev += _msg(4, stat(k, v))
            line += _msg(4, ev)
        body += _msg(3, line)
    for i, n in enumerate(names):
        md = _int(1, i + 1) + _msg(2, n)
        if "tf_op" in stats_of(n):
            md += _msg(5, stat("tf_op", stats_of(n)["tf_op"] + ":"))
        body += _msg(4, _int(1, i + 1) + _msg(2, md))
    for k, sid in stat_ids.items():
        body += _msg(5, _int(1, sid) + _msg(2, _int(1, sid) + _msg(2, k)))
    return _msg(1, body)


SWEEP_ARGS = {"program": "sweep_trace_os", "designs": 3, "streams": 8,
              "blocks": 1, "block": 8}
TF_OPS = {
    "%while.1": "jit(sweep_trace_os)/while",
    "%fusion.a": "jit(sweep_trace_os)/while/body/vmap(generate)/add",
    "%while.b": "jit(sweep_trace_os)/while/body/replay/chunk_scan/while",
    "%fusion.c": "jit(sweep_trace_os)/while/body/replay/chunk_scan/while/"
                 "body/cond/branch_1_fun/escape/while/body/mul",
    "%fusion.d": "jit(sweep_trace_os)/while/body/replay/chunk_scan/while/"
                 "body/mul",
    "%fusion.e": "jit(sweep_trace_os)/vmap(stages)/div",
}


def _synthetic():
    host = [("window", 0, 1000), ("study", 100, 800), ("study.run", 100, 780),
            ("study.plan", 110, 40), ("sweep", 200, 600),
            ("sweep.columns", 200, 100), ("sweep.dispatch", 300, 50),
            ("sweep.fetch", 350, 440), ("study.frame", 820, 50)]
    ops = [("%while.1", 340, 360), ("%fusion.a", 350, 50),
           ("%while.b", 400, 200), ("%fusion.c", 420, 80),
           ("%fusion.d", 500, 80), ("%fusion.e", 650, 40)]
    data = (_plane("/host:CPU", {"python": host},
                   lambda n: SWEEP_ARGS if n == "sweep" else {})
            + _plane("/device:TPU:0",
                     {"XLA Modules": [("jit_sweep_trace_os(123)", 340, 360)],
                      "XLA Ops": ops},
                     lambda n: {"tf_op": TF_OPS[n]} if n in TF_OPS else {}))
    return data


# --- the raw reader --------------------------------------------------------

@pytest.fixture(scope="module")
def old_trace():
    with gzip.open(OLD) as f:
        data = f.read()
    return data, ProfileData.from_serialized_xspace(data)


def test_the_raw_reader_agrees_with_profile_data(old_trace):
    data, pd = old_trace
    raw = scopes.device_lines(data)
    planes = tracing._device_planes(pd)
    assert sorted(raw) == sorted(p.name for p in planes)
    for p in planes:
        for ln in p.lines:
            if ln.name in (tracing.OPS_LINE, scopes.MODULES_LINE):
                got = [(n, s, e) for n, s, e, _ in raw[p.name][ln.name]]
                assert got == [(ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in ln.events]
    tf_ops = {op for *_, op in raw["/device:TPU:0"][tracing.OPS_LINE]}
    assert "jit(fn)/vmap()/add" in tf_ops


def test_the_raw_reader_reads_a_synthetic_trace():
    raw = scopes.device_lines(_synthetic())["/device:TPU:0"]
    assert raw["XLA Ops"][1] == ("%fusion.a", 350_000, 400_000,
                                 TF_OPS["%fusion.a"])
    assert raw["XLA Modules"] == [("jit_sweep_trace_os(123)", 340_000,
                                   700_000, "")]


@pytest.mark.parametrize("path, scope", [
    ("jit(f)/while/body/vmap(generate)/replay/mul", "replay"),
    ("jit(f)/vmap(stages)/div", "stages"),
    ("jit(f)/cond/branch_1_fun/escape/while/body/mul", "escape"),
    ("jit(fn)/vmap()/add", None),
    ("", None),
])
def test_innermost_scope(path, scope):
    assert scopes.innermost(path, scopes.SCOPES) == scope


# --- the split -------------------------------------------------------------

def test_the_split_of_a_synthetic_trace():
    data = _synthetic()
    red = scopes.reduce(data)
    us = 1e-6
    # self time: each op less what its nested ops cover
    assert red["scope_s"] == pytest.approx({
        "unscoped": 70 * us, "generate": 50 * us, "chunk_scan": 120 * us,
        "escape": 80 * us, "stages": 40 * us})
    busy = tracing.reduce(ProfileData.from_serialized_xspace(data))
    assert sum(red["scope_s"].values()) == pytest.approx(busy["busy_s"][0])
    assert red["program_s"] == {"jit_sweep_trace_os": pytest.approx(360 * us)}
    # idle 100..340 and 700..900, each part given to the innermost span
    assert red["span_idle_s"] == pytest.approx({
        "study.run": 90 * us, "study.plan": 40 * us,
        "sweep.columns": 100 * us, "sweep.dispatch": 40 * us,
        "sweep.fetch": 90 * us, "sweep": 10 * us, "study.frame": 50 * us,
        "unspanned": 20 * us})
    assert sum(red["span_idle_s"].values()) == pytest.approx(
        busy["idle_in_studies_s"][0])
    assert red["span_args"] == {"designs": 3, "streams": 8, "blocks": 1,
                                "block": 8, "sweeps": 1}
    m = scopes.per_layer(red, cells=4, studies=1)
    assert m == pytest.approx({
        "generate_ms_per_cell": 50 / 4 * 1e-3, "decode_ms_per_cell": 0.0,
        "replay_ms_per_cell": 200 / 4 * 1e-3,
        "stages_ms_per_cell": 40 / 4 * 1e-3,
        "unscoped_ms_per_cell": 70 / 4 * 1e-3, "replay_escape_share": 0.4,
        "columns_idle_ms_per_study": 0.1, "dispatch_idle_ms_per_study": 0.04,
        "fetch_idle_ms_per_study": 0.09,
        "plan_frame_idle_ms_per_study": 0.18,
        "unspanned_ms_per_study": 0.02})


def test_owned_gives_each_instant_to_the_innermost_interval():
    ivs = [(0, 10, "a"), (2, 4, "b"), (3, 4, "c"), (6, 12, "d")]
    assert scopes.owned(ivs, 1, 11) == [
        (1, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"), (6, 11, "d")]
    assert scopes.owned([], 0, 5) == []


def test_nothing_to_read_without_a_window_scope_or_span(old_trace):
    data, pd = old_trace
    no_window = _plane("/device:TPU:0", {"XLA Ops": [("%f", 1, 2)]})
    assert scopes.reduce(no_window) is None
    # the recorded trace predates the names: nothing scoped or spanned
    red = scopes.reduce(data, pd)
    assert set(red["scope_s"]) == {"unscoped"}
    assert set(red["span_idle_s"]) == {"unspanned"}
    assert scopes.per_layer(red, cells=10, studies=68) == {}
    assert scopes.per_layer(scopes.reduce(_synthetic()), 0, 0) == {}


def test_the_command_reduces_a_recorded_trace(capsys):
    assert scopes.main(["--trace", OLD, "--cells", "10"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["studies"] == 68
    assert out["metrics"]["device_busy_ms_per_cell"] == pytest.approx(
        0.21067980000054956, rel=1e-12)
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps",
                                     "device_scopes", "device_programs",
                                     "idle_spans"}
    assert [n for n, _ in out["breakdown"]["device_programs"]] == [
        "jit_convert_element_type", "jit_fn"]


def test_the_existing_reduction_reads_what_it_read(old_trace):
    """`tracing.reduce` on the recorded trace, as it read when the
    program had no spans or scopes (a reduction of a trace is fixed)."""
    red = tracing.reduce(old_trace[1])
    pins = {"window_s": 2.043663854, "studies": 68, "devices": 1}
    assert {k: red[k] for k in pins} == pytest.approx(pins, rel=1e-12)
    assert red["busy_s"] == pytest.approx([0.0021067980000054956], rel=1e-12)
    assert red["idle_in_studies_s"] == pytest.approx([2.0411065769999936],
                                                     rel=1e-12)
    assert (len(red["op_s"]), len(red["gap_s"]), len(red["host_s"])) == (
        50, 38, 50)
    assert sum(red["host_s"].values()) == pytest.approx(12.99450561,
                                                         rel=1e-12)
    assert dict(tracing.ranked(red["op_s"], 3)) == pytest.approx({
        "%copy.1": 0.001186852000003527,
        "%divide_minimum_fusion": 0.0001309959999995225,
        "%add_add_fusion.1": 8.163300000074009e-05}, rel=1e-12)
    assert dict(tracing.ranked(red["gap_s"], 3)) == pytest.approx({
        "shorter idle gaps": 0.5545138229999744,
        "np.asarray(jax.Array)": 0.44774341999999934,
        "D2H Dispatch": 0.1395240730000009}, rel=1e-12)


def test_slim_keeps_what_both_reductions_read(old_trace):
    data, pd = old_trace
    thin = scopes.slim(data)
    assert len(thin) < len(data)
    pd_thin = ProfileData.from_serialized_xspace(thin)
    assert tracing.reduce(pd_thin) == tracing.reduce(pd)
    assert scopes.reduce(thin, pd_thin) == scopes.reduce(data, pd)


# --- the recorded trace of the named program -------------------------------

@pytest.fixture(scope="module")
def scoped_trace():
    """One `vitb-edp.search-rung` Study on a TPU v5 lite, traced by
    `python3 -m chipbench.scopes --seconds 0` (kept slimmed)."""
    with gzip.open(os.path.join(DATA, "search-rung-study.xspace.gz")) as f:
        data = f.read()
    return data, ProfileData.from_serialized_xspace(data)


def test_the_scoped_trace_holds_every_name(scoped_trace):
    data, pd = scoped_trace
    red = scopes.reduce(data, pd)
    assert set(scopes.SCOPES) <= set(red["scope_s"])
    # the benchmark sets no cache directory, so no `study.cache`
    assert set(scopes.SPANS) - {"study.cache"} <= set(red["span_idle_s"])
    programs = {n for n in red["program_s"] if n.startswith("jit_sweep_")}
    assert len(programs) == 7 and "jit_fn" not in red["program_s"]
    assert red["span_args"] == {"designs": 16, "streams": 768, "blocks": 7,
                                "block": 768, "sweeps": 7}


def test_the_scoped_trace_splits_busy_and_idle(scoped_trace):
    data, pd = scoped_trace
    red, old = scopes.reduce(data, pd), tracing.reduce(pd)
    assert sum(red["scope_s"].values()) == pytest.approx(old["busy_s"][0],
                                                         rel=0.01)
    assert sum(red["span_idle_s"].values()) == pytest.approx(
        old["idle_in_studies_s"][0], rel=0.01)
    m = scopes.per_layer(red, cells=16, studies=1)
    assert m["unscoped_ms_per_cell"] < 0.02 * sum(
        m[k] for k in ("generate_ms_per_cell", "decode_ms_per_cell",
                       "replay_ms_per_cell", "stages_ms_per_cell"))
    assert m["unspanned_ms_per_study"] < 10
