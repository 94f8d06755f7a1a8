"""Rate arithmetic, the count of failed cells, and the verdict."""
import numpy as np
import pytest

from chipbench import check, harness
from chipbench import reference as ref


def _frame(n, failed=(), engine="xla", drop=0, nan=()):
    from repro.api.study import StudyResult
    rows = n - drop
    cols = {"design": np.array([f"d{i}" for i in range(rows)], object),
            "workload": np.array(["w"] * rows, object),
            "fidelity": np.array(["trace"] * rows, object)}
    for c in ref.COLUMNS:
        cols[c] = np.arange(rows, dtype=np.float64) + 1.0
    cols["batched"] = np.ones(rows)
    cols["cell_status"] = np.zeros(rows)
    for i in failed:
        cols["cell_status"][i] = 1.0
        for c in ref.COLUMNS:
            cols[c][i] = np.nan
    for i in nan:
        cols["edp"][i] = np.inf
    res = StudyResult(cols, {"design": list(cols["design"]),
                             "workload": ["w"], "fidelity": ["trace"]})
    res.meta["engine"] = engine
    return res


def _picks(n, first=0):
    return [{"design": first + i} for i in range(n)]


def test_rate_counts_all_cells_over_all_the_window_and_excludes_failed():
    frames = [(_frame(8), _picks(8)), (_frame(8, failed=(1, 5)), _picks(8)),
              (_frame(8, drop=2), _picks(8))]
    attempted, bad, cells = harness.tally(frames, 8, "xla")
    assert attempted == 24
    assert bad == 4                      # two failed, two missing rows
    assert len(cells) == 20
    assert harness.cells_per_s(len(cells), 4.0) == pytest.approx(5.0)
    assert harness.cells_per_s(0, 0.0) == 0.0


def test_non_finite_and_wrong_engine_cells_are_bad():
    assert check.count_bad(_frame(6, nan=(2,)), 6, "xla") == 1
    assert check.count_bad(_frame(6), 6, "pallas") == 6


def test_answered_cells_are_matched_by_label():
    picks = _picks(4, first=10)
    cells = harness.answered(_frame(4, failed=(2,)), picks)
    assert [k["design"] for k, _ in cells] == [10, 11, 13]
    assert cells[2][1]["total_cycles"] == 4.0


def test_sample_holds_the_longest_cell_and_is_seeded():
    totals = [5.0, 9.0, 1.0, 7.0, 3.0]
    a = check.draw_sample(totals, 3, 2**31 + 5)
    assert a[0] == 1 and len(a) == 3 and len(set(a)) == 3
    assert a == check.draw_sample(totals, 3, 2**31 + 5)
    assert sorted(check.draw_sample(totals, 99, 1)) == [0, 1, 2, 3, 4]
    assert check.draw_sample([], 3, 1) == []


def test_judge_holds_each_number_to_its_limit():
    limits = {"bad_cells": 0, "analytic_err": 1e-5, "cycles_err": 1e-2}
    ok = {"bad_cells": 0, "analytic_err": 1e-6, "cycles_err": 1e-2}
    checks, correct = check.judge(ok, limits)
    assert correct and list(checks) == list(check.NUMBERS)
    for k, v in (("bad_cells", 1), ("analytic_err", 2e-5),
                 ("cycles_err", 0.5)):
        assert not check.judge(dict(ok, **{k: v}), limits)[1]
