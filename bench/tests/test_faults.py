"""A run with the timed path broken underneath must not come out correct:
the control (bandwidths without Eq. 15's sharing) and each fault a cell can
have (``control.py``): answers left out or stale or altered, a relaxation
(the kernel) that returns nothing or an even spread, rounding without its
sweeps or onto the first path, Algorithm 1 blind to transfers, and in the
cells with OTFS lanes a residual that admissions leave unreduced. A fault that
hands the event loop a missing or foreign answer may instead crash the
program; the run then prints no result line, which its caller reads as not
correct."""
import json
import os
import types

import numpy as np
import pytest

import check
import control
from conftest import ROOT
from test_cells import CELLS, SPEC


def _policies(cell):
    traffic = next(w for w in SPEC["workloads"] if w["name"] == cell)["traffic"]
    with open(os.path.join(ROOT, "bench", "traffic", traffic + ".json")) as f:
        return json.load(f)["policies"]


OTFS_CELLS = [c for c in CELLS if "OTFS" in _policies(c)]


@pytest.mark.parametrize("fault", control.FAULTS)
def test_broken_path_is_not_correct(fault, small_run, monkeypatch):
    _broken_run_is_not_correct("ents-mesh.otfa", fault, small_run, monkeypatch)


@pytest.mark.parametrize("cell", [c for c in CELLS if c != "ents-mesh.otfa"])
@pytest.mark.parametrize("fault", control.FAULTS)
def test_broken_path_is_not_correct_in_the_other_cells(fault, cell, small_run, monkeypatch):
    _broken_run_is_not_correct(cell, fault, small_run, monkeypatch)


@pytest.mark.parametrize("cell", OTFS_CELLS)
@pytest.mark.parametrize("fault", control.OTFS_FAULTS)
def test_broken_otfs_path_is_not_correct(fault, cell, small_run, monkeypatch):
    # jobs of one lane that run together: more of them than the small size
    # holds, on as few lanes
    import conftest

    traffic = next(w for w in SPEC["workloads"] if w["name"] == cell)["traffic"]
    monkeypatch.setitem(conftest.SMALL, traffic, dict(conftest.SMALL[traffic], jobs_per_lane=6))
    monkeypatch.setenv("REPRO_JRBA_SOLVER", "sparse")
    out = small_run(cell, 2**31 + 3, fault=fault)
    assert out["correct"] is False
    assert out["checks"]["link_overload"]["value"] > out["checks"]["link_overload"]["limit"]


def test_link_overload_replays_the_jobs_running_at_each_admission():
    # a line 0 - 1 - 2 of capacity 1 and 2; job a holds link 0 over [0, 4),
    # b holds links 0 and 1 from its admission at 4 (a's finish) to 6, c
    # holds link 1 over [5, 7)
    def record(start, end, routes, bandwidths):
        return types.SimpleNamespace(schedule_time=start, finish_time=end, routes=routes,
                                     bandwidths=np.array(bandwidths))

    def lane(policy="OTFS", churn=()):
        return types.SimpleNamespace(policy=policy, links=[(0, 1), (1, 2)],
                                     capacity=np.array([1.0, 2.0]), churn_times=list(churn))

    a = record(0.0, 4.0, [[1, 0]], [1.0])
    b = record(4.0, 6.0, [[0, 1, 2]], [1.0])
    c = record(5.0, 7.0, [[2, 1]], [1.0])
    result = types.SimpleNamespace(records=[a, b, c])
    assert check.link_overload([(lane(), result)]) == 0.0
    c.bandwidths = np.array([1.5])  # 2.5 on link 1 over [5, 6)
    assert check.link_overload([(lane(), result)]) == pytest.approx(0.25)
    # OTFA lanes and lanes whose network churns are not replayed
    assert check.link_overload([(lane("OTFA"), result), (lane(churn=[1.0]), result)]) == 0.0
    c.routes = [[2, 0]]  # no link joins 2 and 0
    assert check.link_overload([(lane(), result)]) == np.inf


def _broken_run_is_not_correct(cell, fault, small_run, monkeypatch):
    monkeypatch.setenv("REPRO_JRBA_SOLVER", "sparse")
    try:
        out = small_run(cell, 2**31 + 3, fault=fault)
    except (AttributeError, TypeError, IndexError):
        assert fault in ("half", "stale")
        return
    assert out["correct"] is False
    over = [n for n, c in out["checks"].items() if c["value"] > c["limit"]]
    assert over, out["checks"]
