"""A run with the timed path broken underneath must not come out correct:
the control (bandwidths without Eq. 15's sharing) and each fault a cell can
have (``control.py``): answers left out or stale or altered, a relaxation
(the kernel) that returns nothing or an even spread, rounding without its
sweeps or onto the first path, Algorithm 1 blind to transfers. A fault that
hands the event loop a missing or foreign answer may instead crash the
program; the run then prints no result line, which its caller reads as not
correct."""
import pytest

import control


@pytest.mark.parametrize("fault", control.FAULTS)
def test_broken_path_is_not_correct(fault, small_run, monkeypatch):
    monkeypatch.setenv("REPRO_JRBA_SOLVER", "sparse")
    try:
        out = small_run("ents-mesh.otfa", 2**31 + 3, fault=fault)
    except (AttributeError, TypeError, IndexError):
        assert fault in ("half", "stale")
        return
    assert out["correct"] is False
    over = [n for n, c in out["checks"].items() if c["value"] > c["limit"]]
    assert over, out["checks"]
