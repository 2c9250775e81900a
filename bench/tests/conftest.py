"""Shared set-up of the benchmark's own tests (``python -m pytest bench/tests``):
the program and the harness on the import path, and a small CPU stand-in for
a run of a cell."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


class FakeChip:
    """Stands in for the TPU so a run can go past the harness's look for a
    chip; everything under it runs on the CPU."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return None


# small sizes for a CPU run, per traffic mix: lanes, jobs per lane and a
# warm-up grid of one batch size (the CPU compiles every shape it warms)
WARM = {"grid": [[8, 1]]}
SMALL = {
    "async": {"lanes": 4, "warm": WARM},
    "otfa": {"lanes": 4, "warm": WARM},
    "lockstep": {"lanes": 4, "warm": WARM},
    "backlog": {"lanes": 2, "jobs_per_lane": 4, "warm": WARM},
}
# the cut of a mix that ``SMALL`` does not name and that holds no "small"
CUT = {"lanes": 4, "jobs_per_lane": 4}


def small_traffic(name: str, traffic: dict) -> dict:
    """Mix ``name`` at a CPU run's size: ``SMALL``'s entry where it names the
    mix; else the mix's own ``"small"`` object (``lanes``, ``jobs_per_lane``,
    ``warm``) over a cut to at most ``CUT``'s lanes and jobs with the
    one-tier ``WARM`` grid. A new mix needs no entry here."""
    if name in SMALL:
        return dict(traffic, **SMALL[name])
    cut = {key: min(traffic[key], most) for key, most in CUT.items()}
    return {**traffic, **cut, "warm": WARM, **traffic.get("small", {})}


def chips(run, name: str) -> list:
    """One stand-in chip for each chip cell ``name`` asks for."""
    return [FakeChip() for _ in range(run.load_cell(name)[0]["chips"])]


@pytest.fixture
def small_run(monkeypatch, tmp_path):
    """``run_cell(cell, seed, fault=None, trace=0)``: one run of ``cell``
    through ``run.main`` at its small size (``small_traffic``) on the CPU,
    handed one stand-in chip for each chip the cell asks for, optionally with
    a planted fault from ``control.py``. Returns the result object."""
    import control
    import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.setattr(run, "CACHE_DIR", str(tmp_path / "jax_cache"))
    original = run.load_cell

    def load_small(name):
        cell, config, traffic, spec = original(name)
        return cell, config, small_traffic(cell["traffic"], traffic), spec

    monkeypatch.setattr(run, "load_cell", load_small)

    def run_cell(cell, seed, fault=None, trace=0):
        argv = ["--workload", cell, "--seed", str(seed), "--seconds", "0.01",
                "--trace", str(trace)]
        if fault is None:
            return run.main(argv, devices=chips(run, cell))
        return control.run_broken(fault, argv, devices=chips(run, cell))

    return run_cell
