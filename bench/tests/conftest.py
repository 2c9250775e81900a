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


@pytest.fixture
def small_run(monkeypatch, tmp_path):
    """``run_cell(cell, seed, fault=None, trace=0)``: one run of ``cell``
    through ``run.main`` at the ``SMALL`` size on the CPU, optionally with a
    planted fault from ``control.py``. Returns the result object."""
    import control
    import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.setattr(run, "CACHE_DIR", str(tmp_path / "jax_cache"))
    original = run.load_cell

    def load_small(name):
        cell, config, traffic, spec = original(name)
        return cell, config, dict(traffic, **SMALL[cell["traffic"]]), spec

    monkeypatch.setattr(run, "load_cell", load_small)

    def run_cell(cell, seed, fault=None, trace=0):
        argv = ["--workload", cell, "--seed", str(seed), "--seconds", "0.01",
                "--trace", str(trace)]
        if fault is None:
            return run.main(argv, devices=[FakeChip()])
        return control.run_broken(fault, argv, devices=[FakeChip()])

    return run_cell
