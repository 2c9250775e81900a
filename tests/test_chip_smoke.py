"""chip_smoke.py on the CPU: its record checks at a tiny size with the Pallas
kernel in interpret mode against the sparse-jnp reference, and its refusal
to run (or to pass) anywhere but on a TPU with the compiled kernel."""
import pytest

import chip_smoke
from repro.core import JRBAEngine


@pytest.fixture(scope="module")
def engine():
    return JRBAEngine(solver="pallas-interpret", n_iters=100)


def test_smoke_phases_match_reference_in_interpret_mode(engine):
    row, lockstep = chip_smoke.phase_fleet(engine, 8)
    assert row["lanes"] == 8
    assert row["max_record_dev"] == 0.0 and row["unfinished"] == 0
    assert row["events"] > 0 and row["dispatches"] > 0 and row["compiled_shapes"] > 0
    row = chip_smoke.phase_wan(engine, 1, n_jobs=4)
    assert row["lanes"] == 2 and row["max_record_dev"] == 0.0 and row["unfinished"] == 0
    row = chip_smoke.phase_async(engine, 8, lockstep)
    assert row["max_record_dev"] == 0.0 and row["unfinished"] == 0


def test_smoke_record_check_rejects_a_deviation(engine):
    _, lockstep = chip_smoke.phase_fleet(engine, 2)
    other, _ = chip_smoke._run(engine, chip_smoke.build_async_fleet(engine, 2, seed0=5))
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke._check_records("fleet", lockstep, other.results)


def test_smoke_compiled_phase_rejects_interpret_mode(engine):
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_compiled(engine)


def test_smoke_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
