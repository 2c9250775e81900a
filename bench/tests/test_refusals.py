"""The measurement path refuses to run where it cannot measure: no TPU, a
device kind without peaks, a checkout holding only the benchmark."""
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ents-mesh.otfa", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_refuses_in_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_unknown_device_kind_fails_against_the_peaks_table():
    import run

    assert run.device_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(SystemExit) as err:
        run.device_peaks("TPU v9 imaginary")
    assert err.value.code != 0


def test_unknown_workload_is_refused():
    import run

    with pytest.raises(SystemExit) as err:
        run.load_cell("no-such.cell")
    assert err.value.code != 0
