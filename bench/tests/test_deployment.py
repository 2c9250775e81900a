"""A new deployment enters the benchmark as new files only.

A copy of the checkout (``BENCHMARK.json`` and ``bench/``) gains the files
under ``deployment/`` and nothing else: a configuration with a ``scheduler``
block and a ``checks`` entry (``ents-mesh-failover``), a traffic mix under a
new name with no ``"small"`` object (``failover``), generator kinds for link
and node failures and for cameras on a sensor tier, and one check
(``outage``); ``BENCHMARK.json`` gains one configuration and one cell. The
copy's own tests then run the cell, and a script drives the copy's harness:
with the stall budget, a permanent node failure is migrated; without it,
jobs are stranded; a reference switch is refused; a scheduler deaf to
failures is caught by the configuration's own check, also where every
failure recovers."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

DEPLOYMENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "deployment")
CONFIG = "ents-mesh-failover"
CELL = "ents-mesh-failover.failover"
SEED = 2**31 + 11
OUTAGE = ("outage_view_faults", "outage_route_faults", "outage_time_faults")

# runs the copy's harness at the small size: the sound cell, the cell with
# its scheduler block left out, with a reference switch set, and deaf, on the
# mix as it is and on its link failures alone, which all recover
SCRIPT = r"""
import json, os, sys
root, cell, seed = sys.argv[1], sys.argv[2], sys.argv[3]
sys.path[:0] = [os.path.join(root, "bench", "tests"), os.path.join(root, "bench")]
import conftest, control, run
run.CACHE_DIR = os.path.join(root, ".jax_cache")
original, patch, mix = run.load_cell, {}, {}

def load_small(name):
    c, config, traffic, spec = original(name)
    config = {k: v for k, v in dict(config, **patch).items() if v is not None}
    return c, config, conftest.small_traffic(c["traffic"], dict(traffic, **mix)), spec

run.load_cell = load_small
argv = ["--workload", cell, "--seed", seed, "--seconds", "0.01", "--trace", "0"]
chips = conftest.chips(run, cell)
out = {"sound": run.main(argv, devices=chips)}
patch = {"scheduler": None}
out["no_budget"] = run.main(argv, devices=chips)
patch = {"scheduler": {"OTFS": {"stall_budget": 1.0, "speculate": False}}}
try:
    run.main(argv, devices=chips)
except SystemExit as e:
    out["speculate"] = e.code
patch = {}
out["deaf"] = control.run_broken("deaf", argv, devices=chips)
mix = {"churn": [c for c in original(cell)[2]["churn"] if c["kind"] == "link_failure"]}
out["recovering"] = run.main(argv, devices=chips)
out["deaf_recovering"] = control.run_broken("deaf", argv, devices=chips)
print(json.dumps(out))
"""


def _files(root):
    found = {}
    for base, dirs, names in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                found[os.path.relpath(path, root)] = f.read()
    return found


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The copy with the new deployment added, and its files before."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(root)
    for rel in _files(DEPLOYMENT):
        dest = root / "bench" / rel
        assert not dest.exists(), rel
        dest.parent.mkdir(exist_ok=True)
        shutil.copy(os.path.join(DEPLOYMENT, rel), dest)
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": CONFIG, "source": "https://arxiv.org/abs/2210.07842",
        "file": f"bench/configs/{CONFIG}.json", "reduced": [],
        "why": "the ents-mesh cluster on failing links and nodes, OTFS lanes with a stall budget",
    })
    spec["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "failover", "chips": 1,
        "why": "OTFS clusters whose links fail and recover and whose nodes die for good",
    })
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f, indent=2)
    return root, before


@pytest.fixture(scope="module")
def runs(checkout):
    root, _ = checkout
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_JRBA_SOLVER="sparse",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(root), CELL, str(SEED)],
                          cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_deployment_adds_files_and_cell_entries_only(checkout):
    root, before = checkout
    after = _files(root)
    changed = [rel for rel, data in before.items() if after[rel] != data]
    assert changed == ["BENCHMARK.json"]
    old = json.loads(before["BENCHMARK.json"])
    new = json.loads(after["BENCHMARK.json"])
    for key in ("configs", "workloads"):
        assert new[key][: len(old[key])] == old[key]
    assert {k: v for k, v in new.items() if k not in ("configs", "workloads")} == {
        k: v for k, v in old.items() if k not in ("configs", "workloads")
    }
    added = {rel for rel in after if rel not in before}
    assert added == {os.path.join("bench", rel) for rel in _files(DEPLOYMENT)}
    with open(os.path.join(DEPLOYMENT, "traffic", "failover.json")) as f:
        assert "small" not in json.load(f)


def test_the_new_cell_runs_correct_through_the_copys_cell_test(checkout):
    root, _ = checkout
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    test = f"bench/tests/test_cells.py::test_cell_runs_correct_at_a_small_size[{CELL}]"
    proc = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", test],
                          cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:]
    assert "1 passed" in proc.stdout


def test_a_permanent_node_failure_is_migrated_within_the_stall_budget(runs):
    out = runs["sound"]
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0
    assert out["samples"]["migrations"] > 0
    assert set(OUTAGE) <= set(out["checks"])


def test_without_the_stall_budget_jobs_are_stranded(runs):
    out = runs["no_budget"]
    assert out["failed"] > 0
    assert out["samples"]["migrations"] == 0
    assert out["correct"] is False


def test_a_reference_switch_in_the_configuration_is_refused(runs):
    assert runs["speculate"] == 2


def test_the_configurations_own_check_catches_a_scheduler_deaf_to_failures(runs):
    out = runs["deaf"]
    assert out["correct"] is False
    over = {n for n, c in out["checks"].items() if c["value"] > c["limit"]}
    assert over and over <= set(OUTAGE), out["checks"]


def test_the_own_check_catches_a_deaf_scheduler_whose_failures_all_recover(runs):
    assert runs["recovering"]["correct"] is True, runs["recovering"]["checks"]
    out = runs["deaf_recovering"]
    assert out["correct"] is False
    assert out["checks"]["outage_view_faults"]["value"] > 0, out["checks"]


def test_the_outage_check_replays_liveness_from_the_churn_steps():
    import importlib.util
    import types

    import numpy as np

    from repro.core.scenarios import ChurnOp, ChurnStep

    spec = importlib.util.spec_from_file_location(
        "outage", os.path.join(DEPLOYMENT, "checks", "outage.py"))
    outage = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(outage)
    # a line 0 - 1 - 2 with a shortcut 0 - 2: node 1 is down over [2, 5),
    # the shortcut from 4 on for good
    lane = types.SimpleNamespace(links=[(0, 1), (0, 2), (1, 2)], churn=[
        ChurnStep(2.0, (ChurnOp("fail_node", node=1),)),
        ChurnStep(4.0, (ChurnOp("fail", link=(0, 2)),)),
        ChurnStep(5.0, (ChurnOp("recover_node", node=1),)),
    ])
    live = outage.Liveness(lane)
    assert [m.tolist() for m in live.masks] == [
        [True, True, True], [False, True, False], [False, False, False], [True, False, True]]
    assert live.cut_time([(0, 2)], 0.0, 10.0) == 1.0  # [4, 5): no path at all
    assert live.cut_time([(0, 1)], 3.0, 10.0) == 2.0  # [3, 5)
    assert live.cut_time([(0, 1)], 0.0, 2.0) == 0.0
    assert live.masks[live.before(4.0)].tolist() == [False, True, False]
    # at an instant with a step: the state before it or after it
    assert [m.tolist() for m in live.at(4.0)] == [[False, True, False], [False, False, False]]
    assert [m.tolist() for m in live.at(4.5)] == [[False, False, False]]
    views = [(1.0, [True] * 3), (4.0, [False, True, False]), (4.5, [False, True, False])]
    lane.views = [(t, np.array(v)) for t, v in views]
    lane.power, lane.churn_times = np.ones(3), [s.time for s in lane.churn]
    result = types.SimpleNamespace(records=[])
    got = outage.compare({"lane_results": [(lane, result)]})
    assert got["outage_view_faults"] == 1.0  # at 4.5 the shortcut is down


def test_a_mix_gets_its_cpu_size_from_small_or_the_generic_cut():
    from conftest import SMALL, WARM, small_traffic

    big = {"lanes": 512, "jobs_per_lane": 32, "warm": {"grid": [[32, 1024]]}, "driver": "async"}
    assert small_traffic("backlog", big) == dict(big, **SMALL["backlog"])
    assert small_traffic("new", big) == dict(big, lanes=4, jobs_per_lane=4, warm=WARM)
    few = dict(big, lanes=2, jobs_per_lane=1)
    assert small_traffic("new", few) == dict(few, warm=WARM)
    own = dict(big, small={"lanes": 8, "warm": {"grid": [[16, 2]]}})
    assert small_traffic("new", own) == dict(own, lanes=8, jobs_per_lane=4,
                                             warm={"grid": [[16, 2]]})
