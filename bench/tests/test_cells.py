"""Each cell's run on the CPU at a small size, past the harness's look for a
chip: the pass builder, the warm-up, the window, the reference check and the
result line, with the kernel in Pallas interpret mode."""
import json
import os

import pytest

from conftest import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_a_small_size(cell, small_run, monkeypatch):
    monkeypatch.setenv("REPRO_JRBA_SOLVER", "pallas-interpret")
    out = small_run(cell, 2**31 + 7)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    cfg = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert cfg["chips"] in (1, 4)
    assert out["device"]["count"] == cfg["chips"]
    assert set(out["metrics"]) == {
        m["name"] for m in SPEC["end_to_end"] if cell in m.get("workloads", [cell])
    }
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_traced_run_reads_the_host_side_layers(small_run, monkeypatch):
    # on the CPU there is no device plane: the device metrics read nothing
    # and are left out, the engine and fleet ones are there
    monkeypatch.setenv("REPRO_JRBA_SOLVER", "sparse")
    out = small_run("ents-mesh.otfa", 11, trace=1)
    assert out["correct"] is True
    assert "device.idle_share" not in out["metrics"]
    assert "kernel.roofline" not in out["metrics"]
    for name in ("fleet.occupancy", "engine.dispatch_share", "host.other_share"):
        assert name in out["metrics"]


def test_seed_draws_the_data_and_fixes_the_sizes(monkeypatch):
    import fleetgen
    from repro.core import JRBAEngine

    monkeypatch.setenv("REPRO_JRBA_SOLVER", "sparse")
    with open(os.path.join(ROOT, "bench/configs/ents-mesh.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "bench/traffic/async.json")) as f:
        traffic = dict(json.load(f), lanes=8)
    engine = JRBAEngine()

    def signature(seed, pass_index=1):
        sims, lanes = fleetgen.build_pass(engine, config, traffic, seed, pass_index)
        return [
            (s.name, s.scheduler.net.links, [round(t, 12) for t, _, _ in s.arrivals],
             l.churn_times)
            for s, l in zip(sims, lanes)
        ]

    def sizes(sig):
        return [(name, len(links), len(arr), bool(churn)) for name, links, arr, churn in sig]

    a, b = signature(2**31 + 5), signature(2**31 + 5)
    assert a == b
    # another seed, or another pass: other networks and arrivals, same sizes
    for other in (signature(2**31 + 6), signature(2**31 + 5, pass_index=2)):
        assert sizes(other) == sizes(a)
        assert all(x[1] != y[1] or x[2] != y[2] for x, y in zip(a, other))


def test_arrival_deck_deals_the_same_arrivals_in_another_order(monkeypatch):
    import fleetgen
    from repro.core import JRBAEngine

    monkeypatch.setenv("REPRO_JRBA_SOLVER", "sparse")
    with open(os.path.join(ROOT, "bench/configs/ents-mesh.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "bench/traffic/backlog.json")) as f:
        traffic = dict(json.load(f), jobs_per_lane=4)
    engine = JRBAEngine()

    def arrivals(seed, pass_index=1):
        sims, _ = fleetgen.build_pass(engine, config, traffic, seed, pass_index)
        return [tuple(round(t, 12) for t, _, _ in s.arrivals) for s in sims]

    a, b = arrivals(2**31 + 5), arrivals(2**31 + 6)
    assert sorted(a) == sorted(b)
    nets = [s.scheduler.net.links for s in fleetgen.build_pass(engine, config, traffic,
                                                                2**31 + 5, 1)[0]]
    others = [s.scheduler.net.links for s in fleetgen.build_pass(engine, config, traffic,
                                                                  2**31 + 6, 1)[0]]
    assert nets != others


def test_a_cluster_that_cannot_hold_the_job_is_drawn_again():
    import numpy as np

    import fleetgen
    import reference

    with open(os.path.join(ROOT, "bench/configs/ents-mesh.json")) as f:
        config = json.load(f)
    # lane 445 of pass 2 of this seed first draws a mesh of twelve 1-unit
    # nodes and two 4-unit ones: no room for the job's seven big tasks
    s_topo = fleetgen.lane_seeds(3300001002, 2, 445)[0]
    first = fleetgen.make(config["topology"], np.random.RandomState(s_topo))
    net = fleetgen.network(config, np.random.RandomState(s_topo))
    job = fleetgen.make(config["job"], np.random.RandomState(0), 0)
    tasks = [(t.workload, t.mem, t.pinned_node) for t in job.tasks]

    def fits(g):
        return reference.algorithm1(g.power, g.mem_max, g.links, g.capacity, g.link_alive,
                                    tasks, job.edges) is not None

    assert not fits(first)
    assert fits(net)
