"""The per-layer metrics that read the program's own spans and counters:
all eight are in a small traced run on the CPU, and they split the layers
they belong to as the program measures them."""

NEW = (
    "engine.refine_share", "engine.sweeps_per_program", "engine.stage_share",
    "engine.paths_share", "engine.backend_compiles", "sched.alloc_share",
    "sched.loop_share", "fleet.driver_share",
)


def test_traced_run_reports_the_program_span_metrics(small_run, monkeypatch):
    monkeypatch.setenv("REPRO_JRBA_SOLVER", "sparse")
    out = small_run("ents-mesh.otfa", 2**31 + 19, trace=1)
    assert out["correct"] is True
    m = {name: v["value"] for name, v in out["metrics"].items()}
    for name in NEW:
        assert name in m, name
        assert m[name] >= 0.0, name
    assert m["engine.refine_share"] <= m["engine.finalize_share"]
    assert m["engine.stage_share"] <= m["engine.dispatch_share"]
    assert m["engine.sweeps_per_program"] >= 1.0
    # the host outside the engine's phases is the event loop, Algorithm 1
    # and the driver, up to solve_many's own work outside the phases
    split = m["sched.alloc_share"] + m["sched.loop_share"] + m["fleet.driver_share"]
    assert split <= m["host.other_share"] + 1e-9
    assert split >= m["host.other_share"] - 10.0
