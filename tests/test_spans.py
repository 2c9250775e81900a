"""Spans and counters inside the engine, Algorithm 1 and the fleet driver:
the counters nest inside the phases they split, every span a tracer records
also lands in a JAX profiler capture (nested on one thread), the null
tracer stays inert outside a capture, backend compiles are counted, and the
rounding readouts (per-program relaxation steps, relaxation-start wins)
report what the engine did."""
import collections
import glob
import importlib
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import JRBAEngine, OnlineScheduler, SCENARIOS
from repro.core.graph import Flow
from repro.fleet import FleetRuntime, build_async_fleet
from repro.obs import NULL_TRACER, Tracer, compiles
from repro.obs.trace import NULL_SPAN

# the module, not the jrba() function repro.core re-exports under its name
jrba = importlib.import_module("repro.core.jrba")

MODES = ("async", "lockstep")


def _fleet(mode, n_sims=6, *, tracer=None, n_iters=60):
    engine = JRBAEngine(k=2, n_iters=n_iters)
    fleet = FleetRuntime(engine, mode=mode, tracer=tracer).run(
        build_async_fleet(engine, n_sims, n_jobs=2)
    )
    return engine, fleet


def _capture(tmp_path, fn):
    """Run ``fn`` under a profiler capture; returns its value and the
    capture's host events of program spans as (name, start_ns, end_ns)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if "/" in e.name and not e.name.startswith("/"):
                    events.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out, events


# -- counters -----------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_counters_nest_inside_their_phases(mode):
    engine, fleet = _fleet(mode)
    st = engine.stats
    assert 0.0 < st.refine_seconds <= st.finalize_seconds
    assert 0.0 < st.stage_seconds + st.wait_seconds <= st.dispatch_seconds
    assert 0 < st.refined_programs <= st.refine_chains <= st.refine_sweeps
    assert 0 <= st.relax_start_wins <= st.refined_programs
    assert st.paths_computed > 0 and st.paths_seconds > 0.0
    host = fleet.telemetry.summary["host"]
    # each part is measured where it runs and no two overlap, so their sum
    # misses the wall by the driver's loop overhead between its blocks; a
    # pause of the process (a preemption, a collection) that lands between
    # blocks adds to that, so the smallest miss of three passes is held to 1%
    misses = []
    for fl in (fleet, _fleet(mode)[1], _fleet(mode)[1]):
        h = fl.telemetry.summary["host"]
        parts = h["lane_step_seconds"] + h["solve_many_seconds"] + h["driver_seconds"]
        assert h["driver_seconds"] > 0.0
        assert parts <= fl.wall_seconds
        misses.append(1.0 - parts / fl.wall_seconds)
    assert min(misses) < 1e-2, misses
    assert 0.0 < host["alloc_seconds"] <= host["lane_step_seconds"]
    assert host["alloc_calls"] == sum(r.alloc_calls for r in fleet.results) > 0
    assert host["solve_many_seconds"] == pytest.approx(
        fleet.telemetry.summary["latency"]["barrier"]["dispatch_seconds"]
    )


def test_alloc_is_timed_inside_the_base_allocator():
    """A subclass's work around ``_allocate`` is not Algorithm 1's."""
    import time

    class Slow(OnlineScheduler):
        def _allocate(self, job, job_id):
            time.sleep(0.02)
            return super()._allocate(job, job_id)

    net, arrivals = SCENARIOS["edge-mesh"].build(seed=1, n_jobs=3)
    res = Slow(net, "OTFA", k_paths=2, jrba_iters=40).run(arrivals)
    assert res.alloc_calls >= 3
    assert res.alloc_seconds < 0.02 * res.alloc_calls


# -- the profiler sink ----------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_capture_holds_the_spans_an_enabled_tracer_records(mode, tmp_path):
    engine = JRBAEngine(k=2, n_iters=60)
    tracer = Tracer()
    run = lambda: FleetRuntime(engine, mode=mode, tracer=tracer).run(  # noqa: E731
        build_async_fleet(engine, 6, n_jobs=2)
    )
    _, events = _capture(tmp_path, run)
    want = collections.Counter(e["name"] for e in tracer.events if e["ph"] == "B")
    got = collections.Counter(name for name, _, _ in events)
    # the capture marks every compile of the process; the tracer one instant
    # per relaxation call that compiled, counting the compiles inside it
    compiled = sum(
        e["args"]["compiles"] for e in tracer.events if e["name"] == "jax/compile"
    )
    assert compiled <= got.pop("jax/compile", 0)
    assert got == want
    for name in ("lane/step", "sched/alloc", "fleet/pick", "fleet/dispatch", "engine/build",
                 "engine/paths", "engine/stage", "engine/wait", "engine/finalize",
                 "engine/refine"):
        assert got[name] > 0, name
    # one thread: any two spans are disjoint or one holds the other
    ivs = sorted(((s, -e, n) for n, s, e in events))
    stack: list[int] = []
    for s, neg_e, name in ivs:
        while stack and stack[-1] <= s:
            stack.pop()
        assert not stack or -neg_e <= stack[-1], f"{name} straddles its parent"
        stack.append(-neg_e)


def test_null_tracer_reaches_a_capture_and_is_inert_outside(tmp_path):
    assert NULL_TRACER.span("x", track="t") is NULL_SPAN
    assert NULL_TRACER.resume("x", track="t") is NULL_SPAN
    NULL_TRACER.begin("y", track="t")
    NULL_TRACER.end("y", track="t")
    assert NULL_TRACER.events == [] and NULL_TRACER._open == {}
    (engine, fleet), events = _capture(tmp_path, lambda: _fleet("async", 4))
    names = {n for n, _, _ in events}
    assert {"lane/step", "event/arrive", "engine/wait", "fleet/dispatch"} <= names
    assert NULL_TRACER.events == [] and NULL_TRACER._open == {}


def test_observed_fleet_is_bit_identical_under_a_capture(tmp_path):
    _, plain = _fleet("async", 6)
    (_, captured), _ = _capture(tmp_path, lambda: _fleet("async", 6, tracer=Tracer()))
    for a, b in zip(plain.results, captured.results):
        assert [r.schedule_time for r in a.records] == [r.schedule_time for r in b.records]
        assert [r.finish_time for r in a.records] == [r.finish_time for r in b.records]
        assert [r.routes for r in a.records] == [r.routes for r in b.records]


def test_attributed_intervals_carry_their_dispatch(tmp_path):
    tracer = Tracer()
    _, fleet = _fleet("lockstep", 4, tracer=tracer)
    xs = [e for e in tracer.events if e["ph"] == "X"]
    dispatches = {
        e["args"]["dispatch"] for e in tracer.events
        if e["ph"] == "B" and e["name"] == "fleet/dispatch"
    }
    for name in ("lane/own_solve", "lane/barrier_stall", "sched/solve"):
        got = [e for e in xs if e["name"] == name]
        assert got and all(e["args"]["dispatch"] in dispatches for e in got), name
    tracer = Tracer()
    _fleet("async", 4, tracer=tracer)
    waits = [e for e in tracer.events if e["name"] in ("queue/wait", "sched/solve")]
    assert waits and all(e["args"]["dispatch"] >= 0 for e in waits)


# -- compiles -----------------------------------------------------------------


def test_backend_compiles_count_a_new_shape_once():
    net, _ = SCENARIOS["edge-mesh"].build(seed=5, n_jobs=1)
    engine = JRBAEngine(k=2, n_iters=37)  # a budget no other test compiles
    other = JRBAEngine(k=2, n_iters=37)
    flows = [Flow(0, 3, 1.0), Flow(1, 4, 2.0)]
    c0 = engine.stats.backend_compiles
    p0 = compiles.snapshot()
    engine.solve_many(net, [flows])
    c1 = engine.stats.backend_compiles
    assert c1 >= c0 + 1
    assert engine.stats.compile_seconds > 0.0
    # the process counter saw them too; another live engine did not
    assert (compiles.snapshot() - p0).compiles >= c1 - c0
    assert other.stats.backend_compiles == 0
    engine.solve_many(net, [flows])
    other.solve_many(net, [flows])  # compiled already: nothing new
    assert engine.stats.backend_compiles == c1
    assert other.stats.backend_compiles == 0


# -- rounding readouts ----------------------------------------------------------


def test_results_carry_their_own_relaxation_steps():
    net, _ = SCENARIOS["edge-mesh"].build(seed=2, n_jobs=1)
    engine = JRBAEngine(k=2, n_iters=100, solver="sparse")
    sets = [[Flow(0, 3, 1.0), Flow(1, 4, 2.0)], [Flow(0, 5, 1.0)],
            [Flow(2, 5, 1.0), Flow(3, 1, 0.5), Flow(4, 0, 3.0)]]
    s0 = engine.stats.solver_steps
    out = engine.solve_many(net, sets)
    assert out[1].relax_steps == 0  # one flow: the analytic path, no relaxation
    for res in (out[0], out[2]):
        assert 0 < res.relax_steps <= engine.n_iters
    assert out[0].relax_steps + out[2].relax_steps == engine.stats.solver_steps - s0
    single = engine.solve(net, sets[2])
    assert single.relax_steps == out[2].relax_steps
    dense = JRBAEngine(k=2, n_iters=50, solver="dense").solve(net, sets[0])
    assert dense.relax_steps == 50


@pytest.mark.parametrize("argmax_wins", [True, False])
def test_relax_start_wins_count_only_strict_wins(monkeypatch, argmax_wins):
    net, _ = SCENARIOS["edge-mesh"].build(seed=2, n_jobs=1)
    prog = jrba.build_program(net, [Flow(0, 3, 1.0), Flow(1, 4, 2.0)], k=2)
    m = np.zeros(prog.valid.shape)
    m[0, 1] = 1.0  # the argmax start: flow 0 on path 1, the rest on path 0
    start_w = np.argmax(np.where(prog.valid, m, -1.0), axis=1)
    monkeypatch.setattr(jrba, "_best_response_sweeps", lambda p, ks, counts=None: ks)
    bar = 1.0 if argmax_wins else 3.0
    monkeypatch.setattr(
        jrba, "_rounding_span", lambda p, ks: bar if np.array_equal(ks, start_w) else 2.0
    )
    stats = jrba.EngineStats()
    ks = jrba._round_and_refine(prog, m, stats)
    assert stats.relax_start_wins == int(argmax_wins)
    assert np.array_equal(ks, start_w) == argmax_wins


def test_telemetry_reports_relax_start_wins():
    engine, fleet = _fleet("async")
    solver = fleet.telemetry.summary["solver"]
    assert "phases" not in solver
    assert solver["refined_programs"] == engine.stats.refined_programs > 0
    assert solver["relax_start_wins"] == engine.stats.relax_start_wins
    assert solver["relax_start_win_rate"] == (
        solver["relax_start_wins"] / solver["refined_programs"]
    )
