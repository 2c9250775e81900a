"""Observability stack: tracer/metrics no-op discipline, Chrome-trace
integrity (strict JSON, begin/end balance), streaming-histogram accuracy
against numpy, barrier-stall conservation, event-span agreement with
simulation records, and the trace_report digest tool."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import JRBAEngine, OnlineScheduler, SCENARIOS
from repro.fleet import FleetRuntime, build_scenario_fleet
from repro.obs import (
    NULL_METRICS,
    NULL_TRACER,
    MetricsRegistry,
    StreamingHistogram,
    Tracer,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _strict_loads(text):
    """json.loads that rejects the non-RFC Infinity/NaN tokens."""

    def _reject(tok):
        raise AssertionError(f"non-RFC-8259 token in output: {tok}")

    return json.loads(text, parse_constant=_reject)


def _traced_fleet(tmp_path, *, n_sims=4, n_jobs=2, mode=None):
    """One small observed fleet run; returns (fleet, tracer, chrome_path).
    ``mode=None`` respects REPRO_FLEET_RUNTIME (so the async CI leg re-runs
    the mode-agnostic observability tests through the async driver); tests
    asserting lockstep-only artifacts pass ``mode="lockstep"``."""
    engine = JRBAEngine(k=2, n_iters=60)
    tracer = Tracer()
    runtime = FleetRuntime(engine, tracer=tracer, mode=mode)
    fleet = runtime.run(build_scenario_fleet(engine, n_sims, n_jobs=n_jobs))
    path = tmp_path / "fleet.trace.json"
    tracer.to_chrome(str(path))
    return fleet, tracer, str(path)


# -- tracer basics ------------------------------------------------------------


def test_disabled_tracer_is_inert():
    t = Tracer(enabled=False)
    with t.span("x", track="a"):
        pass
    t.begin("y")
    t.end("y")
    t.complete("z", ts=0.0, dur=1.0)
    t.instant("w")
    assert t.events == []
    # the disabled span is one shared no-op object, not a fresh allocation
    assert t.span("x") is t.span("y") is NULL_TRACER.span("z")
    assert NULL_TRACER.events == []


def test_span_records_balanced_pair():
    t = Tracer()
    with t.span("outer", track="a", cat="test", k=1):
        with t.span("inner", track="a"):
            pass
    phs = [(e["ph"], e["name"]) for e in t.events]
    assert phs == [("B", "outer"), ("B", "inner"), ("E", "inner"), ("E", "outer")]
    assert t.events[0]["args"] == {"k": 1}
    assert t.events[0]["tid"] == t.events[3]["tid"]


# -- Chrome trace integrity ---------------------------------------------------


def test_chrome_trace_is_strict_json_and_balanced(tmp_path):
    """The exported fleet trace must parse under strict RFC 8259, carry the
    metadata rows Perfetto needs, and keep stack discipline: every begin has
    a matching end on the same track, with proper nesting."""
    fleet, tracer, path = _traced_fleet(tmp_path)
    with open(path) as f:
        doc = _strict_loads(f.read())
    events = doc["traceEvents"]
    assert events, "empty trace from an observed fleet run"

    meta = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    track_names = {
        e["tid"]: e["args"]["name"] for e in meta if e["name"] == "thread_name"
    }
    # one track per lane plus the shared engine track
    assert sum(1 for name in track_names.values() if name.startswith("lane")) == 4
    assert "engine" in track_names.values()

    # B/E balance with per-track stack discipline (E must close the
    # innermost open B of the same name)
    stacks: dict[int, list[str]] = {}
    for e in events:
        if e["ph"] == "B":
            stacks.setdefault(e["tid"], []).append(e["name"])
        elif e["ph"] == "E":
            stack = stacks.get(e["tid"], [])
            assert stack, f"E without open B on tid {e['tid']}"
            assert stack.pop() == e["name"]
    assert all(not s for s in stacks.values()), "unclosed spans at export"

    # every X interval is sane: non-negative dur, ts in microseconds
    for e in events:
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
            assert e["ts"] >= 0.0


def test_chrome_trace_sanitizes_nonfinite(tmp_path):
    t = Tracer()
    t.instant("bad", value=float("inf"), other=float("nan"))
    path = tmp_path / "t.json"
    t.to_chrome(str(path))
    doc = _strict_loads(path.read_text())
    (ev,) = [e for e in doc["traceEvents"] if e.get("name") == "bad"]
    assert ev["args"] == {"value": None, "other": None}


# -- streaming histogram ------------------------------------------------------


def test_histogram_exact_matches_numpy_on_small_n():
    rng = np.random.RandomState(0)
    vals = rng.lognormal(mean=-6.0, sigma=1.5, size=200)
    h = StreamingHistogram()  # exact_n=256 > 200: still exact
    for v in vals:
        h.observe(v)
    assert h.is_exact
    for q in (50.0, 95.0, 99.0):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q), rel=1e-12)


@pytest.mark.parametrize(
    "draw",
    [
        lambda rng: rng.lognormal(mean=-6.0, sigma=1.5, size=4096),
        lambda rng: rng.uniform(1e-5, 1e-1, size=4096),
    ],
    ids=["lognormal", "uniform"],
)
def test_histogram_bucketed_within_one_bucket_width(draw):
    """Past exact_n the histogram answers from log-spaced buckets; the
    estimate must stay within one bucket width (a factor of ``growth``) of
    the true numpy percentile."""
    rng = np.random.RandomState(42)
    vals = draw(rng)
    h = StreamingHistogram()
    for v in vals:
        h.observe(v)
    assert not h.is_exact
    for q in (50.0, 95.0, 99.0):
        got = h.percentile(q)
        want = np.percentile(vals, q)
        ratio = got / want
        assert 1.0 / h.growth <= ratio <= h.growth, (
            f"p{q}: {got:.3e} vs numpy {want:.3e} (ratio {ratio:.3f}, "
            f"bucket width {h.growth:.3f})"
        )


def test_histogram_merge_preserves_accuracy():
    rng = np.random.RandomState(7)
    a_vals = rng.lognormal(mean=-5.0, sigma=1.0, size=3000)
    b_vals = rng.lognormal(mean=-7.0, sigma=1.0, size=3000)
    a, b = StreamingHistogram(), StreamingHistogram()
    for v in a_vals:
        a.observe(v)
    for v in b_vals:
        b.observe(v)
    a.merge(b)
    both = np.concatenate([a_vals, b_vals])
    assert a.count == both.size
    assert a.total == pytest.approx(both.sum())
    assert a.min == pytest.approx(both.min())
    assert a.max == pytest.approx(both.max())
    for q in (50.0, 95.0, 99.0):
        ratio = a.percentile(q) / np.percentile(both, q)
        assert 1.0 / a.growth <= ratio <= a.growth


def test_histogram_zero_samples_and_empty():
    h = StreamingHistogram(exact_n=4)
    assert np.isnan(h.percentile(50.0))
    assert h.snapshot() == {"count": 0}
    for _ in range(10):
        h.observe(0.0)
    assert h.percentile(99.0) == 0.0


def test_metrics_registry_and_null():
    reg = MetricsRegistry()
    reg.inc("events/arrival")
    reg.inc("events/arrival", 2.0)
    reg.gauge("depth", 3.0)
    reg.observe("lat", 0.5)
    snap = reg.snapshot()
    assert snap["counters"] == {"events/arrival": 3.0}
    assert snap["gauges"] == {"depth": 3.0}
    assert snap["histograms"]["lat"]["count"] == 1

    NULL_METRICS.inc("x")
    NULL_METRICS.gauge("y", 1.0)
    NULL_METRICS.observe("z", 1.0)
    assert NULL_METRICS.counters == {}
    assert NULL_METRICS.histograms == {}


# -- barrier-stall conservation ----------------------------------------------


def test_barrier_attribution_conserves_wall_clock(tmp_path):
    """Per lane, own + stall must equal the dispatch wall-clock of the
    rounds the lane was live in; fleet-wide, own-solve time sums to the
    total dispatch time (nothing attributed is invented or lost)."""
    fleet, _, _ = _traced_fleet(tmp_path)
    lat = fleet.telemetry.summary["latency"]
    barrier = lat["barrier"]
    for row in barrier["per_lane"]:
        assert row["own_seconds"] + row["stall_seconds"] == pytest.approx(
            row["wall_seconds"], rel=1e-9, abs=1e-12
        )
        assert 0.0 <= row["stall_fraction"] < 1.0
    assert sum(r["own_seconds"] for r in barrier["per_lane"]) == pytest.approx(
        barrier["dispatch_seconds"], rel=1e-9
    )
    assert barrier["own_solve_seconds"] + barrier["stall_seconds"] == pytest.approx(
        sum(r["wall_seconds"] for r in barrier["per_lane"]), rel=1e-9
    )
    assert 0.0 <= barrier["stall_fraction"] < 1.0
    # solver phase split present and non-negative
    assert all(v >= 0.0 for v in lat["solver_phases"].values())


# -- event spans vs simulation records ----------------------------------------


def test_event_spans_agree_with_sim_records():
    """On a crafted 3-job run, the per-job spans' args must carry exactly
    the submit/schedule/finish times the SimResult records report."""
    net, arrivals = SCENARIOS["edge-mesh"].build(seed=0, n_jobs=3)
    tracer = Tracer()
    metrics = MetricsRegistry()
    sched = OnlineScheduler(
        net, "OTFS", k_paths=2, jrba_iters=60, tracer=tracer, metrics=metrics
    )
    res = sched.run(arrivals)
    by_job = {r.job_id: r for r in res.records}

    sched_spans = [
        e
        for e in tracer.events
        if e["ph"] == "X" and e["name"] == "job/arrival_to_scheduled"
    ]
    scheduled = [r for r in res.records if r.scheduled]
    assert len(sched_spans) == len(scheduled) > 0
    for ev in sched_spans:
        rec = by_job[ev["args"]["job"]]
        assert ev["args"]["submit"] == rec.submit_time
        assert ev["args"]["scheduled"] == rec.schedule_time
        assert ev["dur"] >= 0.0

    finishes = [
        e for e in tracer.events if e["ph"] == "i" and e["name"] == "job/finish"
    ]
    done = [r for r in res.records if r.done]
    assert len(finishes) == len(done) > 0
    for ev in finishes:
        assert ev["args"]["finish"] == by_job[ev["args"]["job"]].finish_time

    # the latency metric saw one sample per scheduled job
    assert metrics.histograms["event_latency_s"].count == len(scheduled)
    # event-kind counters sum to the event total
    kinds = {k: v for k, v in metrics.counters.items() if k.startswith("events/")}
    assert sum(kinds.values()) == res.n_events


def test_observed_run_is_bit_identical_to_unobserved(tmp_path):
    """Instrumentation must never perturb scheduling decisions: the same
    scenario run with tracing+metrics on and off, and under a profiler
    capture (every span then also opens an annotation), yields identical
    records."""
    import jax

    def run(observed):
        net, arrivals = SCENARIOS["edge-mesh-flash"].build(seed=3, n_jobs=6)
        kwargs = (
            {"tracer": Tracer(), "metrics": MetricsRegistry()} if observed else {}
        )
        sched = OnlineScheduler(
            net, "OTFS", k_paths=2, jrba_iters=60, speculate=True, **kwargs
        )
        return sched.run(arrivals)

    a, b = run(False), run(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        c = run(True)
    finally:
        jax.profiler.stop_trace()
    for other in (b, c):
        assert [r.finish_time for r in a.records] == [r.finish_time for r in other.records]
        assert [r.scheduled for r in a.records] == [r.scheduled for r in other.records]
        assert a.n_events == other.n_events
        assert a.n_dispatches == other.n_dispatches


# -- trace_report tool --------------------------------------------------------


def test_trace_report_digests_both_formats(tmp_path):
    # pinned: the "barrier attribution" digest reads the lane/own_solve and
    # lane/barrier_stall spans only the lockstep driver emits
    fleet, tracer, chrome_path = _traced_fleet(tmp_path, mode="lockstep")
    jsonl_path = tmp_path / "fleet.trace.jsonl"
    fleet.telemetry.to_jsonl(str(jsonl_path))

    for path, needle in (
        (chrome_path, "chrome trace:"),
        (str(jsonl_path), "telemetry jsonl:"),
    ):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts", "trace_report.py"), path],
            capture_output=True,
            text=True,
        )
        assert proc.returncode == 0, proc.stderr
        assert needle in proc.stdout
    # the chrome digest must have found balanced spans and the barrier rows
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "trace_report.py"), chrome_path],
        capture_output=True,
        text=True,
    )
    assert "WARNING" not in proc.stdout
    assert "barrier attribution" in proc.stdout
