"""Fleet benchmark: scheduler throughput across the scenario suite, the
batched-vs-sequential JRBA engine comparison, the co-scheduled fleet runtime
vs back-to-back simulation runs, and speculative intra-round OTFS batching
vs sequential per-job solves. Emits ``BENCH_fleet.json``.

  PYTHONPATH=src python -m benchmarks.fleet [--smoke] [--out BENCH_fleet.json]

Sections:

  * ``scenarios`` — for each registry scenario x policy: jobs scheduled per
    second of scheduler wall-clock, and simulator events per second (the
    control-plane capacity numbers the ROADMAP's fleet-scale goal needs).
  * ``batch`` — N independent JRBA instances solved sequentially vs through
    ``JRBAEngine.solve_many``; records the solve-stage and end-to-end
    speedups and the max span deviation (must stay within 1%).
  * ``cosched`` — a fleet of full simulations run through
    ``repro.fleet.FleetRuntime`` (lockstep, solves batched across
    simulations) vs the same simulations run back-to-back on a shared
    engine; records total-wall-clock speedup, mean batch occupancy, and the
    per-simulation span deviation (must stay within 1%).
  * ``round_batch`` — OTFS with speculative intra-round batching
    (``OnlineScheduler(speculate=True)``) vs sequential per-waiting-job
    solves, on the MMPP burst scenarios where queues actually build up;
    records the wall-clock speedup, the solver-dispatch collapse, the
    speculation accept/repair split, and the record deviation (which must be
    exactly zero — speculation must preserve sequential admissions).
  * ``solver`` — the sparse congestion solver vs the dense reference on the
    scheduler's own JRBA program stream: microbench solve-stage speedup at
    the default 400-step budget (asserted >= 3x on the large-L Waxman WAN,
    where the dense formulation pays per-link per-step), early-exit step
    counts, iters/s, and the scheduler-equivalence record deviation (which
    must be exactly zero — the sparse solver must reproduce dense rounding).
  * ``churn`` — the dynamic-network acceptance on ``wan-mesh-churn``
    (capacity drift + link/node failures + MMPP dips): dense and sparse
    engines drive OTFS through identical churn traces; every job must
    finish across failure/recovery cycles, the churn machinery must actually
    fire (re-solves, re-routes, stalls), and the records must match
    bit-for-bit (record deviation exactly zero).
  * ``churn_spec`` — churn-resilient speculation on ``edge-mesh-flash-churn``:
    footprint-scoped invalidation + batched speculate-then-repair churn
    re-solves vs the sequential per-job reference (speculation off, wholesale
    invalidation — the pre-scoping behaviour); records must match
    bit-for-bit, queued-job speculations must survive capacity drift outside
    their footprints, batched re-solves must accept speculative solutions,
    and wide churn steps (>= 4 affected jobs) must collapse dispatches by
    >= 1.5x aggregated across seeds.
  * ``migration`` — the fault-tolerance acceptance on
    ``edge-mesh-node-chaos`` (permanent correlated node blasts, sources on a
    protected tier): the migration-off reference must strand >= 1 job across
    the lane fleet while stall-budget migration finishes every job, and the
    batched speculate-then-repair migration re-solves must match the
    sequential migration reference bit-for-bit (record deviation exactly
    zero); also reports the migrate-or-wait decision split and the
    data-transfer penalty totals.
  * ``latency`` — the observability acceptance: the cosched fleet run with
    tracing + metrics enabled vs disabled (min-of-repeats each side;
    instrumentation must cost < 5% wall-clock), plus the observables
    themselves — per-scenario arrival→scheduled latency p50/p95/p99,
    fleet barrier-stall fraction, and the engine's solver phase breakdown.
    ``--trace out.trace.json`` additionally exports the instrumented run as
    a Chrome trace-event file (load it in https://ui.perfetto.dev).
  * ``fleet_async`` — the async continuous-batching runtime headline: an
    O(1000)-lane mixed-churn fleet under ``AsyncFleetRuntime`` vs the same
    fleet under the lockstep barrier; records must match bit-for-bit
    (deviation exactly zero) while the section reports async events/sec,
    arrival→scheduled p99, dispatcher fire causes and queue-wait
    percentiles, and the recovered-stall fraction.

``--smoke`` shrinks everything to a few events so CI can catch harness bitrot
without measuring timings. All artifacts (telemetry + trace JSONL) derive
from the ``--out`` stem, so CI jobs only name the stem once.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    EventTrace,
    JRBAEngine,
    OnlineScheduler,
    SCENARIOS,
    jrba,
    random_edge_network,
    random_flow_sets,
)
from repro.core.graph import NetworkGraph  # noqa: E402
from repro.fleet import (  # noqa: E402
    FLEET_SCENARIOS,
    AsyncFleetRuntime,
    FleetRuntime,
    build_async_fleet,
    build_chaos_fleet,
    build_scenario_fleet,
)
from repro.obs import Tracer  # noqa: E402
from repro.obs.trace import dumps_strict  # noqa: E402

BATCH_POLICIES = ("OTFS", "OTFA")


def max_record_dev(results_a, results_b) -> float:
    """Worst relative deviation between two runs' job records. Strict: a
    record pair only contributes zero when schedule/finish times are
    *exactly* equal — sign/finiteness mismatches (one side scheduled at t=0
    or never finished while the other wasn't) count as full deviation
    instead of being silently skipped."""
    dev = 0.0
    for a, b in zip(results_a, results_b):
        for ra, rb in zip(a.records, b.records):
            for va, vb in (
                (ra.schedule_time, rb.schedule_time),
                (ra.finish_time, rb.finish_time),
            ):
                if va == vb:
                    continue
                scale = abs(va) if np.isfinite(va) and va != 0 else 1.0
                gap = abs(va - vb)
                dev = max(dev, gap / scale if np.isfinite(gap) else 1.0)
    return dev


class _CapturingEngine(JRBAEngine):
    """Engine that records every (net, flows, capacity) solve request —
    used to extract the scheduler's real JRBA program stream for the solver
    microbenchmark."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.captured: list = []

    def _record(self, net, flows, capacity):
        self.captured.append((net, list(flows), None if capacity is None else capacity.copy()))

    def solve(self, net, flows, *, capacity=None, **kwargs):
        self._record(net, flows, capacity)
        return super().solve(net, flows, capacity=capacity, **kwargs)

    def solve_many(self, net, flow_sets, *, capacities=None, **kwargs):
        nets = [net] * len(flow_sets) if isinstance(net, NetworkGraph) else list(net)
        caps = capacities if capacities is not None else [None] * len(flow_sets)
        for g, fs, c in zip(nets, flow_sets, caps):
            self._record(g, fs, c)
        return super().solve_many(net, flow_sets, capacities=capacities, **kwargs)


def bench_solver(
    *,
    smoke: bool,
    scenarios: tuple[str, ...] = ("edge-mesh", "wan-mesh", "wan-mesh-xl"),
    n_jobs: int = 8,
    seeds: int = 2,
) -> list[dict]:
    """Sparse-vs-dense congestion solver on the scheduler's own program
    stream. Two measurements per scenario:

    * **microbench** — capture every JRBA program an OTFS run solves, then
      replay the stream (warm: compiled buckets, program cache, device
      mirrors) through a dense engine and a sparse engine at the
      module-default budget (n_iters=400, the fixed schedule the dense
      formulation always burns). ``speedup_solve_stage`` is the
      solve-stage-seconds ratio; iters/s and early-exit step counts come
      from the same replay.
    * **scheduler equivalence** — the capture run (dense) vs the same
      scheduler on a sparse engine: job records must be IDENTICAL
      (``max_record_rel_dev == 0`` — the sparse early exit only fires once
      the rounding has provably settled on these workloads).

    On the paper-scale topologies (edge-mesh L=21, wan-mesh L=33) the dense
    einsum is already dispatch-bound on CPU, so the sparse win there comes
    from early exit + single-flow fast paths; the order-of-magnitude shows
    up exactly where the dense formulation pays per-link per-step —
    the large-L Waxman WAN (wan-mesh-xl, ~300 links)."""
    n_iters_sched = 60 if smoke else 200
    n_iters_micro = 60 if smoke else 400
    if smoke:
        n_jobs, seeds = 3, 1
    k = 3
    rows = []
    for scenario in scenarios:
        # -- capture pass (dense) + scheduler-equivalence pass (sparse) ----
        def run_sched(engine):
            out = []
            for seed in range(seeds):
                net, arrivals = SCENARIOS[scenario].build(seed=seed, n_jobs=n_jobs)
                sched = OnlineScheduler(
                    net, "OTFS", k_paths=k, jrba_iters=n_iters_sched, engine=engine
                )
                out.append(sched.run(arrivals))
            return out

        cap_engine = _CapturingEngine(k=k, n_iters=n_iters_sched, solver="dense")
        dense_res = run_sched(cap_engine)
        stream = cap_engine.captured
        sparse_engine = JRBAEngine(k=k, n_iters=n_iters_sched, solver="sparse")
        sparse_res = run_sched(sparse_engine)

        for a, b in zip(dense_res, sparse_res):
            assert a.n_scheduled == b.n_scheduled, (
                f"sparse solver changed admissions on {scenario}"
            )
        max_dev = max_record_dev(dense_res, sparse_res)

        # -- microbench: replay the captured stream at the default budget --
        def replay(mode):
            eng = JRBAEngine(k=k, n_iters=n_iters_micro, solver=mode)
            for net, flows, cap in stream:  # warm compiles + caches + mirrors
                eng.solve(net, flows, capacity=cap)
            s0 = eng.stats.solve_seconds
            steps0 = eng.stats.solver_steps
            for net, flows, cap in stream:
                eng.solve(net, flows, capacity=cap)
            return (
                eng.stats.solve_seconds - s0,
                eng.stats.solver_steps - steps0,
                eng.stats,
            )

        dense_s, dense_steps, _ = replay("dense")
        sparse_s, sparse_steps, sstats = replay("sparse")
        budget = n_iters_micro * (dense_steps // n_iters_micro)  # relax solves
        rows.append(
            {
                "scenario": scenario,
                "n_jobs": n_jobs,
                "seeds": seeds,
                "n_programs": len(stream),
                "n_iters_micro": n_iters_micro,
                "n_iters_sched": n_iters_sched,
                "max_record_rel_dev": max_dev,
                "dense_solve_seconds": dense_s,
                "sparse_solve_seconds": sparse_s,
                "speedup_solve_stage": dense_s / sparse_s if sparse_s else None,
                "dense_iters_per_s": dense_steps / dense_s if dense_s else None,
                "sparse_iters_per_s": sparse_steps / sparse_s if sparse_s else None,
                "sparse_steps": sparse_steps,
                "step_budget": budget,
                "early_exit_step_frac": sparse_steps / budget if budget else None,
                "fast_path_solves": sstats.fast_path_solves // 2,  # per pass
            }
        )
        print(
            f"solver[{scenario}] dev={max_dev:.1e} "
            f"solve-stage {dense_s * 1e3:.0f}ms->{sparse_s * 1e3:.0f}ms "
            f"({rows[-1]['speedup_solve_stage']:.2f}x) "
            f"steps {sparse_steps}/{budget} "
            f"fast={rows[-1]['fast_path_solves']}"
        )
    return rows


def bench_scenarios(*, smoke: bool, n_jobs: int, seeds: int) -> list[dict]:
    rows = []
    for name, sc in sorted(SCENARIOS.items()):
        for policy in BATCH_POLICIES:
            engine = JRBAEngine(k=3, n_iters=60 if smoke else 200)
            scheduled = events = 0
            overhead = wall = 0.0
            for seed in range(seeds):
                net, arrivals = sc.build(seed=seed, n_jobs=n_jobs)
                sched = OnlineScheduler(
                    net, policy, k_paths=3, jrba_iters=engine.n_iters, engine=engine
                )
                t0 = time.perf_counter()
                res = sched.run(arrivals)
                wall += time.perf_counter() - t0
                scheduled += res.n_scheduled
                events += res.n_events
                overhead += res.sched_overhead
            rows.append(
                {
                    "scenario": name,
                    "policy": policy,
                    "jobs": n_jobs * seeds,
                    "jobs_scheduled": scheduled,
                    "events": events,
                    "sched_seconds": overhead,
                    "wall_seconds": wall,
                    "sched_jobs_per_s": scheduled / overhead if overhead else None,
                    "events_per_s": events / wall if wall else None,
                    "engine": engine.stats.as_dict(),
                }
            )
            print(
                f"{name:16s} {policy:5s} sched={scheduled:3d} events={events:4d} "
                f"sched_jobs/s={rows[-1]['sched_jobs_per_s']:.1f} "
                f"events/s={rows[-1]['events_per_s']:.1f}"
            )
    return rows


def _random_instances(n_instances: int, n_flows: int, seed: int = 0):
    net = random_edge_network(12, mean_bandwidth=5.0, rng=np.random.RandomState(seed))
    return net, random_flow_sets(net, n_instances, n_flows, seed=1000)


def bench_batch(*, smoke: bool, n_instances: int = 32, n_flows: int = 6) -> dict:
    """The acceptance measurement: batch vs sequential on one shape bucket."""
    n_iters = 60 if smoke else 300
    k = 3
    net, sets = _random_instances(n_instances, n_flows)
    # dense-pinned: this section isolates the PR-1 batching win against the
    # stable dense solve cost (the sparse-vs-dense comparison lives in the
    # `solver` section)
    engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")

    seq = [jrba(net, fs, k=k, n_iters=n_iters, solver="dense") for fs in sets]  # also warms jit
    bat = engine.solve_many(net, sets)  # warms the batched bucket
    max_dev = max(
        abs(a.span - b.span) / max(a.span, 1e-12) for a, b in zip(seq, bat)
    )

    t0 = time.perf_counter()
    for fs in sets:
        jrba(net, fs, k=k, n_iters=n_iters, solver="dense")
    t_seq = time.perf_counter() - t0

    solver_before = engine.stats.solve_seconds
    t0 = time.perf_counter()
    engine.solve_many(net, sets)
    t_bat = time.perf_counter() - t0
    t_bat_solve = engine.stats.solve_seconds - solver_before

    # sequential solve-stage time through the engine's own single path, so
    # both sides share program construction + path caching
    seq_engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")
    for fs in sets:
        seq_engine.solve(net, fs)  # warm
    solver_before = seq_engine.stats.solve_seconds
    for fs in sets:
        seq_engine.solve(net, fs)
    t_seq_solve = seq_engine.stats.solve_seconds - solver_before

    out = {
        "n_instances": n_instances,
        "n_flows": n_flows,
        "n_iters": n_iters,
        "max_span_rel_dev": max_dev,
        "seq_seconds": t_seq,
        "batch_seconds": t_bat,
        "speedup_end_to_end": t_seq / t_bat if t_bat else None,
        "seq_solve_seconds": t_seq_solve,
        "batch_solve_seconds": t_bat_solve,
        "speedup_solve_stage": t_seq_solve / t_bat_solve if t_bat_solve else None,
        "engine": engine.stats.as_dict(),
    }
    print(
        f"batch[{n_instances}x{n_flows} flows] dev={max_dev:.2e} "
        f"solve {t_seq_solve * 1e3:.1f}ms->{t_bat_solve * 1e3:.1f}ms "
        f"({out['speedup_solve_stage']:.1f}x) "
        f"end-to-end {t_seq * 1e3:.1f}ms->{t_bat * 1e3:.1f}ms "
        f"({out['speedup_end_to_end']:.1f}x)"
    )
    return out


def bench_cosched(
    *, smoke: bool, n_sims: int = 16, n_jobs: int = 4, trace_path: str | None = None
) -> dict:
    """Co-scheduled fleet vs the same simulations back-to-back. Both sides
    share one engine per pass (the PR-1 status quo already shares caches);
    the delta is purely lockstep cross-simulation solve batching."""
    names = FLEET_SCENARIOS
    if smoke:
        # two families x two lanes: still exercises cross-sim batching
        # (occupancy > 1) with a handful of events
        n_sims, n_jobs, names = 4, 2, FLEET_SCENARIOS[:2]
    n_iters = 60 if smoke else 250
    k = 3

    # dense-pinned like `batch`/`round_batch`: isolates the PR-2 lockstep
    # co-scheduling win against the stable dense solve cost
    seq_engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")
    if not smoke:  # warm the compile caches so timings compare steady state
        for s in build_scenario_fleet(seq_engine, n_sims, n_jobs=n_jobs, names=names):
            s.scheduler.run(s.arrivals)
    t0 = time.perf_counter()
    solo = [
        s.scheduler.run(s.arrivals)
        for s in build_scenario_fleet(seq_engine, n_sims, n_jobs=n_jobs, names=names)
    ]
    t_seq = time.perf_counter() - t0

    fleet_engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")
    # pinned lockstep: this section measures the PR-2 barrier-round batching
    # win specifically (the async driver is benchmarked by `fleet_async`)
    runtime = FleetRuntime(fleet_engine, mode="lockstep")
    if not smoke:
        runtime.run(build_scenario_fleet(fleet_engine, n_sims, n_jobs=n_jobs, names=names))
    fleet = runtime.run(
        build_scenario_fleet(fleet_engine, n_sims, n_jobs=n_jobs, names=names)
    )
    t_cos = fleet.wall_seconds
    if trace_path:
        fleet.telemetry.to_jsonl(trace_path)

    devs = []
    for a, b in zip(solo, fleet.results):
        assert a.n_scheduled == b.n_scheduled, "fleet diverged from solo schedules"
        if np.isfinite(a.avg_scheduled_span):
            devs.append(
                abs(a.avg_scheduled_span - b.avg_scheduled_span) / a.avg_scheduled_span
            )
    out = {
        "n_sims": n_sims,
        "n_jobs": n_jobs,
        "n_iters": n_iters,
        "scenarios": sorted(set(names[: max(n_sims, 1)])),
        "max_span_rel_dev": max(devs) if devs else 0.0,
        "seq_seconds": t_seq,
        "cosched_seconds": t_cos,
        "speedup_wall_clock": t_seq / t_cos if t_cos else None,
        "mean_batch_occupancy": fleet.telemetry.mean_batch_occupancy,
        "cache_hit_rate": fleet.telemetry.cache_hit_rate,
        "events_per_s": fleet.telemetry.summary.get("events_per_s"),
        "dispatch_rounds": len(fleet.telemetry.rounds),
        "engine": fleet_engine.stats.as_dict(),
    }
    print(
        f"cosched[{n_sims} sims x {n_jobs} jobs] dev={out['max_span_rel_dev']:.2e} "
        f"occupancy={out['mean_batch_occupancy']:.2f} "
        f"wall {t_seq * 1e3:.0f}ms->{t_cos * 1e3:.0f}ms "
        f"({out['speedup_wall_clock']:.2f}x)"
    )
    return out


def bench_round_batch(
    *,
    smoke: bool,
    scenarios: tuple[str, ...] = ("edge-mesh-burst", "edge-mesh-flash"),
    n_jobs: int = 24,
    n_seeds: int = 2,
    repeats: int = 2,
) -> list[dict]:
    """Speculative intra-round OTFS batching vs sequential per-job solves.

    Both sides share one engine per pass (warm compile caches, warm path
    caches); the delta is purely the stepper's round batching + repair. The
    records must match EXACTLY — speculation is only accepted when the solve
    is bitwise the sequential one — so the deviation reported here is a
    correctness tripwire, not a tolerance."""
    n_iters = 60 if smoke else 250
    k = 3
    if smoke:
        n_jobs, n_seeds, repeats = 6, 1, 1

    rows = []
    for scenario in scenarios:
        def run_side(speculate: bool):
            # pinned to the dense solver: this section measures the PR-3
            # speculation feature in isolation, against the stable dense
            # solve cost — the sparse solver shrinks per-solve time and with
            # it the relative win, which belongs to the `solver` section
            # (speculation-vs-sequential equivalence under the sparse
            # default is asserted by tests/test_speculation.py, including
            # the Pallas interpret path in CI)
            engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")

            def one_pass():
                out = []
                for seed in range(n_seeds):
                    net, arrivals = SCENARIOS[scenario].build(seed=seed, n_jobs=n_jobs)
                    sched = OnlineScheduler(
                        net,
                        "OTFS",
                        k_paths=k,
                        jrba_iters=n_iters,
                        engine=engine,
                        speculate=speculate,
                    )
                    out.append(sched.run(arrivals))
                return out

            if not smoke:  # warm the compile + path caches
                one_pass()
            best, results = float("inf"), None
            for _ in range(repeats):
                t0 = time.perf_counter()
                results = one_pass()
                best = min(best, time.perf_counter() - t0)
            return best, results

        t_seq, seq = run_side(False)
        t_spec, spec = run_side(True)

        for a, b in zip(seq, spec):
            assert a.n_scheduled == b.n_scheduled, "speculation changed admissions"
        max_dev = max_record_dev(seq, spec)

        seq_disp = sum(r.n_dispatches for r in seq)
        spec_disp = sum(r.n_dispatches for r in spec)
        accepted = sum(r.spec_accepted for r in spec)
        repaired = sum(r.spec_repaired for r in spec)
        rows.append(
            {
                "scenario": scenario,
                "n_jobs": n_jobs,
                "n_seeds": n_seeds,
                "n_iters": n_iters,
                "max_record_rel_dev": max_dev,
                "seq_seconds": t_seq,
                "spec_seconds": t_spec,
                "speedup_wall_clock": t_seq / t_spec if t_spec else None,
                "seq_dispatches": seq_disp,
                "spec_dispatches": spec_disp,
                "dispatch_collapse": seq_disp / spec_disp if spec_disp else None,
                "seq_solves": sum(r.n_solves for r in seq),
                "spec_solves": sum(r.n_solves for r in spec),
                "spec_accepted": accepted,
                "spec_repaired": repaired,
                "spec_accept_rate": (
                    accepted / (accepted + repaired) if accepted + repaired else None
                ),
            }
        )
        print(
            f"round_batch[{scenario} {n_jobs}x{n_seeds} jobs] dev={max_dev:.2e} "
            f"disp {seq_disp}->{spec_disp} "
            f"({rows[-1]['dispatch_collapse']:.2f}x collapse) "
            f"wall {t_seq * 1e3:.0f}ms->{t_spec * 1e3:.0f}ms "
            f"({rows[-1]['speedup_wall_clock']:.2f}x) "
            f"accept {accepted}/{accepted + repaired}"
        )
    return rows


def bench_churn(
    *,
    smoke: bool,
    scenario: str = "wan-mesh-churn",
    n_jobs: int = 10,
    seeds: int = 2,
) -> dict:
    """Dynamic-network acceptance: OTFS under churn, dense vs sparse.

    Both engines replay the identical (topology, arrivals, churn trace)
    tuple per seed; the trace heals the network by construction, so every
    job must eventually finish, and the two formulations must produce
    bit-identical scheduler records (the start-portfolio rounding makes this
    hold even on the degenerate symmetric programs churn re-solves create)."""
    n_iters = 60 if smoke else 150
    if smoke:
        n_jobs, seeds = 4, 1
    k = 3
    sc = SCENARIOS[scenario]

    def run_side(solver: str):
        engine = JRBAEngine(k=k, n_iters=n_iters, solver=solver)
        out, churn_len = [], 0
        t0 = time.perf_counter()
        for seed in range(seeds):
            net, arrivals, churn = sc.build_churn(seed=seed, n_jobs=n_jobs)
            churn_len += len(churn)
            sched = OnlineScheduler(
                net, "OTFS", k_paths=k, jrba_iters=n_iters, engine=engine
            )
            out.append(sched.run(EventTrace(arrivals, churn=churn)))
        return out, time.perf_counter() - t0, churn_len

    dense_res, t_dense, n_steps = run_side("dense")
    sparse_res, t_sparse, _ = run_side("sparse")

    for a, b in zip(dense_res, sparse_res):
        assert a.n_scheduled == b.n_scheduled, "sparse changed admissions under churn"
    unfinished = sum(r.unfinished for r in dense_res) + sum(
        r.unfinished for r in sparse_res
    )
    assert unfinished == 0, f"{unfinished} jobs never finished across churn cycles"
    max_dev = max_record_dev(dense_res, sparse_res)

    def agg(results, field):
        return sum(getattr(r, field) for r in results)

    assert agg(dense_res, "churn_events") == agg(sparse_res, "churn_events")
    out = {
        "scenario": scenario,
        "n_jobs": n_jobs,
        "seeds": seeds,
        "n_iters": n_iters,
        "trace_steps": n_steps,
        "max_record_rel_dev": max_dev,
        "unfinished": unfinished,
        "churn_events": agg(dense_res, "churn_events"),
        "churn_resolves": agg(dense_res, "churn_resolves"),
        "churn_reroutes": agg(dense_res, "churn_reroutes"),
        "churn_stalls": agg(dense_res, "churn_stalls"),
        "dense_seconds": t_dense,
        "sparse_seconds": t_sparse,
    }
    print(
        f"churn[{scenario} {n_jobs}x{seeds} jobs] dev={max_dev:.2e} "
        f"events={out['churn_events']} resolves={out['churn_resolves']} "
        f"reroutes={out['churn_reroutes']} stalls={out['churn_stalls']} "
        f"unfinished={unfinished}"
    )
    return out


def bench_churn_spec(
    *,
    smoke: bool,
    scenario: str = "edge-mesh-flash-churn",
    n_jobs: int = 20,
    seeds: int = 2,
) -> dict:
    """Churn-resilient speculation: footprint-scoped invalidation + batched
    churn re-solves vs the sequential per-job reference.

    The reference side runs with ``speculate=False, scoped_churn=False`` —
    the pre-scoping behaviour (every churn step drops all speculative state
    wholesale and re-solves affected jobs one dispatch at a time). The
    speculative side keeps queued-job speculations alive across churn steps
    that miss their footprints and routes wide churn steps through one
    speculate-then-repair dispatch. Both sides must produce bit-identical
    records — the batched path commits in admission order and only accepts a
    speculative entry when the live residual still clamp-equals its input
    snapshot on the solution's candidate links, so acceptance is exactness,
    not a tolerance.

    Deliberately low solver budget (n_iters=40, k=2): churn re-solves are
    latency-critical singles where dispatch overhead dominates, which is the
    regime the batching targets; record identity is budget-independent. The
    dispatch-collapse floor aggregates ``churn_wide_jobs`` /
    ``churn_wide_dispatches`` across seeds — individual seeds can land a
    conflict-heavy trace and dip below the floor while the aggregate holds."""
    n_iters = 40
    k = 2
    if smoke:
        n_jobs, seeds = 8, 1
    sc = SCENARIOS[scenario]

    def run_side(*, speculate: bool, scoped: bool):
        engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")
        out = []
        t0 = time.perf_counter()
        for seed in range(seeds):
            net, arrivals, churn = sc.build_churn(seed=seed, n_jobs=n_jobs)
            sched = OnlineScheduler(
                net,
                "OTFS",
                k_paths=k,
                jrba_iters=n_iters,
                engine=engine,
                speculate=speculate,
                scoped_churn=scoped,
            )
            out.append(sched.run(EventTrace(arrivals, churn=churn)))
        return out, time.perf_counter() - t0

    seq_res, t_seq = run_side(speculate=False, scoped=False)
    spec_res, t_spec = run_side(speculate=True, scoped=True)

    for a, b in zip(seq_res, spec_res):
        assert a.n_scheduled == b.n_scheduled, (
            "scoped speculation changed admissions under churn"
        )
    max_dev = max_record_dev(seq_res, spec_res)

    def agg(results, field):
        return sum(getattr(r, field) for r in results)

    wide_jobs = agg(spec_res, "churn_wide_jobs")
    wide_disp = agg(spec_res, "churn_wide_dispatches")
    accepted = agg(spec_res, "churn_spec_accepted")
    repaired = agg(spec_res, "churn_spec_repaired")
    out = {
        "scenario": scenario,
        "n_jobs": n_jobs,
        "seeds": seeds,
        "n_iters": n_iters,
        "max_record_rel_dev": max_dev,
        "churn_events": agg(spec_res, "churn_events"),
        "churn_resolves": agg(spec_res, "churn_resolves"),
        "seq_dispatches": agg(seq_res, "n_dispatches"),
        "spec_dispatches": agg(spec_res, "n_dispatches"),
        "spec_survived": agg(spec_res, "churn_spec_survived"),
        "spec_dropped": agg(spec_res, "churn_spec_dropped"),
        "spec_accepted": accepted,
        "spec_repaired": repaired,
        "spec_accept_rate": (
            accepted / (accepted + repaired) if accepted + repaired else None
        ),
        "wide_jobs": wide_jobs,
        "wide_dispatches": wide_disp,
        "dispatch_collapse": wide_jobs / wide_disp if wide_disp else None,
        "seq_seconds": t_seq,
        "spec_seconds": t_spec,
    }
    print(
        f"churn_spec[{scenario} {n_jobs}x{seeds} jobs] dev={max_dev:.2e} "
        f"survived={out['spec_survived']} dropped={out['spec_dropped']} "
        f"accept {accepted}/{accepted + repaired} "
        f"disp {out['seq_dispatches']}->{out['spec_dispatches']} "
        f"wide {wide_jobs}/{wide_disp} "
        f"({out['dispatch_collapse'] or 0:.2f}x collapse)"
    )
    return out


def bench_migration(
    *,
    smoke: bool,
    scenario: str = "edge-mesh-node-chaos",
    n_lanes: int = 10,
    n_jobs: int = 4,
    stall_budget: float = 1.0,
) -> dict:
    """Fault-tolerance acceptance: stall-budget migration under permanent
    correlated node failures.

    Three sides over the same chaos lane fleet (lane i = scenario seed i):
    the migration-off reference (``stall_budget=None`` — a job whose
    placement a blast kills stalls forever, so permanent traces strand it),
    stall-budget migration with batched speculate-then-repair re-solves, and
    the sequential migration reference (``speculate=False`` — one dispatch
    per candidate). The off side must strand >= 1 job across the fleet (the
    trace is genuinely lethal), both migration sides must finish every job
    (the liveness claim), and the batched side must reproduce the sequential
    records bit-for-bit — speculative migration entries are only accepted on
    exact memory-state + clamp-equal residual matches, so acceptance is
    exactness, not a tolerance. No timing ratios: migration is a rare-event
    robustness path, not a throughput path."""
    if smoke:
        n_lanes = 5  # seeds 0-4: seed 3 checks-and-backs-off, seed 4 migrates
    engine = JRBAEngine(k=4, n_iters=60)
    runtime = FleetRuntime(engine, mode="lockstep")

    def run_side(*, budget, speculate=True):
        t0 = time.perf_counter()
        res = runtime.run(
            build_chaos_fleet(
                engine,
                n_lanes,
                n_jobs=n_jobs,
                name=scenario,
                stall_budget=budget,
                speculate=speculate,
            )
        )
        return res, time.perf_counter() - t0

    off, t_off = run_side(budget=None)
    seq, t_seq = run_side(budget=stall_budget, speculate=False)
    spec, t_spec = run_side(budget=stall_budget, speculate=True)
    max_dev = max_record_dev(seq.results, spec.results)

    def agg(results, field):
        return sum(getattr(r, field) for r in results)

    checks = agg(spec.results, "migration_checks")
    migrations = agg(spec.results, "migrations")
    accepted = agg(spec.results, "migration_spec_accepted")
    repaired = agg(spec.results, "migration_spec_repaired")
    out = {
        "scenario": scenario,
        "n_lanes": n_lanes,
        "n_jobs": n_jobs,
        "stall_budget": stall_budget,
        "stranded_without_migration": int(off.unfinished),
        "unfinished_with_migration": int(spec.unfinished),
        "unfinished_sequential": int(seq.unfinished),
        "max_record_rel_dev": max_dev,
        "checks": checks,
        "migrations": migrations,
        "rejected": agg(spec.results, "migration_rejected"),
        "infeasible": agg(spec.results, "migration_infeasible"),
        "moved_tasks": agg(spec.results, "migration_moved_tasks"),
        "penalty_seconds": float(agg(spec.results, "migration_penalty_seconds")),
        "commit_rate": migrations / checks if checks else None,
        "spec_accepted": accepted,
        "spec_repaired": repaired,
        "spec_accept_rate": (
            accepted / (accepted + repaired) if accepted + repaired else None
        ),
        "off_seconds": t_off,
        "seq_seconds": t_seq,
        "spec_seconds": t_spec,
    }
    print(
        f"migration[{scenario} {n_lanes}x{n_jobs} jobs] dev={max_dev:.2e} "
        f"stranded(off)={out['stranded_without_migration']} "
        f"unfinished(on)={out['unfinished_with_migration']} "
        f"migrations {migrations}/{checks} checks "
        f"(rej {out['rejected']}, infeas {out['infeasible']}) "
        f"penalty {out['penalty_seconds']:.3f}s"
    )
    return out


def bench_latency(
    *,
    smoke: bool,
    trace_path: str | None = None,
    n_sims: int = 16,
    n_jobs: int = 4,
    repeats: int = 3,
) -> dict:
    """Observability acceptance: the cosched fleet with tracing + metrics
    enabled vs disabled, same engine warm-up discipline on both sides
    (min-of-``repeats`` to tame host noise). The <5% overhead bar is the
    point of the null-object design — instrumentation lives permanently in
    the event loop, gated by one attribute load + branch.

    The instrumented run also supplies the observables the report surfaces:
    per-scenario arrival→scheduled latency percentiles (streaming
    histograms, merged per scenario), the barrier-stall fraction the
    lockstep runtime attributes per lane, and the engine's phase breakdown.
    ``trace_path`` exports that run as a Chrome trace-event file."""
    names = FLEET_SCENARIOS
    if smoke:
        n_sims, n_jobs, names, repeats = 4, 2, FLEET_SCENARIOS[:2], 1
    n_iters = 60 if smoke else 250
    k = 3

    def run_fleet(engine, *, tracer=None, observe=False):
        # pinned lockstep: the stall_fraction readout below asserts the
        # barrier-specific attribution (async queue wait is a different
        # quantity, reported by `fleet_async`)
        runtime = FleetRuntime(engine, tracer=tracer, observe=observe, mode="lockstep")
        return runtime.run(
            build_scenario_fleet(engine, n_sims, n_jobs=n_jobs, names=names)
        )

    off_engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")
    run_fleet(off_engine)  # warm compiles + caches
    t_off = float("inf")
    for _ in range(repeats):
        t_off = min(t_off, run_fleet(off_engine).wall_seconds)

    on_engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")
    run_fleet(on_engine, tracer=Tracer())  # warm (instrumented path)
    t_on, fleet_on, tracer_on = float("inf"), None, None
    for _ in range(repeats):
        tracer = Tracer()
        fleet = run_fleet(on_engine, tracer=tracer)
        if fleet.wall_seconds < t_on:
            t_on, fleet_on, tracer_on = fleet.wall_seconds, fleet, tracer

    if trace_path:
        tracer_on.to_chrome(trace_path)
    lat = fleet_on.telemetry.summary["latency"]
    barrier = {key: v for key, v in lat["barrier"].items() if key != "per_lane"}
    out = {
        "n_sims": n_sims,
        "n_jobs": n_jobs,
        "n_iters": n_iters,
        "repeats": repeats,
        "off_seconds": t_off,
        "on_seconds": t_on,
        "overhead_frac": t_on / t_off - 1.0 if t_off else None,
        "event_latency": lat["events"],
        "barrier": barrier,
        "stall_fraction": barrier["stall_fraction"],
        "solver_phases": lat["solver_phases"],
        "trace_events": len(tracer_on.events),
        "trace_path": trace_path,
    }
    p = lat["events"]["overall"]
    print(
        f"latency[{n_sims} sims x {n_jobs} jobs] "
        f"wall off {t_off * 1e3:.0f}ms on {t_on * 1e3:.0f}ms "
        f"(overhead {out['overhead_frac'] * 100:+.1f}%) "
        f"event p50/p95/p99 {p.get('p50', 0) * 1e3:.1f}/"
        f"{p.get('p95', 0) * 1e3:.1f}/{p.get('p99', 0) * 1e3:.1f}ms "
        f"stall={out['stall_fraction']:.2f}"
    )
    return out


def bench_fleet_async(
    *,
    smoke: bool,
    n_lanes: int = 1000,
    n_jobs: int = 2,
    trace_path: str | None = None,
) -> dict:
    """The async-runtime headline: an O(1000)-lane mixed-churn fleet (every
    4th lane carries a capacity-drift trace) under the continuous-batching
    dispatcher vs the same fleet under the lockstep barrier. The contract is
    bit-identical per-lane records — ``max_record_rel_dev`` must be exactly
    0.0, no tolerance — while the dispatcher swaps the barrier stall for
    bounded queue wait. Headline metrics: async events/sec, per-job
    arrival→scheduled p99, and the fraction of lockstep stall the async
    driver recovered (negative at small scale, where the barrier is cheap
    and queue bookkeeping isn't amortized — the dispatcher is built for the
    1000-lane regime this section times)."""
    if smoke:
        n_lanes = 24
    n_iters = 40
    k = 2
    batch_target, deadline_s = 32, 0.002

    def build(engine):
        return build_async_fleet(engine, n_lanes, n_jobs=n_jobs, churn_every=4)

    # dense-pinned like `cosched`/`batch`: exact (Nf, K, L) bucket keys make
    # dispatch occupancy directly interpretable (the sparse solver re-buckets
    # on compressed shapes inside each dispatch; its record equivalence is
    # covered by tests/test_fleet_async.py)
    lock_engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")
    lock_rt = FleetRuntime(lock_engine, mode="lockstep")
    if not smoke:  # warm compiles + caches so the timed passes compare steady state
        lock_rt.run(build(lock_engine))
    lock = lock_rt.run(build(lock_engine))

    async_engine = JRBAEngine(k=k, n_iters=n_iters, solver="dense")
    async_rt = AsyncFleetRuntime(
        async_engine, observe=True, batch_target=batch_target, deadline_s=deadline_s
    )
    if not smoke:
        async_rt.run(build(async_engine))
    asyn = async_rt.run(build(async_engine))
    if trace_path:
        asyn.telemetry.to_jsonl(trace_path)

    lock_bar = lock.telemetry.summary["latency"]["barrier"]
    async_bar = asyn.telemetry.summary["latency"]["barrier"]
    queue = asyn.telemetry.summary["latency"]["queue"]
    events = asyn.telemetry.summary["latency"]["events"]["overall"]
    out = {
        "n_lanes": n_lanes,
        "n_jobs": n_jobs,
        "n_iters": n_iters,
        "batch_target": batch_target,
        "deadline_s": deadline_s,
        "max_record_rel_dev": max_record_dev(lock.results, asyn.results),
        "events": asyn.total_events,
        "unfinished": asyn.unfinished,
        "events_per_s": asyn.total_events / asyn.wall_seconds,
        "lockstep_events_per_s": lock.total_events / lock.wall_seconds,
        "speedup_wall_clock": lock.wall_seconds / asyn.wall_seconds,
        "event_latency_p50": events.get("p50"),
        "event_latency_p99": events.get("p99"),
        "async_stall_seconds": async_bar["stall_seconds"],
        "async_stall_fraction": async_bar["stall_fraction"],
        "lockstep_stall_seconds": lock_bar["stall_seconds"],
        "lockstep_stall_fraction": lock_bar["stall_fraction"],
        "recovered_stall_frac": (
            1.0 - async_bar["stall_seconds"] / lock_bar["stall_seconds"]
            if lock_bar["stall_seconds"]
            else None
        ),
        "mean_batch_occupancy": asyn.telemetry.mean_batch_occupancy,
        "dispatches": queue["dispatches"],
        "fired_by": queue["fired_by"],
        "queue_wait": queue["wait"],
        "trace_path": trace_path,
    }
    print(
        f"fleet_async[{n_lanes} lanes x {n_jobs} jobs] "
        f"dev={out['max_record_rel_dev']:.2e} "
        f"{out['events_per_s']:.0f} ev/s (lockstep {out['lockstep_events_per_s']:.0f}, "
        f"{out['speedup_wall_clock']:.2f}x) "
        f"p99={(out['event_latency_p99'] or 0) * 1e3:.1f}ms "
        f"occupancy={out['mean_batch_occupancy']:.2f} "
        f"stall {out['lockstep_stall_fraction']:.2f}->{out['async_stall_fraction']:.2f}"
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny run, no timing claims")
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument(
        "--trace",
        default=None,
        metavar="OUT.trace.json",
        help="export the instrumented latency-bench fleet run as a Chrome "
        "trace-event file (loadable in Perfetto / chrome://tracing)",
    )
    args = ap.parse_args()
    enable_compile_cache()

    # every artifact derives from the --out stem (CI names them the same way)
    stem = os.path.splitext(args.out)[0]
    trace_path = stem + "_trace.jsonl"
    async_trace_path = stem + "_async_trace.jsonl"
    n_jobs, seeds = (3, 1) if args.smoke else (8, 2)
    report = {
        "smoke": args.smoke,
        "scenarios": bench_scenarios(smoke=args.smoke, n_jobs=n_jobs, seeds=seeds),
        "batch": bench_batch(
            smoke=args.smoke, n_instances=8 if args.smoke else 32
        ),
        "cosched": bench_cosched(smoke=args.smoke, trace_path=trace_path),
        "round_batch": bench_round_batch(smoke=args.smoke),
        "solver": bench_solver(smoke=args.smoke),
        "churn": bench_churn(smoke=args.smoke),
        "churn_spec": bench_churn_spec(smoke=args.smoke),
        "migration": bench_migration(smoke=args.smoke),
        "latency": bench_latency(smoke=args.smoke, trace_path=args.trace),
        "fleet_async": bench_fleet_async(
            smoke=args.smoke, trace_path=async_trace_path
        ),
    }
    with open(args.out, "w") as f:
        f.write(dumps_strict(report, indent=2))
    print(f"wrote {args.out} (+ {trace_path}, {async_trace_path})")
    if not args.smoke:
        dev = report["batch"]["max_span_rel_dev"]
        speedup = report["batch"]["speedup_solve_stage"]
        assert dev <= 0.01, f"batched span deviates {dev:.3%} from sequential"
        # floor recalibrated from 5x (PR 1): the per-program device-tensor
        # memoization of PR 4 sped the *sequential* baseline up ~20%, so the
        # relative batching win shrank while both absolute times improved
        assert speedup >= 4.0, f"batch solve speedup {speedup:.1f}x < 4x"
        cos = report["cosched"]
        assert cos["max_span_rel_dev"] <= 0.01, (
            f"co-scheduled spans deviate {cos['max_span_rel_dev']:.3%} from solo runs"
        )
        assert cos["mean_batch_occupancy"] > 1.0, (
            f"no cross-simulation batching (occupancy {cos['mean_batch_occupancy']:.2f})"
        )
        assert cos["speedup_wall_clock"] > 1.0, (
            f"co-scheduling slower than sequential ({cos['speedup_wall_clock']:.2f}x)"
        )
        for row in report["round_batch"]:
            assert row["max_record_rel_dev"] == 0.0, (
                f"speculative OTFS deviated from sequential records on "
                f"{row['scenario']} ({row['max_record_rel_dev']:.3e})"
            )
            assert row["dispatch_collapse"] > 1.0, (
                f"no dispatch collapse on {row['scenario']} "
                f"({row['dispatch_collapse']:.2f}x)"
            )
        flash = next(
            r for r in report["round_batch"] if r["scenario"] == "edge-mesh-flash"
        )
        # floor recalibrated from 1.15x (PR 5): the capacity-epoch
        # avg-bandwidth value memo (PR 6) cut BOTH sides' host-side
        # allocation cost ~35%, and what remains is dominated by solver
        # dispatch whose cost the sequential side pays per solve and the
        # speculative side per batch — on dispatch-bound hosts the ratio
        # hovers within a few % of parity (the pre-PR-6 tree measures ~1.04x
        # on the same host). The structural win — >2x dispatch collapse with
        # zero record deviation — is asserted above, and the wall-clock
        # ratio stays tracked by the check_bench regression gate; here we
        # only floor "not materially slower"
        assert flash["speedup_wall_clock"] >= 0.95, (
            f"speculative round batching {flash['speedup_wall_clock']:.2f}x < 0.95x "
            "over sequential OTFS on the MMPP flash-crowd scenario"
        )
        for row in report["solver"]:
            assert row["max_record_rel_dev"] == 0.0, (
                f"sparse solver deviated from dense scheduler records on "
                f"{row['scenario']} ({row['max_record_rel_dev']:.3e})"
            )
        # the >= 3x acceptance floor binds where the dense formulation pays
        # per-link per-step (the large-L WAN); on the small paper-scale
        # topologies the solver is dispatch-bound on CPU, so its ~1-2x ratio
        # swings with host load and is tracked by the regression gate rather
        # than floor-asserted here
        xl = next(r for r in report["solver"] if r["scenario"] == "wan-mesh-xl")
        assert xl["speedup_solve_stage"] >= 3.0, (
            f"sparse solve-stage speedup {xl['speedup_solve_stage']:.2f}x < 3x "
            "on the large-L Waxman WAN"
        )
        churn = report["churn"]
        assert churn["max_record_rel_dev"] == 0.0, (
            f"dense and sparse scheduler records diverged under churn "
            f"({churn['max_record_rel_dev']:.3e})"
        )
        for counter in ("churn_events", "churn_resolves", "churn_reroutes"):
            assert churn[counter] > 0, f"churn bench never exercised {counter}"
        cspec = report["churn_spec"]
        assert cspec["max_record_rel_dev"] == 0.0, (
            f"batched churn re-solves deviated from sequential records "
            f"({cspec['max_record_rel_dev']:.3e})"
        )
        assert cspec["spec_survived"] > 0, (
            "no queued-job speculation survived a churn step (footprint "
            "scoping never paid off)"
        )
        assert cspec["spec_accept_rate"] and cspec["spec_accept_rate"] > 0.0, (
            "batched churn re-solves never accepted a speculative solution"
        )
        assert cspec["dispatch_collapse"] and cspec["dispatch_collapse"] >= 1.5, (
            f"wide churn steps collapsed dispatches only "
            f"{cspec['dispatch_collapse'] or 0:.2f}x < 1.5x"
        )
        mig = report["migration"]
        assert mig["stranded_without_migration"] >= 1, (
            "chaos trace stranded no jobs with migration off — the scenario "
            "no longer exercises permanent-failure liveness"
        )
        assert mig["unfinished_with_migration"] == 0, (
            f"{mig['unfinished_with_migration']} jobs still stranded with "
            "stall-budget migration on"
        )
        assert mig["unfinished_sequential"] == 0, (
            f"{mig['unfinished_sequential']} jobs stranded on the sequential "
            "migration reference"
        )
        assert mig["max_record_rel_dev"] == 0.0, (
            f"batched migration re-solves deviated from sequential records "
            f"({mig['max_record_rel_dev']:.3e})"
        )
        assert mig["migrations"] > 0, (
            "migration bench never committed a migration"
        )
        lat = report["latency"]
        assert lat["overhead_frac"] is not None and lat["overhead_frac"] < 0.05, (
            f"instrumentation overhead {lat['overhead_frac'] * 100:.1f}% >= 5% "
            "on the non-smoke fleet bench"
        )
        p99 = lat["event_latency"]["overall"].get("p99")
        assert p99 is not None and np.isfinite(p99) and p99 > 0, (
            f"event-latency p99 not recorded finite ({p99!r})"
        )
        sf = lat["stall_fraction"]
        assert np.isfinite(sf) and 0.0 <= sf < 1.0, (
            f"barrier-stall fraction not recorded finite in [0, 1) ({sf!r})"
        )
        fa = report["fleet_async"]
        assert fa["max_record_rel_dev"] == 0.0, (
            f"async runtime deviated from lockstep records at "
            f"{fa['n_lanes']} lanes ({fa['max_record_rel_dev']:.3e})"
        )
        assert np.isfinite(fa["events_per_s"]) and fa["events_per_s"] > 0, (
            f"async events/sec not recorded finite ({fa['events_per_s']!r})"
        )
        ap99 = fa["event_latency_p99"]
        assert ap99 is not None and np.isfinite(ap99) and ap99 > 0, (
            f"async event-latency p99 not recorded finite ({ap99!r})"
        )
        assert fa["mean_batch_occupancy"] > 1.0, (
            f"async dispatcher never batched across lanes "
            f"(occupancy {fa['mean_batch_occupancy']:.2f})"
        )


if __name__ == "__main__":
    main()
