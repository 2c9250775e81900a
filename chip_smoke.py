"""Chip smoke test: drive the scheduler's JRBA path once on one TPU chip.

    python chip_smoke.py

One process, one chip, through the entry points a user calls:
``JRBAEngine()`` at its defaults (k=4, n_iters=400, solver="auto") and
``FleetRuntime(engine).run(...)``. Phases, in order:

  a. device and solver: the first device is a TPU and the engine resolved
     the fused Pallas kernel (``"pallas"``), else exit non-zero — the smoke
     never carries on on the CPU, in interpret mode or on the jnp path;
  b. compiled kernel: one real dispatch lowers to HLO holding
     ``tpu_custom_call`` (compiled Pallas, not interpret mode);
  c. fleet: ``build_async_fleet(engine, 1000, n_jobs=2, churn_every=4)`` —
     1000 mixed-churn lanes over edge-mesh, edge-cloud, fat-tree and
     hetero-low — under the lockstep driver; every lane finishes and every
     lane matches the reference record for record;
  d. large WAN with a backlog: wan-mesh-xl (64 sites, ~300 links) under
     OTFA and OTFS, two seeds each at n_jobs=32, the widest active-link
     buckets, against the reference;
  e. second driver: phase (c)'s lanes under ``FleetRuntime(mode="async")``,
     whose records must equal the lockstep records.

The reference is the sparse formulation written in plain XLA
(``JRBAEngine(solver="sparse")`` with the engine's settings): the same
objective, hand-fused gradient, chunked schedule and early exit as the
kernel, implemented independently of it. The dense formulation is not an
exact reference for these runs: on some lanes its relaxation settles on a
different point of a non-unique optimum, or the sparse early exit stops
before the dense schedule would, and the rounding differs — on the CPU as
on the chip (see PERF.md).

Each phase prints one line: device kind, solver, lanes, events, dispatches,
compiled shapes (``engine.stats.cache_misses``), wall seconds (a smoke
timing, not a benchmark), record deviation and unfinished jobs. Any failure
— a deviation above 0 included — exits non-zero without the last line,
which is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.fleet import max_record_dev  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import SCENARIOS, JRBAEngine, OnlineScheduler, random_flow_sets  # noqa: E402
from repro.fleet import FleetRuntime, FleetSim, build_async_fleet  # noqa: E402
from repro.kernels.jrba_congestion import sparse_congestion_solve  # noqa: E402
from repro.obs.trace import dumps_strict  # noqa: E402

LANES = 1000  # phase (c)/(e) fleet size: the repo's largest fleet
XL_SEEDS = 2  # wan-mesh-xl seeds per policy in phase (d)


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def _reference(engine: JRBAEngine) -> JRBAEngine:
    """The sparse-jnp engine every phase compares records against, with the
    engine's own solver settings."""
    return JRBAEngine(
        k=engine.k,
        n_iters=engine.n_iters,
        solver="sparse",
        early_exit=engine.early_exit,
        span_rtol=engine.span_rtol,
        stable_chunks=engine.stable_chunks,
    )


def _run(engine: JRBAEngine, sims: list[FleetSim], mode: str = "lockstep") -> tuple:
    """Run a fleet and return ``(result, stats)`` where ``stats`` counts the
    dispatches and newly compiled shapes this run cost the engine."""
    s0 = engine.stats
    d0, c0 = s0.batched_solves + s0.single_solves, s0.cache_misses
    res = FleetRuntime(engine, mode=mode).run(sims)
    s = engine.stats
    return res, {
        "lanes": len(sims),
        "events": res.total_events,
        "dispatches": s.batched_solves + s.single_solves - d0,
        "compiled_shapes": s.cache_misses - c0,
        "smoke_wall_s": res.wall_seconds,
        "unfinished": res.unfinished,
    }


def _check_records(name: str, got: list, want: list) -> float:
    """Record-for-record equality of two runs' per-lane results."""
    if len(got) != len(want):
        raise SmokeFailure(f"{name}: {len(got)} lanes vs {len(want)} reference lanes")
    for i, (a, b) in enumerate(zip(got, want)):
        if len(a.records) != len(b.records) or a.n_scheduled != b.n_scheduled:
            raise SmokeFailure(f"{name}: lane {i} scheduled different jobs than the reference")
    dev = max_record_dev(got, want)
    if dev != 0.0:
        raise SmokeFailure(f"{name}: max_record_dev {dev!r} != 0 against the reference")
    return dev


def _report(phase: str, engine: JRBAEngine, **fields) -> dict:
    row = {"phase": phase, "device_kind": jax.devices()[0].device_kind, "solver": engine.solver}
    row.update(fields)
    print(" ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    return row


def phase_device(engine: JRBAEngine) -> dict:
    """(a) The first device is a TPU and the engine resolved ``"pallas"``."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (first device is {dev.platform!r}); refusing to run")
    if engine.solver != "pallas":
        raise SystemExit(
            f"chip_smoke: solver resolved to {engine.solver!r}, not 'pallas' "
            f"(REPRO_JRBA_SOLVER={os.environ.get('REPRO_JRBA_SOLVER')!r})"
        )
    return _report("a", engine, platform=dev.platform, count=len(jax.devices()))


def phase_compiled(engine: JRBAEngine) -> dict:
    """(b) Lower one real solver dispatch and find the compiled Pallas call in
    its HLO (interpret mode would lower to plain XLA ops instead)."""
    net, _ = SCENARIOS["edge-mesh"].build(seed=0, n_jobs=4)
    (flows,) = random_flow_sets(net, 1, 5, seed=0)
    prog = engine.build(net, flows)
    t0 = time.perf_counter()
    hlo = (
        sparse_congestion_solve.lower(
            prog.usage_active[None],
            prog.valid[None],
            prog.volumes[None],
            prog.capacity_active()[None],
            np.array([len(prog.capacity) - prog.la_pad], np.float32),
            n_iters=engine.n_iters,
            early_exit=engine.early_exit,
            span_rtol=engine.span_rtol,
            stable_chunks=engine.stable_chunks,
            interpret=engine.solver == "pallas-interpret",
        )
        .compile()
        .as_text()
    )
    found = "tpu_custom_call" in hlo
    row = _report(
        "b",
        engine,
        shape=prog.usage_active.shape,
        tpu_custom_call=found,
        smoke_wall_s=time.perf_counter() - t0,
    )
    if not found:
        raise SmokeFailure("compiled solver HLO holds no tpu_custom_call")
    return row


def phase_fleet(engine: JRBAEngine, n_lanes: int) -> tuple[dict, list]:
    """(c) The mixed-churn fleet under lockstep against the reference.
    Returns the row and the lane results."""
    res, stats = _run(engine, build_async_fleet(engine, n_lanes, n_jobs=2, churn_every=4))
    ref_engine = _reference(engine)
    ref, _ = _run(ref_engine, build_async_fleet(ref_engine, n_lanes, n_jobs=2, churn_every=4))
    dev = _check_records("fleet", res.results, ref.results)
    row = _report("c", engine, **stats, max_record_dev=dev)
    if res.unfinished:
        raise SmokeFailure(f"fleet: {res.unfinished} jobs unfinished")
    return row, res.results


def _wan_sims(engine: JRBAEngine, seeds: int, n_jobs: int) -> list[FleetSim]:
    sims = []
    for policy in ("OTFA", "OTFS"):
        for seed in range(seeds):
            net, arrivals = SCENARIOS["wan-mesh-xl"].build(seed=seed, n_jobs=n_jobs)
            sched = OnlineScheduler(
                net, policy, k_paths=engine.k, jrba_iters=engine.n_iters, engine=engine
            )
            sims.append(FleetSim(sched, arrivals, name=f"wan-mesh-xl/{policy}"))
    return sims


def phase_wan(engine: JRBAEngine, seeds: int, n_jobs: int = 32) -> dict:
    """(d) wan-mesh-xl under OTFA and OTFS against the reference."""
    res, stats = _run(engine, _wan_sims(engine, seeds, n_jobs))
    ref_engine = _reference(engine)
    ref, _ = _run(ref_engine, _wan_sims(ref_engine, seeds, n_jobs))
    dev = _check_records("wan-mesh-xl", res.results, ref.results)
    row = _report("d", engine, **stats, n_jobs=n_jobs, max_record_dev=dev)
    if res.unfinished:
        raise SmokeFailure(f"wan-mesh-xl: {res.unfinished} jobs unfinished")
    return row


def phase_async(engine: JRBAEngine, n_lanes: int, lockstep: list) -> dict:
    """(e) Phase (c)'s lanes under the async driver: records equal lockstep."""
    res, stats = _run(
        engine, build_async_fleet(engine, n_lanes, n_jobs=2, churn_every=4), mode="async"
    )
    dev = _check_records("async", res.results, lockstep)
    return _report("e", engine, **stats, max_record_dev=dev)


def main() -> None:
    engine = JRBAEngine()
    phase_device(engine)  # exits off the chip before anything compiles or is cached
    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache {cache}: {n_cached} entries at start", flush=True)
    phase_compiled(engine)
    _, lockstep = phase_fleet(engine, LANES)
    phase_wan(engine, XL_SEEDS)
    phase_async(engine, LANES, lockstep)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(dumps_strict({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
