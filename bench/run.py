"""Run one benchmark cell once on the chip and print one result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``: the deployment) and a traffic mix
(``bench/traffic/<traffic>.json``: the driver, the lanes and jobs of a pass,
their arrivals and churn). The run:

1. refuses to run without a TPU (or with fewer chips than the cell asks
   for) and on a device kind that ``bench/peaks.json`` does not know;
2. keeps the persistent compile cache in ``<checkout>/.jax_cache``, set as
   ``JAX_COMPILATION_CACHE_DIR`` for the program's ``enable_compile_cache``;
3. warm-up: every relaxation shape the mix can send (the traffic's
   ``warm.grid``: flow buckets by active-link widths by batch sizes),
   relaxed once on networks of pass 0 (set-up ends here);
4. window: passes 1, 2, ... of fresh lanes drawn from ``--seed`` through
   ``FleetRuntime.run`` until ``--seconds`` of pass time have elapsed; the
   pass in progress finishes;
5. reads the device's peak memory, then compares the window's answers,
   placements and records with the plain reference (``check.py``);
6. prints the end-to-end metrics (``--trace 0``) or the per-layer metrics
   read from a profiler trace of the window (``--trace 1``).

The last line of standard output is one JSON object; the numbers compared
for ``correct`` are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# JAX's persistent compilation cache: one fixed directory inside the
# checkout (the path is part of the cache key), handed to the program
# through the variable it reads
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class Refused(SystemExit):
    """The run cannot measure here; exits non-zero with no result line."""

    def __init__(self, why: str) -> None:
        print(f"bench: {why}", file=sys.stderr)
        super().__init__(2)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """The cell's ``BENCHMARK.json`` entry, configuration, traffic mix and
    the whole benchmark description."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise Refused(f"no BENCHMARK.json at {ROOT}")
    with open(path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    (cfg_entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic, spec


def device_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def require_chips(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU (JAX found {devs[0].platform!r}); refusing to measure")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _la_pad(la: int, n_links: int) -> int:
    """The active-link width a program of ``la`` touched links compiles to."""
    pad = 8
    while pad < la:
        pad *= 2
    return min(pad, n_links)


def _find_flows(engine, net, nf, la_pad, rng, tries=200):
    """Flows on ``net`` whose program lands in flow bucket ``nf`` and
    active-link width ``la_pad`` (None if the search finds none)."""
    from repro.core import Flow

    lo_n = 2 if nf == engine.min_bucket else nf // 2 + 1
    pairs = [(u, v) for u in range(net.n_nodes) for v in range(net.n_nodes) if u != v]
    for _ in range(tries):
        rng.shuffle(pairs)
        want = int(rng.integers(lo_n, nf + 1))
        chosen, touched = [], np.zeros(len(net.links), dtype=bool)
        for u, v in pairs:
            if len(chosen) == want:
                break
            mask = touched | engine.candidate_links(net, [Flow(u, v, 1.0)])
            if _la_pad(int(mask.sum()), len(net.links)) > la_pad:
                continue
            chosen.append((u, v))
            touched = mask
            if _la_pad(int(touched.sum()), len(net.links)) == la_pad and rng.random() < 0.3:
                break
        if not chosen or _la_pad(int(touched.sum()), len(net.links)) != la_pad:
            continue
        flows = [Flow(*chosen[i % len(chosen)], 1.0 + 1e-3 * i) for i in range(want)]
        return flows
    return None


def warm_grid(engine, config: dict, traffic: dict, seed: int) -> list:
    """Relax one program of every shape the mix can send: the traffic's
    ``warm.grid`` lists tiers ``[nf_max, batch_max]``; each flow bucket up to
    a tier's ``nf_max`` (above the tier before) is relaxed at every
    active-link width its networks allow, in batches of every power of two
    up to the tier's ``batch_max``, on networks of pass 0 (no window
    measures them), through the engine's batch relaxation, with its shape
    signature noted as ``solve_many`` notes it. Returns the (bucket, width)
    pairs no search found and the seconds spent relaxing."""
    import fleetgen

    rng = np.random.default_rng([seed % 2**64, 0, 1])
    nets = [
        fleetgen.network(config, np.random.RandomState(fleetgen.lane_seeds(seed, 0, i)[0]))
        for i in range(4)
    ]
    widths = sorted({_la_pad(la, len(g.links)) for g in nets for la in range(1, len(g.links) + 1)})
    missed = []
    relaxing = 0.0
    nf = engine.min_bucket
    for nf_max, b_max in traffic["warm"]["grid"]:
        while nf <= nf_max:
            for pad in widths:
                found = None
                for g in nets:
                    if pad <= len(g.links):
                        flows = _find_flows(engine, g, nf, pad, rng)
                        if flows is not None:
                            found = (g, flows)
                            break
                if found is None:
                    missed.append((nf, pad))
                    continue
                # the relaxation alone, at every batch size: rounding the
                # copies would teach the compiler nothing and cost seconds
                g, flows = found
                prog = engine.build(g, flows)
                b = 1
                while b <= b_max:
                    engine._note_shape(("batch", b, engine._shape_key(prog), engine.n_iters))
                    t0 = time.perf_counter()
                    engine._relax_group([prog] * b, 1)
                    relaxing += time.perf_counter() - t0
                    b *= 2
            nf *= 2
    return missed, relaxing


def main(argv=None, *, devices=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, config, traffic, spec = load_cell(args.workload)

    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    devs = devices if devices is not None else require_chips(jax, cell["chips"])
    peaks = device_peaks(devs[0].device_kind)

    from repro.compile_cache import enable_compile_cache
    from repro.fleet import FleetRuntime

    import check
    import devtrace
    from fleetgen import build_pass
    from recorder import LatencyRecorder, PlacementLog, RecordingEngine, RecordingScheduler
    from stats import percentile

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    enable_compile_cache()
    trace = bool(args.trace)
    engine = RecordingEngine(
        k=config["engine"]["k"], n_iters=config["engine"]["n_iters"], annotate=trace
    )
    if devices is None and engine.solver != "pallas":
        raise Refused(f"solver resolved to {engine.solver!r}, not the Pallas kernel")
    latency = LatencyRecorder()
    placements = PlacementLog()
    runtime = FleetRuntime(
        engine,
        mode=traffic["driver"],
        batch_target=traffic.get("batch_target", 32),
        deadline_s=traffic.get("deadline_s", 0.002),
    )

    def make_scheduler(net, policy, churned):
        return RecordingScheduler(
            net, policy, k_paths=engine.k, jrba_iters=engine.n_iters, engine=engine,
            max_acceptable_span=config["engine"]["max_acceptable_span"],
            metrics=latency, log=None if churned else placements,
        )

    # warm-up: every shape the mix can send, compiled (or read from the
    # cache) and run once
    t_warm = time.perf_counter()
    missed, relaxing = warm_grid(engine, config, traffic, args.seed)
    setup_s = time.perf_counter() - T_START
    print(
        f"setup {setup_s!r} s: start {t_warm - T_START!r}, warm-up {setup_s + T_START - t_warm!r}"
        f" ({relaxing!r} s relaxing), {engine.stats.cache_misses} shapes,"
        f" none found for {missed}",
        file=sys.stderr,
    )

    # the window: passes 1, 2, ... until --seconds of pass time
    stats0 = engine.stats.as_dict()
    lane_results, telemetry, walls = [], [], []
    latency.active = engine.recording = placements.active = True
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python call tracing costs more than the host work
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/window") if trace else nullcontext():
        while sum(walls) < args.seconds:
            sims, lanes = build_pass(engine, config, traffic, args.seed, len(walls) + 1,
                                     make_scheduler)
            with jax.profiler.TraceAnnotation("bench/pass") if trace else nullcontext():
                t0 = time.perf_counter()
                res = runtime.run(sims)
                walls.append(time.perf_counter() - t0)
            lane_results.extend(zip(lanes, res.results))
            telemetry.append(res.telemetry.summary)
    if trace:
        jax.profiler.stop_trace()
    latency.active = engine.recording = placements.active = False
    window_s = sum(walls)
    stats1 = engine.stats.as_dict()
    delta = {k: stats1[k] - stats0[k] for k in stats0}
    memory_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs[: cell["chips"]]
    )
    results = [r for _, r in lane_results]

    t_check = time.perf_counter()
    numbers, seen = check.compare(
        engine.calls, placements.placements, lane_results, args.seed, config["engine"]
    )
    correct, checks = check.judge(numbers, check.load_limits())
    print(f"reference check {time.perf_counter() - t_check!r} s, {seen}", file=sys.stderr)
    attempted = sum(len(r.records) for r in results)

    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": int(memory_peak),
    }
    out = {"correct": correct, "attempted": attempted, "failed": int(numbers["unfinished"])}
    metrics: dict = {}
    if not trace:
        events = sum(r.n_events for r in results)
        ms = [1e3 * s for s in latency.samples]
        values = {
            "events_per_s": events / window_s,
            "admit_p50_ms": percentile(ms, 50),
            "admit_p95_ms": percentile(ms, 95),
            "setup_s": setup_s,
        }
        for m in spec["end_to_end"]:
            if args.workload in m.get("workloads", [args.workload]):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        out["samples"] = {"passes": len(walls), "events": events, "admissions": len(ms)}
    else:
        reduced = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev = devtrace.reduce(reduced)
        ctx = {
            "window_s": window_s,
            "delta": delta,
            "telemetry": telemetry,
            "trace": dev,
            "calls": engine.calls,
            "k": engine.k,
            "n_iters": engine.n_iters,
            "peaks": peaks,
        }
        for m in spec["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["notes"] = {
            "roofline_bound": ctx.get("roofline_bound"),
            "kernel_s": None if dev is None else dev["kernel_s"],
            "kernel_events": None if dev is None else dev["kernel_events"],
        }
        if dev is not None:
            device["busy_s"] = dev["busy_s"]
            device["window_s"] = dev["window_s"]
            out["breakdown"] = {"device_ops": dev["device_ops"], "idle_gaps": dev["idle_gaps"]}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
