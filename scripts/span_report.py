"""What tracing costs, and where the device's idle time goes, for one cell.

    python scripts/span_report.py --workload ents-mesh.otfa --seed 7 \
        --passes 2 --modes off tracer capture off tracer capture \
        --out build/spans

Builds the cell's engine and lanes as ``bench/run.py`` does (configuration,
traffic, warm-up grid, passes drawn from ``--seed``), but with the program's
plain ``JRBAEngine`` and ``OnlineScheduler``, and runs passes ``1..--passes``
once per entry of ``--modes``, so every mode does the same work:

* ``off``: no tracer, no capture;
* ``tracer``: ``FleetRuntime(tracer=Tracer())`` keeps every span in memory;
* ``capture``: no tracer, under ``jax.profiler.start_trace`` with Python
  call tracing off (as in the benchmark's traced run); the program's spans
  reach the capture through the null tracer.

Each mode prints events/s and the admission latency median and 95th
percentile of its passes. A ``capture`` run also reads its profiler trace:
the device's idle time inside the passes split by the innermost program span
the host was in, the share of the Pallas kernel's device time inside the
program's ``engine/wait`` spans, and the innermost span around each
``jax/compile``. The warm-up's backend compiles and persistent-cache hits are
printed first. One JSON object per line on standard output; each is also
written to ``--out``.
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

PASS = "span_report/pass"


def innermost(spans):
    """Properly nested (start, end, name) host spans -> disjoint segments
    (start, end, innermost name), None where no span is open."""
    segs = []
    stack: list[tuple[int, str]] = []
    cur = None
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            segs.append((cur, end, nm))
            cur = end
        if cur is not None:
            segs.append((cur, s, stack[-1][1] if stack else None))
        cur = s
        stack.append((e, name))
    while stack:
        end, nm = stack.pop()
        segs.append((cur, end, nm))
        cur = end
    return [(a, b, n) for a, b, n in segs if b > a]


def overlap(a, b):
    """Total overlap of two lists of disjoint sorted intervals."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read_capture(trace_dir: str) -> dict:
    """The capture's program spans and device ops, reduced."""
    import devtrace
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    spans, passes, compiles, ops = [], [], [], []
    counts: collections.Counter = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(devtrace.DEVICE_PLANE) and not ops:
            for line in plane.lines:
                if line.name == devtrace.OP_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name == PASS:
                        passes.append(iv[:2])
                    elif e.name == "jax/compile":
                        compiles.append(iv)
                    elif "/" in e.name and not e.name.startswith(("/", "bench/")):
                        spans.append(iv)
                        counts[e.name] += 1
    out: dict = {"span_counts": dict(counts.most_common())}
    segs = innermost(spans)
    out["compiles_in"] = [
        next((n for a, b, n in segs if a <= c[0] <= b), None) for c in compiles
    ]
    if not ops:
        return out
    passes = devtrace.union(passes)
    window = sum(b - a for a, b in passes)
    clipped = [(n, s, e) for n, s, e in ops if overlap([(s, e)], passes)]
    busy = devtrace.union((s, e) for _, s, e in clipped)
    idle = []
    for a, b in passes:
        edges = [a] + [x for s, e in busy if s < b and e > a for x in (max(s, a), min(e, b))] + [b]
        idle.extend((x, y) for x, y in zip(edges[0::2], edges[1::2]) if y > x)
    idle_ns = sum(b - a for a, b in idle)
    by_span: collections.Counter = collections.Counter()
    for a, b, n in segs:
        by_span[n or "(no program span)"] += overlap([(a, b)], idle)
    by_span["(no program span)"] += idle_ns - sum(by_span.values())
    kernel = devtrace.union((s, e) for n, s, e in clipped if devtrace.KERNEL in n)
    waits = devtrace.union((a, b) for a, b, n in spans if n == "engine/wait")
    kernel_ns = sum(e - s for s, e in kernel)
    out.update(
        window_s=window / 1e9,
        busy_s=overlap(busy, passes) / 1e9,
        idle_s=idle_ns / 1e9,
        idle_by_innermost_span_s={k: v / 1e9 for k, v in by_span.most_common() if v},
        kernel_s=kernel_ns / 1e9,
        kernel_in_engine_wait=overlap(kernel, waits) / kernel_ns if kernel_ns else None,
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--modes", nargs="+", default=["off", "tracer", "capture"],
                    choices=("off", "tracer", "capture"))
    ap.add_argument("--lanes", type=int, help="lanes per pass instead of the traffic's")
    ap.add_argument("--out", default=os.path.join(ROOT, "build", "spans"))
    args = ap.parse_args()

    import run  # bench/run.py: the cell's files and its warm-up

    cell, config, traffic, _ = run.load_cell(args.workload)
    if args.lanes:
        traffic = dict(traffic, lanes=args.lanes, warm={"grid": [[8, 1]]})
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax

    from fleetgen import build_pass
    from recorder import LatencyRecorder
    from repro.compile_cache import enable_compile_cache
    from repro.core import JRBAEngine, OnlineScheduler
    from repro.fleet import FleetRuntime
    from repro.obs import NULL_TRACER, Tracer, dumps_strict
    from stats import percentile

    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    os.makedirs(args.out, exist_ok=True)
    sink = open(os.path.join(args.out, f"span_report_{args.seed}.jsonl"), "w")

    def emit(obj: dict) -> None:
        line = dumps_strict({**obj, "device": device})
        print(line, flush=True)
        sink.write(line + "\n")

    engine = JRBAEngine(k=config["engine"]["k"], n_iters=config["engine"]["n_iters"])
    t0 = time.perf_counter()
    missed, relaxing = run.warm_grid(engine, config, traffic, args.seed)
    st = engine.stats
    emit({
        "phase": "warm-up", "seconds": time.perf_counter() - t0, "relaxing_s": relaxing,
        "shapes": st.cache_misses, "backend_compiles": st.backend_compiles,
        "compile_s": st.compile_seconds, "persistent_cache_hits": st.persistent_cache_hits,
        "missed": missed,
    })
    latency = LatencyRecorder()

    def make_scheduler(net, policy, churned):
        return OnlineScheduler(
            net, policy, k_paths=engine.k, jrba_iters=engine.n_iters, engine=engine,
            max_acceptable_span=config["engine"]["max_acceptable_span"], metrics=latency,
        )

    for i, mode in enumerate(args.modes):
        latency.samples = []
        latency.active = True
        stats0 = engine.stats.as_dict()
        trace_dir = tempfile.mkdtemp(prefix="span-report-") if mode == "capture" else None
        if trace_dir:
            # as the benchmark's traced run: Python call tracing would cost
            # more than the host work it watches
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        walls, events = [], 0
        for p in range(1, args.passes + 1):
            sims, _ = build_pass(engine, config, traffic, args.seed, p, make_scheduler)
            tracer = Tracer() if mode == "tracer" else None
            runtime = FleetRuntime(
                engine, mode=traffic["driver"], batch_target=traffic.get("batch_target", 32),
                deadline_s=traffic.get("deadline_s", 0.002), tracer=tracer,
            )
            with jax.profiler.TraceAnnotation(PASS):
                t = time.perf_counter()
                res = runtime.run(sims)
                walls.append(time.perf_counter() - t)
            events += res.total_events
            if tracer is not None:
                # an observed fleet reports admissions to its own registries;
                # the tracer holds each one's latency as the span's duration
                latency.samples.extend(
                    e["dur"] for e in tracer.events if e["name"] == "job/arrival_to_scheduled"
                )
                engine.tracer = NULL_TRACER
        if trace_dir:
            jax.profiler.stop_trace()
        latency.active = False
        ms = [1e3 * s for s in latency.samples]
        delta = {k: v - stats0[k] for k, v in engine.stats.as_dict().items()}
        out = {
            "phase": "window", "run": i, "mode": mode, "passes": args.passes,
            "window_s": sum(walls), "events_per_s": events / sum(walls),
            "admit_p50_ms": percentile(ms, 50), "admit_p95_ms": percentile(ms, 95),
            "admissions": len(ms), "backend_compiles": delta["backend_compiles"],
        }
        if trace_dir:
            out["capture"] = read_capture(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        emit(out)
    sink.close()


if __name__ == "__main__":
    main()
