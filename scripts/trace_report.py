"""Digest a fleet trace into a terminal latency report.

  python scripts/trace_report.py TRACE [--top 12]

Accepts either artifact the fleet tooling emits, sniffing the format from
the file contents (no flag needed):

  * a **Chrome trace** (``benchmarks/fleet.py --trace out.trace.json`` or
    ``Tracer.to_chrome``): a JSON object with a ``traceEvents`` array.
    The report aggregates complete ("X") and matched begin/end ("B"/"E")
    spans per name, prints the top spans by total duration, the host layers
    the program measures (``lane/step`` with ``sched/alloc`` inside it,
    ``fleet/pick``, ``fleet/dispatch`` and the engine's ``engine/build``,
    ``engine/paths``, ``engine/stage``, ``engine/wait``, ``engine/finalize``,
    ``engine/refine``, plus ``jax/compile`` instants), a percentile
    table for per-job ``job/arrival_to_scheduled`` latencies, the
    barrier-stall attribution (``lane/own_solve`` vs ``lane/barrier_stall``
    totals), a migration digest (``migrate/*`` rounds, commit/reject/
    infeasible splits, moved tasks and transfer-penalty totals), instant-
    event counts, and per-track wall-clock totals.
  * a **telemetry JSONL** (``FleetTelemetry.to_jsonl``): one ``round`` line
    per dispatch round plus a terminal ``summary`` line. The report prints
    round-level dispatch/stall totals, the summary's ``migration`` block
    (commit/reject/infeasible splits and transfer-penalty totals) when a
    lane ran with a stall budget, and, when the summary carries the
    ``latency`` observability block, the event-latency percentiles, per-lane
    stall table and solver phase split, the ``host`` split of each pass
    (lane steps, Algorithm 1, ``solve_many``, driver) and the relaxation's
    rounding wins.

Pure stdlib (json/argparse/math) so it runs in the minimal CI environment.
Exit status 0 on success, 1 on unreadable or empty input.
"""
from __future__ import annotations

import argparse
import json
import math
import sys


def _percentile(sorted_vals: list[float], q: float) -> float:
    """numpy-style linear-interpolation percentile on pre-sorted data."""
    n = len(sorted_vals)
    if n == 1:
        return sorted_vals[0]
    rank = (n - 1) * q / 100.0
    lo = int(math.floor(rank))
    hi = min(lo + 1, n - 1)
    frac = rank - lo
    return sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac


def _fmt_s(seconds: float) -> str:
    """Human-scale a duration in seconds."""
    if seconds >= 1.0:
        return f"{seconds:8.3f} s "
    if seconds >= 1e-3:
        return f"{seconds * 1e3:8.3f} ms"
    return f"{seconds * 1e6:8.3f} us"


def _percentile_row(vals: list[float]) -> str:
    vals = sorted(vals)
    return (
        f"n={len(vals):<6d}"
        f" p50={_fmt_s(_percentile(vals, 50)).strip():<12s}"
        f" p95={_fmt_s(_percentile(vals, 95)).strip():<12s}"
        f" p99={_fmt_s(_percentile(vals, 99)).strip():<12s}"
        f" max={_fmt_s(vals[-1]).strip()}"
    )


# -- Chrome trace -------------------------------------------------------------


# the spans the program measures around its own work, by layer (a fleet
# lane's event/* spans are split at its yields: each piece is self time)
LAYER_SPANS = (
    ("lane/step", "lane stepper resumes (event loop)"),
    ("sched/alloc", "  Algorithm 1 inside them"),
    ("fleet/pick", "fleet driver: queue picks, bookkeeping"),
    ("fleet/dispatch", "fleet driver: solve_many calls"),
    ("engine/build", "  program build"),
    ("engine/paths", "    Yen's paths (cache misses)"),
    ("engine/stage", "  relaxation staging (stack, upload)"),
    ("engine/wait", "  relaxation launch -> results on host"),
    ("engine/finalize", "  rounding, Eq. 15"),
    ("engine/refine", "    start portfolio"),
)


def report_chrome(doc: dict, *, top: int) -> int:
    events = doc.get("traceEvents", [])
    tracks: dict[int, str] = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tracks[ev.get("tid", 0)] = ev.get("args", {}).get("name", "?")

    # span durations in seconds, per name: X events carry dur; B/E pairs are
    # matched per (tid, name) with a stack, tolerating unbalanced tails
    durs: dict[str, list[float]] = {}
    track_busy: dict[int, float] = {}
    instants: dict[str, int] = {}
    migrate_args: dict[str, list[dict]] = {}
    compile_args: list[dict] = []
    open_b: dict[tuple[int, str], list[float]] = {}
    unbalanced = 0
    for ev in events:
        ph = ev.get("ph")
        name = ev.get("name", "?")
        tid = ev.get("tid", 0)
        if ph == "X":
            dur = ev.get("dur", 0) / 1e6
            durs.setdefault(name, []).append(dur)
            track_busy[tid] = track_busy.get(tid, 0.0) + dur
        elif ph == "B":
            open_b.setdefault((tid, name), []).append(ev.get("ts", 0))
        elif ph == "E":
            stack = open_b.get((tid, name))
            if not stack:
                unbalanced += 1
                continue
            dur = (ev.get("ts", 0) - stack.pop()) / 1e6
            durs.setdefault(name, []).append(dur)
            track_busy[tid] = track_busy.get(tid, 0.0) + dur
        elif ph == "i":
            instants[name] = instants.get(name, 0) + 1
            if name.startswith("migrate/"):
                migrate_args.setdefault(name, []).append(ev.get("args") or {})
            elif name == "jax/compile":
                compile_args.append(ev.get("args") or {})
    unbalanced += sum(len(s) for s in open_b.values())

    n_spans = sum(len(v) for v in durs.values())
    print(f"chrome trace: {len(events)} events, {n_spans} spans, {len(tracks)} tracks")
    if unbalanced:
        print(f"  WARNING: {unbalanced} unmatched begin/end events")

    if durs:
        print(f"\ntop {min(top, len(durs))} spans by total duration:")
        ranked = sorted(durs.items(), key=lambda kv: -sum(kv[1]))
        for name, vals in ranked[:top]:
            total = sum(vals)
            print(
                f"  {name:<28s} {_fmt_s(total)} total"
                f"  n={len(vals):<6d} mean={_fmt_s(total / len(vals)).strip()}"
            )

    if any(name in durs for name, _ in LAYER_SPANS):
        print("\nhost layers (measured spans):")
        for name, what in LAYER_SPANS:
            vals = durs.get(name)
            if vals:
                print(f"  {what:<40s} {name:<16s} {_fmt_s(sum(vals))}  n={len(vals)}")
    if compile_args:
        n = sum(int(a.get("compiles") or 0) for a in compile_args)
        secs = sum(float(a.get("duration_s") or 0.0) for a in compile_args)
        print(f"\nbackend compiles: {n}, {_fmt_s(secs).strip()}")

    jobs = durs.get("job/arrival_to_scheduled")
    if jobs:
        print(f"\njob arrival->scheduled latency: {_percentile_row(jobs)}")

    own = sum(durs.get("lane/own_solve", []))
    stall = sum(durs.get("lane/barrier_stall", []))
    if own or stall:
        frac = stall / (own + stall) if own + stall else 0.0
        print(
            f"\nbarrier attribution: own-solve {_fmt_s(own).strip()}, "
            f"stall {_fmt_s(stall).strip()} ({frac:.1%} of lane wall-clock)"
        )

    if migrate_args or durs.get("migrate/round"):
        commits = migrate_args.get("migrate/commit", [])
        rejects = migrate_args.get("migrate/reject", [])
        infeasible = migrate_args.get("migrate/infeasible", [])
        rounds = durs.get("migrate/round", [])
        print(
            f"\nmigration: {len(rounds)} rounds, {len(commits)} commits, "
            f"{len(rejects)} rejects, {len(infeasible)} infeasible checks"
        )
        if commits:
            moved = sum(int(a.get("moved", 0)) for a in commits)
            penalty = sum(float(a.get("penalty", 0.0)) for a in commits)
            print(
                f"  moved {moved} tasks, transfer penalty "
                f"{penalty:.3f} simulated s"
            )
        if rejects:
            worst = max(
                (float(a["migrated_proj"]) for a in rejects if "migrated_proj" in a),
                default=None,
            )
            if worst is not None:
                print(f"  worst rejected migrated-projection {worst:.3f} simulated s")

    if instants:
        print("\ninstant events:")
        for name, n in sorted(instants.items(), key=lambda kv: -kv[1]):
            print(f"  {name:<28s} x{n}")

    if track_busy:
        print("\nper-track busy time (span durations, nesting double-counts):")
        for tid, busy in sorted(track_busy.items(), key=lambda kv: -kv[1]):
            print(f"  [{tid:2d}] {tracks.get(tid, '?'):<24s} {_fmt_s(busy)}")
    return 0


# -- telemetry JSONL ----------------------------------------------------------


def report_jsonl(lines: list[dict], *, top: int) -> int:
    rounds = [ln for ln in lines if ln.get("type") == "round"]
    summaries = [ln for ln in lines if ln.get("type") == "summary"]
    print(f"telemetry jsonl: {len(rounds)} rounds, {len(summaries)} summary line(s)")

    if rounds:
        dispatch = sum(r.get("dispatch_seconds", 0.0) for r in rounds)
        stall = sum(r.get("stall_seconds", 0.0) for r in rounds)
        solves = sum(r.get("n_solves", 0) for r in rounds)
        requests = sum(r.get("n_requests", 0) for r in rounds)
        print(
            f"  dispatch {_fmt_s(dispatch).strip()} total, "
            f"summed lane stall {_fmt_s(stall).strip()}, "
            f"{solves} solves from {requests} requesting lane-rounds"
        )

    for summary in summaries:
        mig = summary.get("migration")
        if mig:
            print(
                f"\nmigration: {mig.get('migrations', 0)} commits / "
                f"{mig.get('checks', 0)} checks "
                f"(rejected {mig.get('rejected', 0)}, "
                f"infeasible {mig.get('infeasible', 0)}), "
                f"moved {mig.get('moved_tasks', 0)} tasks, "
                f"penalty {mig.get('penalty_seconds', 0.0):.3f} simulated s"
            )
        lat = summary.get("latency")
        if not lat:
            print("  summary carries no latency block (run not observed)")
            continue
        barrier = lat.get("barrier", {})
        sf = barrier.get("stall_fraction")
        if sf is not None:
            print(
                f"\nbarrier: dispatch {_fmt_s(barrier.get('dispatch_seconds', 0.0)).strip()}, "
                f"own {_fmt_s(barrier.get('own_solve_seconds', 0.0)).strip()}, "
                f"stall {_fmt_s(barrier.get('stall_seconds', 0.0)).strip()} "
                f"({sf:.1%} of lane wall-clock)"
            )
        lanes = barrier.get("per_lane") or []
        for row in sorted(lanes, key=lambda r: -r.get("stall_seconds", 0.0))[:top]:
            print(
                f"  lane {row.get('lane'):>3} {row.get('name', '?'):<18s}"
                f" own={_fmt_s(row.get('own_seconds', 0.0)).strip():<12s}"
                f" stall={_fmt_s(row.get('stall_seconds', 0.0)).strip():<12s}"
                f" ({row.get('stall_fraction', 0.0):.1%})"
            )
        events = lat.get("events")
        if events:
            overall = events.get("overall") or {}
            if overall.get("count"):
                print(
                    "\nevent latency (arrival->scheduled): "
                    f"n={overall['count']} "
                    f"p50={_fmt_s(overall.get('p50') or 0.0).strip()} "
                    f"p95={_fmt_s(overall.get('p95') or 0.0).strip()} "
                    f"p99={_fmt_s(overall.get('p99') or 0.0).strip()}"
                )
            for name, snap in sorted((events.get("by_scenario") or {}).items()):
                if snap.get("count"):
                    print(
                        f"  {name:<24s} n={snap['count']:<5d}"
                        f" p50={_fmt_s(snap.get('p50') or 0.0).strip():<12s}"
                        f" p99={_fmt_s(snap.get('p99') or 0.0).strip()}"
                    )
        phases = lat.get("solver_phases")
        if phases:
            print("\nsolver phases:")
            for key, val in sorted(phases.items(), key=lambda kv: -kv[1]):
                print(f"  {key:<20s} {_fmt_s(val)}")
        host = summary.get("host")
        if host:
            print(
                f"\nhost: lane steps {_fmt_s(host['lane_step_seconds']).strip()} "
                f"(Algorithm 1 {_fmt_s(host['alloc_seconds']).strip()}, "
                f"{host['alloc_calls']} calls), "
                f"solve_many {_fmt_s(host['solve_many_seconds']).strip()}, "
                f"driver {_fmt_s(host['driver_seconds']).strip()}"
            )
        solver = summary.get("solver") or {}
        if solver.get("refined_programs"):
            print(
                f"rounding: the relaxation's start won {solver['relax_start_wins']} "
                f"of {solver['refined_programs']} programs"
            )
    return 0


# -- entry --------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome trace JSON or telemetry JSONL")
    ap.add_argument(
        "--top", type=int, default=12, help="rows in ranked tables (default 12)"
    )
    args = ap.parse_args()

    with open(args.trace) as f:
        text = f.read()
    if not text.strip():
        print(f"error: {args.trace} is empty", file=sys.stderr)
        return 1

    # format sniff: a Chrome trace is one JSON object with "traceEvents";
    # telemetry is JSON-lines (one object per line)
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict) and "traceEvents" in doc:
        return report_chrome(doc, top=args.top)

    lines = []
    for i, raw in enumerate(text.splitlines()):
        if not raw.strip():
            continue
        try:
            lines.append(json.loads(raw))
        except json.JSONDecodeError as exc:
            print(f"error: {args.trace}:{i + 1} is not valid JSON: {exc}", file=sys.stderr)
            return 1
    if not lines:
        print(f"error: {args.trace} contains no records", file=sys.stderr)
        return 1
    return report_jsonl(lines, top=args.top)


if __name__ == "__main__":
    sys.exit(main())
