"""The comparison that decides ``correct``: what the timed passes produced,
held against the plain reference in ``reference.py`` and the limits in
``limits.json``.

Numbers compared, each beside its limit:

* ``unfinished``: jobs of the window's passes that never finished;
* ``record_faults``: job records not done, or whose times run backwards;
* ``time_faults``: jobs admitted at a time when nothing happened in their
  lane (no arrival, finish, network change or migration round), and, on
  OTFS lanes whose network holds still, jobs whose time per stream unit is
  not the plain one of their placement and bandwidths or whose finish is
  not their admission plus their units at that time each;
* ``place_faults``: sampled Algorithm 1 placements that differ from the
  plain Algorithm 1 on the same network state;
* ``answer_faults``: sampled answers that answer other flows than asked, or
  whose candidate paths are not the ``k`` shortest loopless ones by hops over
  live links, or whose route is none of them;
* ``bw_gap``: the widest relative gap between a sampled answer's bandwidths
  or span and Eq. 15 on its routes;
* ``relax_gap``: the widest relative gap of the relaxation's span, and of
  the congestion and totals of the spread it returned, from the plain
  relaxation over the same candidate paths;
* ``span_gap``: the largest share by which an answer's span exceeds the
  best span of the rounding from its relaxation-free starts;
* ``link_overload``: on OTFS lanes whose network holds still, replayed from
  the job records alone, the largest share by which the bandwidths of the
  jobs running together at an admission oversubscribe a link's capacity
  (each job holds its admission's bandwidths on its routes until its finish;
  a job finishing at the instant of the admission has released them). OTFS
  solves each job on the residual its running jobs leave, so no link is
  oversubscribed; an OTFA lane re-solves every job jointly at each event
  and its records keep only the last answer, so it is not replayed here.

A configuration adds checks of its own guarantees by name (``"checks":
["<name>", ...]``): each is a file ``bench/checks/<name>.py`` that holds

* ``LIMITS``: each number it compares and its limit, with the readings the
  limit was set from written beside it;
* ``compare(ctx)``: those numbers for one run, from ``ctx``'s ``calls`` and
  ``placements`` (what ``recorder.py`` kept), ``lane_results`` (each lane's
  ``fleetgen.Lane``, which holds its churn steps, beside its
  ``SimResult``), ``seed`` and ``engine`` (the configuration's block).

Their numbers join the nine above, and ``correct`` asks all of them to be
within their limits.

Where both the answer's span and the reference's exceed the configuration's
``max_acceptable_span``, the scheduler admits nothing on either and the job
waits: the two agree, and their spans are not compared further. Those are
solves over links with next to nothing left (capacity floored at 1e-9),
whose congestions run to 1e9, where float32 keeps no digit of a flow's own
share and every path ties.
"""
from __future__ import annotations

import bisect
import importlib.util
import json
import os
import re

import numpy as np

import reference as ref

BENCH = os.path.dirname(os.path.abspath(__file__))

# answers checked per run: a sample drawn from the seed, plus the answers
# with the most flows (the widest programs the kernel saw); the LP and the
# rounding run on a smaller sample, and on the widest too
SAMPLE = 3000
DEEP_SAMPLE = 300
WIDEST = 32
PLACEMENTS = 500
# relative tolerance of the time checks: the event loop itself takes a
# finish event for its job's finish when the two agree to 1e-9
TIME_RTOL = 1e-9


def load_limits() -> dict:
    with open(os.path.join(BENCH, "limits.json")) as f:
        return {k: v["limit"] for k, v in json.load(f)["limits"].items()}


def named(names) -> list:
    """The modules ``bench/checks/<name>.py`` of a configuration's
    ``checks``; a name without a file, or a number that another check
    already compares, is an error."""
    taken = set(load_limits())
    modules = []
    for name in names:
        path = os.path.join(BENCH, "checks", name + ".py")
        if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", name) or not os.path.exists(path):
            raise KeyError(f"no check bench/checks/{name}.py")
        spec = importlib.util.spec_from_file_location("bench_check_" + name.replace(".", "_"),
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if taken & set(mod.LIMITS):
            raise ValueError(f"check {name} compares {sorted(taken & set(mod.LIMITS))} again")
        taken |= set(mod.LIMITS)
        modules.append(mod)
    return modules


def _sample(n, size, rng, always=()):
    picked = set(always)
    if n > size:
        picked.update(int(i) for i in rng.choice(n, size, replace=False))
    else:
        picked.update(range(n))
    return sorted(picked)


def _close(a, b):
    return abs(a - b) <= TIME_RTOL * max(abs(a), abs(b), 1.0)


def _is_event(t, events):
    i = bisect.bisect_left(events, t)
    return any(_close(t, events[j]) for j in (i - 1, i) if 0 <= j < len(events))


def time_faults(lane_results) -> int:
    faults = 0
    for lane, result in lane_results:
        events = {r.submit_time for r in result.records}
        events |= {r.finish_time for r in result.records}
        events = sorted(events | set(lane.churn_times) | set(lane.migrate_times))
        for r in result.records:
            if not _is_event(r.schedule_time, events):
                faults += 1
                continue
            if lane.policy != "OTFS" or lane.churn_times:
                continue
            place = [int(x) for x in r.alloc.assignment]
            span = ref.job_span(lane.power, place, [t.workload for t in r.job.tasks],
                                [f.volume for f in r.flows],
                                [] if r.bandwidths is None else r.bandwidths)
            if not (_close(r.span, span)
                    and _close(r.finish_time, r.schedule_time + r.total_units * r.span)):
                faults += 1
    return faults


def link_overload(lane_results) -> float:
    worst = 0.0
    for lane, result in lane_results:
        if lane.policy != "OTFS" or lane.churn_times:
            continue
        topo = ref.Topology(lane.links)
        cap = np.maximum(lane.capacity, ref.CAPACITY_FLOOR)
        held = []  # (admission, finish, load on each link)
        for r in result.records:
            if r.schedule_time < 0 or r.bandwidths is None:
                continue
            load = np.zeros(len(cap))
            for route, b in zip(r.routes, r.bandwidths):
                ids = topo.path_links(list(route))
                if ids is None:
                    return float("inf")  # a route that is no path carries nothing anywhere
                load[ids] += b
            end = r.finish_time if r.finish_time >= r.schedule_time else np.inf
            held.append((r.schedule_time, end, load))
        for t, _, _ in held:
            running = sum(
                (load for start, end, load in held
                 if (start <= t or _close(start, t)) and end > t and not _close(end, t)),
                np.zeros(len(cap)),
            )
            worst = max(worst, float(np.max((running - cap) / cap)))
    return worst


def place_faults(placements, rng) -> int:
    faults = 0
    for i in _sample(len(placements), PLACEMENTS, rng):
        p = placements[i]
        net = p.net
        tasks = [(t.workload, t.mem, t.pinned_node) for t in p.job.tasks]
        want = ref.algorithm1(net.power, p.mem, net.links, p.capacity, p.link_alive,
                              tasks, p.job.edges)
        if want is None:
            faults += p.feasible
        else:
            faults += (not p.feasible) or list(p.assignment) != want
    return faults


def answer_numbers(calls, rng, k, n_iters, accept):
    answers = [a for c in calls for a in c.answers if a.result is not None or a.flows]
    widest = sorted(range(len(answers)), key=lambda i: -len(answers[i].flows))[:WIDEST]
    picked = _sample(len(answers), SAMPLE, rng, widest)
    deep = set(_sample(len(picked), DEEP_SAMPLE, rng))
    deep |= {j for j, i in enumerate(picked) if i in set(widest)}
    topos: dict = {}
    faults, bw_gap, relax_gap, span_gap, relax_wins = 0, 0.0, 0.0, 0.0, 0
    for j, i in enumerate(picked):
        a = answers[i]
        key = (id(a.net), None if a.links_alive is None else a.links_alive.tobytes())
        topo = topos.get(key)
        if topo is None:
            topo = topos[key] = ref.Topology(a.net.links, a.links_alive)
        want = [(s, d, v) for s, d, v in a.flows if s != d and v > 0]
        res = a.result
        if res is None:
            faults += bool(want)
            continue
        got = [(f.src, f.dst, f.volume) for f in res.flows]
        if got != want or len(res.routes) != len(want) or a.paths is None or (
            len(a.paths) != len(want)
        ):
            faults += 1
            continue
        bad, cand_links = ref.check_candidates(topo, k, want, a.paths, res.routes)
        faults += bad
        if bad:
            continue
        vol = np.array([v for _, _, v in want], dtype=np.float64)
        cap = np.maximum(np.asarray(a.capacity, dtype=np.float64), ref.CAPACITY_FLOOR)
        on_route = [topo.path_links(list(r)) if len(r) else [] for r in res.routes]
        bw_gap = max(bw_gap, ref.bandwidth_gap(vol, cap, on_route, res.bandwidth, res.span))
        if j not in deep or any(not c for c in cand_links):
            continue  # a cut-off flow has no route to round or relax
        best = max(ref.rounding_span(cand_links, vol, cap),
                   ref.rounding_span(cand_links, vol, np.maximum(cap.astype(np.float32), 1e-9),
                                     np.float32))
        if not (best > accept and res.span > accept):
            span_gap = max(span_gap, res.span / best - 1.0)
        relax_wins += res.span < best * (1 - 1e-6)
        if a.m is not None:
            gap = ref.relaxation_gap(cand_links, vol, cap, a.m, a.relaxed, n_iters, accept)
            relax_gap = max(relax_gap, gap)
    return faults, bw_gap, relax_gap, span_gap, relax_wins


def compare(calls, placements, lane_results, seed: int, engine: dict) -> tuple[dict, dict]:
    """Numbers compared for one run (see the module docstring), and what the
    check saw besides."""
    rng = np.random.default_rng([seed % 2**64, 7])
    k, n_iters, accept = engine["k"], engine["n_iters"], engine["max_acceptable_span"]
    records = [r for _, res in lane_results for r in res.records]
    unfinished = sum(res.unfinished for _, res in lane_results)
    record_faults = sum(
        1 for r in records
        if not r.done or r.schedule_time < r.submit_time or r.finish_time < r.schedule_time
    )
    faults, bw_gap, relax_gap, span_gap, relax_wins = answer_numbers(calls, rng, k, n_iters, accept)
    numbers = {
        "unfinished": float(unfinished),
        "record_faults": float(record_faults),
        "time_faults": float(time_faults(lane_results)),
        "place_faults": float(place_faults(placements, rng)),
        "answer_faults": float(faults),
        "bw_gap": float(bw_gap),
        "relax_gap": float(relax_gap),
        "span_gap": float(span_gap),
        "link_overload": link_overload(lane_results),
    }
    return numbers, {"relax_wins": relax_wins, "placements": len(placements)}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit."""
    checks = {n: {"value": v, "limit": limits[n]} for n, v in numbers.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
