"""Check ``outage``: no job makes progress over a dead link or node.

The guarantee of a deployment whose network fails under its jobs: a flow
runs only over links that are up, so a job one of whose flows has no live
path (a node under it down, or its nodes cut apart) makes no progress until
a recovery or a migration lets it go on. The check replays each lane's churn
steps (``fleetgen.Lane.churn``: the input, not the program's view of it) on a
plain per-link liveness mask, as the steps define it: ``fail`` and
``recover`` set one link, ``fail_node`` takes down every live link of the
node, ``recover_node`` brings back every dead one, ``capacity`` changes no
liveness; steps of one instant apply in their order.

* ``outage_view_faults``: events of a lane at whose end the scheduler's own
  network (``fleetgen.Lane.views``: its ``link_alive`` as the event leaves
  it) says other links are up than the steps do at that instant (before,
  between or after the steps of that instant). Every solve and every
  re-route reads that network, so a scheduler that misses a failure, or a
  recovery, is caught at its next event, whether or not the link is back
  before its jobs finish;
* ``outage_route_faults``: finished jobs whose last routes cross a link that
  is down just before their finish (a failure on a route makes the job pick
  new routes or stall, so the routes a job ends on are up);
* ``outage_time_faults``: finished jobs that never migrated and finished
  sooner than a job can that stands still while one of its flows has no
  live path: their run, admission to finish, is shorter than the time their
  flows were cut plus their units at the compute speed of their placement
  (the busiest node's work over its power, the least time a unit takes at
  any bandwidth). A migrated job's flows changed on the way, which the
  record does not keep.
"""
from __future__ import annotations

import bisect
import collections

import numpy as np

import reference as ref

LIMITS = {
    # exact: the scheduler's network is the input's, event by event
    "outage_view_faults": 0,
    # exact: one route over a dead link is a wrong answer
    "outage_route_faults": 0,
    # exact: a job that outran its outages moved data over a dead path
    "outage_time_faults": 0,
}
# relative slack of the time comparison, the event loop's own
RTOL = 1e-9


def _key(u, v):
    return (u, v) if u < v else (v, u)


class Liveness:
    """One lane's link liveness over time: ``times[i]`` is the instant of
    step ``i`` (``-inf`` for the state before any), ``masks[i]`` the links
    up after it."""

    def __init__(self, lane):
        self.link_id = {_key(u, v): l for l, (u, v) in enumerate(lane.links)}
        incident = collections.defaultdict(list)
        for l, (u, v) in enumerate(lane.links):
            incident[u].append(l)
            incident[v].append(l)
        alive = np.ones(len(lane.links), dtype=bool)
        self.times, self.masks = [-np.inf], [alive.copy()]
        for step in lane.churn:
            for op in step.ops:
                if op.kind in ("fail", "recover"):
                    alive[self.link_id[_key(*op.link)]] = op.kind == "recover"
                elif op.kind in ("fail_node", "recover_node"):
                    alive[incident[op.node]] = op.kind == "recover_node"
            self.times.append(step.time)
            self.masks.append(alive.copy())
        self.links = lane.links
        self._parts: dict = {}

    def before(self, t: float) -> int:
        """Index of the state in force just before ``t``."""
        return bisect.bisect_left(self.times, t) - 1

    def at(self, t: float) -> list:
        """The states in force at instant ``t``: just before it, between its
        steps, and after them."""
        return self.masks[self.before(t): bisect.bisect_right(self.times, t)]

    def parts(self, i: int) -> dict:
        """Connected component of every node in state ``i``."""
        if i not in self._parts:
            adj = collections.defaultdict(list)
            for l, (u, v) in enumerate(self.links):
                if self.masks[i][l]:
                    adj[u].append(v)
                    adj[v].append(u)
            part: dict = {}
            for s in {n for uv in self.links for n in uv}:
                if s in part:
                    continue
                part[s], stack = s, [s]
                while stack:
                    for v in adj[stack.pop()]:
                        if v not in part:
                            part[v] = s
                            stack.append(v)
            self._parts[i] = part
        return self._parts[i]

    def cut_time(self, pairs, start: float, end: float) -> float:
        """Seconds of ``[start, end]`` in which some node pair of ``pairs``
        has no live path."""
        cut = 0.0
        for i in range(self.before(start), len(self.times)):
            lo = max(start, self.times[i])
            hi = min(end, self.times[i + 1] if i + 1 < len(self.times) else np.inf)
            if hi <= lo:
                if self.times[i] >= end:
                    break
                continue
            part = self.parts(i)
            if any(part.get(s, s) != part.get(d, d) for s, d in pairs):
                cut += hi - lo
        return cut


def compare(ctx) -> dict:
    view_faults = route_faults = time_faults = 0
    for lane, result in ctx["lane_results"]:
        if not lane.churn:
            continue
        live = Liveness(lane)
        for t, view in lane.views:
            view_faults += not any(np.array_equal(view, mask) for mask in live.at(t))
        for r in result.records:
            if not r.done:
                continue
            mask = live.masks[live.before(r.finish_time)]
            ids = [live.link_id.get(_key(u, v)) for route in r.routes
                   for u, v in zip(route[:-1], route[1:])]
            if any(l is None or not mask[l] for l in ids):
                route_faults += 1
                continue
            if r.migrations:
                continue
            pairs = [(f.src, f.dst) for f in r.flows if f.src != f.dst]
            cut = live.cut_time(pairs, r.schedule_time, r.finish_time)
            place = [int(x) for x in r.alloc.assignment]
            unit = ref.job_span(lane.power, place, [t.workload for t in r.job.tasks], [], [])
            least = cut + r.total_units * unit
            if r.finish_time - r.schedule_time < least * (1 - RTOL) - RTOL:
                time_faults += 1
    return {"outage_view_faults": float(view_faults), "outage_route_faults": float(route_faults),
            "outage_time_faults": float(time_faults)}
