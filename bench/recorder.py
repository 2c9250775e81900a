"""What the benchmark keeps of the timed path: every answer the engine gives
(for the reference check and the kernel's operation count), every Algorithm 1
placement of a lane whose network holds still, every admission latency
(for the tails) with its lane's policy, on lanes with a migration SLO the
simulated time of each migration round (an event of the lane, at which jobs
may be admitted), and on lanes whose network churns the scheduler's view of
which links are up at the end of each event. All hang off the program's
hooks: a ``JRBAEngine`` subclass around ``solve_many``, ``build`` and the
relaxation, an ``OnlineScheduler`` subclass around its allocator, the
``metrics`` object a scheduler reports to, and the (disabled) tracer it
opens its spans on."""
from __future__ import annotations

from contextlib import nullcontext

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import JRBAEngine, OnlineScheduler
from repro.obs import Tracer


class LatencyRecorder:
    """The ``metrics`` hook of a lane's scheduler: keeps each admission's
    arrival-to-decision latency verbatim while ``active``, in ``samples``.
    ``lane(policy)`` is the hook of one lane that also keeps its samples
    apart under its policy, in ``by_policy``."""

    enabled = True

    def __init__(self) -> None:
        self.samples: list[float] = []
        self.by_policy: dict[str, list[float]] = {}
        self.active = False

    def inc(self, name: str, value: float = 1.0) -> None:
        pass

    def observe(self, name: str, value: float) -> None:
        if self.active and name == "event_latency_s":
            self.samples.append(value)

    def lane(self, policy: str) -> "LaneLatency":
        return LaneLatency(self, self.by_policy.setdefault(policy, []))


class LaneLatency:
    """The ``metrics`` hook of one lane's scheduler (``LatencyRecorder.lane``)."""

    enabled = True

    def __init__(self, recorder: LatencyRecorder, samples: list) -> None:
        self.recorder = recorder
        self.samples = samples

    def inc(self, name: str, value: float = 1.0) -> None:
        pass

    def observe(self, name: str, value: float) -> None:
        if self.recorder.active and name == "event_latency_s":
            self.recorder.samples.append(value)
            self.samples.append(value)


class Answer:
    """One answered solve: the plain data the reference needs. ``paths`` are
    the candidate node paths of each flow the program solved over, ``m`` and
    ``relaxed`` the relaxation's path volumes and span (``None`` where the
    engine took its analytic single-flow path)."""

    __slots__ = ("net", "links_alive", "capacity", "flows", "result", "paths", "m", "relaxed")

    def __init__(self, net, capacity, flows, result, prog, relaxation):
        self.net = net
        alive = net.link_alive
        self.links_alive = None if alive.all() else alive.copy()
        self.capacity = np.array(capacity if capacity is not None else net.capacity)
        self.flows = [(f.src, f.dst, f.volume) for f in flows]
        self.result = result
        self.paths = None if prog is None else prog.paths[: prog.n_real]
        self.m, self.relaxed = (None, None) if relaxation is None else relaxation
        if self.m is not None:
            self.m = np.asarray(self.m[: prog.n_real], dtype=np.float64)


class Call:
    """One ``solve_many`` call: its answers and the relaxation steps it ran."""

    __slots__ = ("answers", "steps")

    def __init__(self, answers, steps):
        self.answers = answers
        self.steps = steps


class RecordingEngine(JRBAEngine):
    """``JRBAEngine`` that logs each ``solve_many`` call while ``recording``
    and, with ``annotate``, wraps ``solve_many`` and ``build`` in profiler
    annotations so the trace can say what the host did in each device gap."""

    def __init__(self, *args, annotate: bool = False, **kwargs) -> None:
        super().__init__(*args, **kwargs)
        self.annotate = annotate
        self.recording = False
        self.calls: list[Call] = []
        self._built = None
        self._relaxed = None

    def build(self, net, flows, *, capacity=None):
        with TraceAnnotation("bench/build") if self.annotate else nullcontext():
            prog = super().build(net, flows, capacity=capacity)
        if self._built is not None:
            self._built.append(prog)
        return prog

    def relax(self, progs, n_real):
        """The relaxation of one same-shape group (the planted faults of
        ``control.py`` replace it)."""
        return super()._relax_group(progs, n_real)

    def _relax_group(self, progs, n_real=None):
        out = self.relax(progs, n_real)
        if self._relaxed is not None:
            for p, r in zip(progs, out):
                self._relaxed[id(p)] = r
        return out

    def solve_many(self, net, flow_sets, *, capacities=None, water_filling=False, refine=True):
        steps0 = self.stats.solver_steps
        if self.recording:
            self._built, self._relaxed = [], {}
        try:
            with TraceAnnotation("bench/solve_many") if self.annotate else nullcontext():
                out = super().solve_many(
                    net, flow_sets, capacities=capacities, water_filling=water_filling,
                    refine=refine,
                )
            if self.recording:
                n = len(flow_sets)
                nets = [net] * n if not isinstance(net, (list, tuple)) else net
                caps = capacities if capacities is not None else [None] * n
                progs = self._built
                self.calls.append(
                    Call(
                        [
                            Answer(g, c, fs, r, p, None if p is None else self._relaxed.get(id(p)))
                            for g, c, fs, r, p in zip(nets, caps, flow_sets, out, progs)
                        ],
                        self.stats.solver_steps - steps0,
                    )
                )
        finally:
            self._built = self._relaxed = None
        return out


class Placement:
    """One Algorithm 1 call: the network state it read and what it chose."""

    __slots__ = ("net", "capacity", "link_alive", "mem", "job", "assignment", "feasible")

    def __init__(self, net, mem, job, alloc):
        self.net = net
        self.capacity = net.capacity.copy()
        self.link_alive = net.link_alive.copy()
        self.mem = mem
        self.job = job
        self.assignment = np.array(alloc.assignment)
        self.feasible = bool(alloc.feasible)


class LaneClock(Tracer):
    """A disabled tracer, as a scheduler's default, that keeps the simulated
    time ``t`` of each ``migrate/round`` span the event loop opens and, with
    ``net``, the network's ``link_alive`` at the end of each ``event/*``
    span, with that event's time."""

    def __init__(self, net=None) -> None:
        super().__init__(enabled=False)
        self.net = net
        self.times: list[float] = []
        self.views: list[tuple[float, np.ndarray]] = []
        self._now = 0.0

    def span(self, name, **kwargs):
        if name == "migrate/round":
            self.times.append(kwargs["t"])
        return super().span(name, **kwargs)

    def begin(self, name, **kwargs):
        if name.startswith("event/"):
            self._now = kwargs["t"]
        return super().begin(name, **kwargs)

    def end(self, name, **kwargs):
        if self.net is not None and name.startswith("event/"):
            self.views.append((self._now, self.net.link_alive.copy()))
        return super().end(name, **kwargs)


class RecordingScheduler(OnlineScheduler):
    """``OnlineScheduler`` that logs its Algorithm 1 calls into ``log`` while
    ``log.active``. Lanes whose network changes under them (``churned``) are
    not logged: Algorithm 1's bandwidth term there depends on which path it
    pinned before the change. With a ``stall_budget``, or on a lane that
    churns, its tracer is a ``LaneClock``: the time check reads its
    ``migrate_times``, a configuration's own checks its ``views``."""

    def __init__(self, net, *args, log=None, churned=False, **kwargs) -> None:
        if (churned or kwargs.get("stall_budget") is not None) and kwargs.get("tracer") is None:
            kwargs["tracer"] = LaneClock(net if churned else None)
        super().__init__(net, *args, **kwargs)
        self.log = log

    @property
    def migrate_times(self) -> list[float]:
        return self.tracer.times if isinstance(self.tracer, LaneClock) else []

    @property
    def views(self) -> list:
        return self.tracer.views if isinstance(self.tracer, LaneClock) else []

    def _allocate(self, job, job_id):
        mem = self.net.mem_avail.copy()
        alloc, flows = super()._allocate(job, job_id)
        if self.log is not None and self.log.active:
            self.log.placements.append(Placement(self.net, mem, job, alloc))
        return alloc, flows


class PlacementLog:
    def __init__(self) -> None:
        self.placements: list[Placement] = []
        self.active = False
