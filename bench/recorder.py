"""What the benchmark keeps of the timed path: every answer the engine gives
(for the reference check and the kernel's operation count), every Algorithm 1
placement of a lane whose network holds still, and every admission latency
(for the tails). All hang off the program's hooks: a ``JRBAEngine`` subclass
around ``solve_many``, ``build`` and the relaxation, an ``OnlineScheduler``
subclass around its allocator, and the ``metrics`` object a scheduler reports
to."""
from __future__ import annotations

from contextlib import nullcontext

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import JRBAEngine, OnlineScheduler


class LatencyRecorder:
    """The ``metrics`` hook of every lane's scheduler: keeps each admission's
    arrival-to-decision latency verbatim while ``active``."""

    enabled = True

    def __init__(self) -> None:
        self.samples: list[float] = []
        self.active = False

    def inc(self, name: str, value: float = 1.0) -> None:
        pass

    def observe(self, name: str, value: float) -> None:
        if self.active and name == "event_latency_s":
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

    __slots__ = ("net", "capacity", "mem", "job", "assignment", "feasible")

    def __init__(self, net, mem, job, alloc):
        self.net = net
        self.capacity = net.capacity.copy()
        self.mem = mem
        self.job = job
        self.assignment = np.array(alloc.assignment)
        self.feasible = bool(alloc.feasible)


class RecordingScheduler(OnlineScheduler):
    """``OnlineScheduler`` that logs its Algorithm 1 calls into ``log`` while
    ``log.active``. Lanes whose network changes under them are not logged:
    Algorithm 1's bandwidth term there depends on which path it pinned
    before the change."""

    def __init__(self, *args, log=None, **kwargs) -> None:
        super().__init__(*args, **kwargs)
        self.log = log

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
