"""The control and the planted faults of the ``correct`` comparison.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...] [--faults <name> ...]

Each run is a normal ``bench/run.py`` run (same warm-up, a short window,
the same reference check) with the timed path broken where it is produced.
The benchmark's own runs never import this file.

* ``control``: the configuration states no precision for its answers, so
  the control breaks a guarantee it does state: every flow gets its route's
  bottleneck capacity, as if it were alone on the network, instead of its
  Eq. 15 share (links can be oversubscribed);
* ``half``: every other answer, counted across dispatches, is left out;
* ``stale``: a dispatch hands back the previous dispatch's answers (the
  engine's state left unchanged);
* ``altered``: one flow's bandwidth in every answer is raised by 0.1%;
* ``relax_zero``: the relaxation (the kernel) returns no spread and span 0;
* ``relax_uniform``: the relaxation returns each flow spread evenly over its
  candidates, with that spread's span;
* ``no_sweeps``: rounding takes the relaxation's argmax path of each flow,
  without the start portfolio and its sweeps;
* ``first_path``: every flow is routed on its first candidate path;
* ``place_no_comm``: Algorithm 1 places tasks by compute time alone.

``OTFS_FAULTS`` break what only a cell with OTFS lanes can show:

* ``unreduced``: an admission leaves the residual capacity as it was (the
  scheduler's state left unchanged): every OTFS job is solved on the whole
  capacity, as if it ran alone, and jobs that run together oversubscribe
  the links they share.

``CHURN_FAULTS`` break what only a cell whose network fails can show, for a
configuration's own checks to catch (``bench/checks/``):

* ``deaf``: the scheduler never learns of a failure: its network keeps every
  link up, so jobs run on over dead links and nodes.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
FAULTS = ("control", "half", "stale", "altered", "relax_zero", "relax_uniform", "no_sweeps",
          "first_path", "place_no_comm")
OTFS_FAULTS = ("unreduced",)
CHURN_FAULTS = ("deaf",)


def _route_links(net, route):
    return [net.link_id(u, v) for u, v in zip(route[:-1], route[1:])]


def broken(fault):
    """The recorder's engine and scheduler classes with ``fault`` planted
    (built lazily: they subclass the recorder's, which import the program)."""
    import recorder
    from repro.core.allocation import allocate_greedy, flows_from_assignment
    from repro.core.graph import JobGraph

    Engine, Scheduler = recorder.RecordingEngine, recorder.RecordingScheduler

    class Control(Engine):
        def solve_many(self, net, flow_sets, *, capacities=None, **kw):
            out = super().solve_many(net, flow_sets, capacities=capacities, **kw)
            nets = net if isinstance(net, (list, tuple)) else [net] * len(out)
            caps = capacities if capacities is not None else [None] * len(out)
            for g, cap, res in zip(nets, caps, out):
                if res is None:
                    continue
                c = np.maximum(np.asarray(g.capacity if cap is None else cap, float), 1e-9)
                b = np.array(
                    [min(c[l] for l in _route_links(g, r)) if len(r) > 1 else 0.0
                     for r in res.routes]
                )
                load = np.zeros(len(c))
                for r, bw in zip(res.routes, b):
                    for l in _route_links(g, r):
                        load[l] += bw
                vol = np.array([f.volume for f in res.flows])
                with np.errstate(divide="ignore"):
                    res.span = float(np.max(np.where(b > 0, vol / np.maximum(b, 1e-300), np.inf)))
                res.bandwidth, res.link_load = b, load
            return out

    class Half(Engine):
        answered = 0

        def solve_many(self, net, flow_sets, **kw):
            out = super().solve_many(net, flow_sets, **kw)
            first = self.answered
            self.answered += len(out)
            return [r if (first + i) % 2 == 0 else None for i, r in enumerate(out)]

    class Stale(Engine):
        last = None

        def solve_many(self, net, flow_sets, **kw):
            out = super().solve_many(net, flow_sets, **kw)
            prev, self.last = self.last, out
            return prev if prev is not None and len(prev) == len(out) else out

    class Altered(Engine):
        def solve_many(self, net, flow_sets, **kw):
            out = super().solve_many(net, flow_sets, **kw)
            for res in out:
                if res is not None and len(res.bandwidth):
                    res.bandwidth = res.bandwidth.copy()
                    res.bandwidth[0] *= 1.001
            return out

    class RelaxZero(Engine):
        def relax(self, progs, n_real):
            return [(np.zeros_like(m), 0.0) for m, _ in super().relax(progs, n_real)]

    class RelaxUniform(Engine):
        def relax(self, progs, n_real):
            super().relax(progs, n_real)  # the kernel still runs
            out = []
            for p in progs:
                w = p.valid / p.valid.sum(axis=1, keepdims=True)
                load = np.einsum("i,ik,ikl->l", p.volumes, w, p.usage)
                out.append((w * p.volumes[:, None], float(np.max(load / p.capacity))))
            return out

    class NoSweeps(Engine):
        def solve_many(self, net, flow_sets, **kw):
            kw["refine"] = False
            return super().solve_many(net, flow_sets, **kw)

        def _use_fast_path(self, prog, refine):
            return False

    class FirstPath(NoSweeps):
        def relax(self, progs, n_real):
            out = super().relax(progs, n_real)
            first = []
            for p, (m, relaxed) in zip(progs, out):
                pick = np.zeros_like(m)
                pick[:, 0] = 1e-3  # argmax rounds every flow onto candidate 0
                first.append((m * 1e-6 + pick, relaxed))
            return first

    class PlaceNoComm(Scheduler):
        def _allocate(self, job, job_id):
            mem = self.net.mem_avail.copy()
            blind = JobGraph(job.tasks, [(u, v, 0.0) for u, v, _ in job.edges], name=job.name)
            alloc, _ = allocate_greedy(self.net, blind, job_id=job_id)
            alloc.job = job
            flows = flows_from_assignment(job, alloc.assignment, job_id) if alloc.feasible else []
            if self.log is not None and self.log.active:
                self.log.placements.append(recorder.Placement(self.net, mem, job, alloc))
            return alloc, flows

    class Unreduced(Scheduler):
        def __init__(self, net, *args, **kwargs):
            super().__init__(net, *args, **kwargs)
            # every read of the residual sees the whole capacity, every
            # write is dropped
            whole = property(lambda g: g.capacity.copy(), lambda g, value: None)
            net.__class__ = type("WholeCapacity", (type(net),), {"residual": whole})

    class Deaf(Scheduler):
        def __init__(self, net, *args, **kwargs):
            super().__init__(net, *args, **kwargs)
            net.fail_link = lambda u, v: False  # fail_node goes through it too

    engines = {"control": Control, "half": Half, "stale": Stale, "altered": Altered,
               "relax_zero": RelaxZero, "relax_uniform": RelaxUniform, "no_sweeps": NoSweeps,
               "first_path": FirstPath}
    if fault == "place_no_comm":
        return Engine, PlaceNoComm
    if fault == "deaf":
        return Engine, Deaf
    if fault == "unreduced":
        return Engine, Unreduced
    return engines[fault], Scheduler


def run_broken(fault: str, argv, **main_kw) -> dict:
    """One ``run.main`` with ``fault`` planted in the engine or scheduler."""
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import recorder
    import run

    saved = recorder.RecordingEngine, recorder.RecordingScheduler
    recorder.RecordingEngine, recorder.RecordingScheduler = broken(fault)
    try:
        return run.main(argv, **main_kw)
    finally:
        recorder.RecordingEngine, recorder.RecordingScheduler = saved


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="+", default=["control"], choices=FAULTS + OTFS_FAULTS + CHURN_FAULTS)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    for fault in args.faults:
        for seed in args.seeds:
            out = run_broken(
                fault,
                ["--workload", args.workload, "--seed", str(seed), "--seconds", str(args.seconds)],
            )
            over = {n: c["value"] for n, c in out["checks"].items() if c["value"] > c["limit"]}
            print(f"{fault} {args.workload} seed {seed} correct {out['correct']} over {over}",
                  flush=True)


if __name__ == "__main__":
    main()
