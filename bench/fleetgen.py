"""The benchmark's one traffic generator: builds a pass of fleet lanes from a
configuration file (the deployment: one cluster's topology, its job and the
engine) and a traffic file (the driver, how many clusters and jobs a pass
carries, their arrivals and churn), both plain JSON.

Every kind a file names (``"kind": "random_mesh"``, ``"poisson"``,
``"capacity_drift"``, ...) is a file of its own, ``bench/gen/<kind>.py``,
whose ``make`` builds it. A new topology, job, arrival process or churn is a
new file there; nothing here changes. The kinds copy the program's own
generators, so that no later change to the program can move the yardstick,
and build the program's input types and nothing else of it.

Lane ``i`` of pass ``j`` draws its network, jobs, arrivals and churn from
``(--seed, j, i)``: every pass and every seed is new data, of the same sizes.
A traffic with an ``arrival_deck`` instead deals each pass the same
arrival processes (times and stream units, drawn from the deck), in an
order drawn from ``(--seed, j)``: where a pass holds few lanes, bursts of
other sizes would otherwise change the work from seed to seed. The
warm-up builds its own networks from pass 0, which no window measures.

A traffic's ``churn`` is one ``{"every": n, "kind": ..., **params}`` object
or a list of them: each lands on every ``n``-th lane, drawn in turn from the
lane's churn seed, and a lane's steps run in time order. A traffic may also
hold a ``"small"`` object (``lanes``, ``jobs_per_lane``, ``warm``): the size
of the benchmark's CPU tests, which a chip run never reads.

A configuration's ``scheduler`` block (``{"<policy>": {setting: value}}``)
sets what a deployment chooses for every lane of that policy: the keys of
``SETTINGS``, on the policies listed there. The program's reference
switches are not a deployment's, and no cell selects a path by a flag.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

from repro.core import OnlineScheduler
from repro.fleet import FleetSim

GEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "gen")
_KINDS: dict = {}
# scheduler settings a configuration may state, and the policies that take
# each: the migration SLO of OTFS lanes (``OnlineScheduler(stall_budget=)``)
SETTINGS = {"stall_budget": ("OTFS",)}


def kind(name: str):
    """``bench/gen/<name>.py``'s ``make``."""
    if name not in _KINDS:
        path = os.path.join(GEN, name + ".py")
        if not os.path.exists(path):
            raise KeyError(f"no generator bench/gen/{name}.py")
        spec = importlib.util.spec_from_file_location("bench_gen_" + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _KINDS[name] = mod.make
    return _KINDS[name]


def make(spec: dict, *args, **kwargs):
    """Build ``spec`` (a ``{"kind": ..., **params}`` object) from its kind."""
    params = dict(spec)
    return kind(params.pop("kind"))(*args, **kwargs, **params)


def scheduler_settings(config: dict) -> dict:
    """The configuration's ``scheduler`` block, ``{policy: {setting: value}}``;
    a setting ``SETTINGS`` does not give that policy is an error."""
    block = config.get("scheduler", {})
    for policy, settings in block.items():
        for key in settings:
            if policy not in SETTINGS.get(key, ()):
                raise ValueError(
                    f"the configuration sets {key!r} on {policy} lanes; a deployment states"
                    f" only {SETTINGS}"
                )
    return block


def lane_seeds(seed: int, pass_index: int, lane: int) -> list[int]:
    """Topology, job, arrival and churn seeds of one lane of one pass."""
    ss = np.random.SeedSequence([seed % 2**64, pass_index, lane])
    return [int(x) for x in ss.generate_state(4)]


def network(config: dict, rng):
    """One cluster of the configuration. A cluster whose nodes cannot hold
    the job even when empty is drawn again: no scheduler could place its
    jobs, which would wait for ever."""
    import reference

    job = make(config["job"], np.random.RandomState(0), 0)
    tasks = [(t.workload, t.mem, t.pinned_node) for t in job.tasks]
    while True:
        net = make(config["topology"], rng)
        if reference.algorithm1(net.power, net.mem_max, net.links, net.capacity,
                                net.link_alive, tasks, job.edges) is not None:
            return net


def sources(net) -> list[int]:
    """Nodes that can host a camera: those with memory."""
    return [n for n in range(net.n_nodes) if net.mem_max[n] >= 0.5]


class Lane:
    """What the reference needs to know of one lane beside its result: its
    nodes' power, its policy, its links and their capacities before any
    churn, the churn steps it ran (``churn``) and their times, and what the
    harness fills in after the pass from the lane's scheduler: the times of
    its migration rounds (``migrate_times``: events of the lane that no input
    names) and, on a lane that churns, the scheduler's view of which links
    are up at the end of each event (``views``: ``(time, mask)``)."""

    __slots__ = ("power", "policy", "links", "capacity", "churn", "churn_times",
                 "migrate_times", "views")

    def __init__(self, net, policy, churn):
        self.power = np.array(net.power, dtype=np.float64)
        self.policy = policy
        self.links = list(net.links)
        self.capacity = np.array(net.capacity, dtype=np.float64)
        self.churn = churn
        self.churn_times = [s.time for s in churn]
        self.migrate_times: list[float] = []
        self.views: list = []


def _churn(specs, lane: int, net, rng, t_end: float):
    """The churn steps of lane ``lane``: every spec whose ``every`` divides
    the lane's index, drawn from ``rng`` in turn, in time order (None where
    no spec lands)."""
    if isinstance(specs, dict):
        specs = [specs]
    steps, landed = [], False
    for spec in specs or ():
        if lane % spec["every"] == 0:
            landed = True
            steps += make({k: v for k, v in spec.items() if k != "every"}, net, rng, t_end=t_end)
    return sorted(steps, key=lambda s: s.time) if landed else None


def build_pass(engine, config: dict, traffic: dict, seed: int, pass_index: int,
               make_scheduler=None) -> tuple[list[FleetSim], list[Lane]]:
    """Fresh lanes for one pass, all on ``engine``: lane ``i`` runs policy
    ``policies[i % len(policies)]``, and every ``churn.every``-th lane
    carries the traffic's churn. ``make_scheduler(net, policy, churned)``
    builds each lane's scheduler (default: an ``OnlineScheduler`` with the
    configuration's ``scheduler`` settings)."""
    if make_scheduler is None:
        settings = scheduler_settings(config)

        def make_scheduler(net, policy, churned):
            return OnlineScheduler(net, policy, k_paths=engine.k, jrba_iters=engine.n_iters,
                                   engine=engine, **settings.get(policy, {}))
    policies = traffic["policies"]
    deck = traffic.get("arrival_deck")
    if deck is not None:
        order = np.random.default_rng([seed % 2**64, pass_index]).permutation(traffic["lanes"])
    sims, lanes = [], []
    for i in range(traffic["lanes"]):
        s_topo, s_job, s_arr, s_churn = lane_seeds(seed, pass_index, i)
        if deck is not None:
            s_arr = lane_seeds(deck, 0, int(order[i]))[2]
        net = network(config, np.random.RandomState(s_topo))
        job_rng = np.random.RandomState(s_job)

        def new_job(source, job_rng=job_rng):
            return make(config["job"], job_rng, source)

        arrivals = make(traffic["arrivals"], np.random.RandomState(s_arr),
                        traffic["jobs_per_lane"], sources(net), new_job)
        policy = policies[i % len(policies)]
        t_end = max((t for t, _, _ in arrivals), default=0.0) * 1.25 + 10.0
        events = _churn(traffic.get("churn"), i, net, np.random.RandomState(s_churn), t_end)
        sched = make_scheduler(net, policy, events is not None)
        sims.append(FleetSim(sched, arrivals, name=policy, network_events=events))
        lanes.append(Lane(net, policy, events or []))
    return sims, lanes
