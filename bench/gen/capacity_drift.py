"""Churn ``capacity_drift``: every ``dt`` s a random ``frac`` of the links
take one step of a bounded multiplicative random walk around their base
capacity (log-step ``sigma``, bounds ``lo`` and ``hi``), up to ``t_end``. A
copy of ``repro.core.scenarios.capacity_drift_trace``."""
import numpy as np

from repro.core.scenarios import ChurnOp, ChurnStep


def make(net, rng, *, t_end, dt, sigma, lo, hi, frac):
    walk = np.ones(len(net.links))
    steps, t = [], dt
    while t < t_end:
        picked = np.flatnonzero(rng.uniform(size=len(net.links)) < frac)
        ops = []
        for l in picked:
            walk[l] = float(np.clip(walk[l] * np.exp(sigma * rng.normal()), lo, hi))
            ops.append(ChurnOp("capacity", link=net.links[l],
                               capacity=float(net.base_capacity[l] * walk[l])))
        if ops:
            steps.append(ChurnStep(t, tuple(ops)))
        t += dt
    return steps
