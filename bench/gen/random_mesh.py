"""Topology ``random_mesh``: ENTS (arXiv:2210.07842) Sec. VI-A4 random
connected edge mesh: a random spanning tree, then random extra links up to
the average degree; link bandwidth ~ N(mean, var), clipped at a tenth of the
mean; each node drawn from one of the Tab. I hardware classes (power and
memory listed pairwise). A copy of ``repro.core.graph.random_edge_network``."""
import itertools

import numpy as np

from repro.core import NetworkGraph


def make(rng, *, n_nodes, avg_degree, mean_bandwidth, bandwidth_var, power_choices,
         mem_choices):
    links = set()
    perm = rng.permutation(n_nodes)
    for i in range(1, n_nodes):
        u = int(perm[i])
        v = int(perm[rng.randint(i)])
        links.add((min(u, v), max(u, v)))
    target = int(avg_degree * n_nodes / 2)
    pairs = list(itertools.combinations(range(n_nodes), 2))
    rng.shuffle(pairs)
    for u, v in pairs:
        if len(links) >= target:
            break
        links.add((u, v))
    bws = np.clip(
        rng.normal(mean_bandwidth, np.sqrt(bandwidth_var), size=len(links)),
        0.1 * mean_bandwidth,
        None,
    )
    klass = rng.randint(len(power_choices), size=n_nodes)
    power = [float(power_choices[k]) for k in klass]
    mem = [float(mem_choices[k]) for k in klass]
    return NetworkGraph(power, mem, [(u, v, b) for (u, v), b in zip(sorted(links), bws)])
