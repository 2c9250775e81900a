"""Churn ``node_failure``: whole-node outages (every link of the node goes
down) on ``n_nodes`` nodes drawn at random, in fail/recover cycles (mean up
``mtbf`` s, mean down ``mttr`` s) up to ``t_end``; with ``permanent`` each
node dies once and never comes back. With ``spare_sensors`` the nodes that
carry cameras (the sensor tier of ``poisson_tier``: the first quarter, at
least 2, of the nodes with memory) are kept out of the blast: a job whose
camera dies has lost its input, a failure no scheduler can mend. A copy of
``repro.core.scenarios.node_failure_trace`` (``spare_sensors`` stands for its
``nodes`` argument, as the program's node-chaos scenario sets it)."""
from repro.core.scenarios import ChurnOp, ChurnStep


def make(net, rng, *, t_end, n_nodes, mtbf, mttr, permanent=False, spare_sensors=False):
    pool = list(range(net.n_nodes))
    if spare_sensors:
        hosts = [n for n in pool if net.mem_max[n] >= 0.5]
        tier = set(hosts[: max(2, len(hosts) // 4)])
        pool = [n for n in pool if n not in tier]
    chosen = rng.choice(len(pool), size=min(n_nodes, len(pool)), replace=False)
    steps = []
    for node in sorted(pool[int(c)] for c in chosen):
        t = rng.exponential(mtbf)
        while t < t_end:
            down = rng.exponential(mttr)
            steps.append(ChurnStep(t, (ChurnOp("fail_node", node=node),)))
            if permanent:
                break
            steps.append(ChurnStep(t + down, (ChurnOp("recover_node", node=node),)))
            t += down + rng.exponential(mtbf)
    return steps
