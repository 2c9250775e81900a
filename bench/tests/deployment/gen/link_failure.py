"""Churn ``link_failure``: exponential fail/recover cycles on ``n_links``
links drawn at random: each alternates up (mean ``mtbf`` s) and down (mean
``mttr`` s) phases up to ``t_end``, and every failure comes with its
recovery; with ``permanent`` each link fails once and stays down. A copy of
``repro.core.scenarios.link_failure_trace``."""
from repro.core.scenarios import ChurnOp, ChurnStep


def make(net, rng, *, t_end, n_links, mtbf, mttr, permanent=False):
    chosen = rng.choice(len(net.links), size=min(n_links, len(net.links)), replace=False)
    steps = []
    for l in sorted(int(c) for c in chosen):
        link = net.links[l]
        t = rng.exponential(mtbf)
        while t < t_end:
            down = rng.exponential(mttr)
            steps.append(ChurnStep(t, (ChurnOp("fail", link=link),)))
            if permanent:
                break
            steps.append(ChurnStep(t + down, (ChurnOp("recover", link=link),)))
            t += down + rng.exponential(mtbf)
    return steps
