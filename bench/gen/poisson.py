"""Arrivals ``poisson``: steady Poisson job arrivals at ``lam`` jobs/s, each
job from a camera drawn uniformly among ``sources`` and carrying
``total_units`` stream units jittered +-30%. A copy of
``repro.core.workloads.poisson_arrivals``."""


def make(rng, n_jobs, sources, new_job, *, lam, total_units):
    t, out = 0.0, []
    for _ in range(n_jobs):
        t += rng.exponential(1.0 / lam)
        job = new_job(int(sources[rng.randint(len(sources))]))
        out.append((t, job, total_units * rng.uniform(0.7, 1.3)))
    return out
