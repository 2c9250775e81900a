"""Arrivals ``poisson_tier``: Poisson job arrivals at ``lam`` jobs/s, each
job's camera drawn uniformly from the sensor tier (the first quarter, at
least 2, of ``sources``), each job carrying ``total_units`` stream units
jittered +-30%. A copy of ``repro.core.workloads.poisson_arrivals`` with
``source_nodes`` set as the program's node-chaos scenario sets it."""


def make(rng, n_jobs, sources, new_job, *, lam, total_units):
    tier = sources[: max(2, len(sources) // 4)]
    t, out = 0.0, []
    for _ in range(n_jobs):
        t += rng.exponential(1.0 / lam)
        job = new_job(int(tier[rng.randint(len(tier))]))
        out.append((t, job, total_units * rng.uniform(0.7, 1.3)))
    return out
