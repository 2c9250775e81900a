"""Arrivals ``mmpp``: a two-state Markov-modulated Poisson process (flash
crowds): ``lam_base`` jobs/s in quiet phases of mean ``quiet_dwell`` s,
``lam_burst`` in bursts of mean ``burst_dwell`` s. Cameras and stream units
as in ``poisson``. A copy of ``repro.core.workloads.poisson_burst_arrivals``."""


def make(rng, n_jobs, sources, new_job, *, lam_base, lam_burst, burst_dwell, quiet_dwell,
         total_units):
    out, t, bursting = [], 0.0, False
    phase_end = rng.exponential(quiet_dwell)
    while len(out) < n_jobs:
        lam = lam_burst if bursting else lam_base
        dt = rng.exponential(1.0 / lam)
        if t + dt >= phase_end:
            t = phase_end
            bursting = not bursting
            phase_end = t + rng.exponential(burst_dwell if bursting else quiet_dwell)
            continue
        t += dt
        job = new_job(int(sources[rng.randint(len(sources))]))
        out.append((t, job, total_units * rng.uniform(0.7, 1.3)))
    return out
