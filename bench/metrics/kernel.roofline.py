"""The Pallas congestion kernel's share of its roofline: the least time the
chip could take for the relaxations the window ran (``bench/roofline.py``:
operations and bytes from each program's flows, paths, touched links and
steps), over the device time of the kernel's events in the trace.

Steps per program are approximated: the engine counts relaxation steps per
``solve_many`` call, so each program of a call is given the call's mean.
Single-flow programs take the engine's analytic path and never reach the
kernel, so they are left out."""
from roofline import least_time


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["kernel_s"]:
        return None
    programs = []
    for call in ctx["calls"]:
        solved = [
            a.result for a in call.answers if a.result is not None and len(a.result.flows) >= 2
        ]
        if not solved or not call.steps:
            continue
        steps = call.steps / len(solved)
        for res in solved:
            programs.append((len(res.flows), ctx["k"], int(res.candidate_links.sum()), steps))
    if not programs:
        return None
    peaks = ctx["peaks"]
    seconds, bound = least_time(
        programs, ctx["n_iters"], peaks["flops_per_s"], peaks["bytes_per_s"]
    )
    ctx["roofline_bound"] = bound
    return 100.0 * seconds / trace["kernel_s"]
