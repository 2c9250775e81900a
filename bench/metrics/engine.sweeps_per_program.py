"""Best-response passes over a program's flows per program rounded through
the start portfolio (``EngineStats.refine_sweeps / refined_programs``): the
rounding work one answer costs. Nothing where the program does not count
it, or rounded nothing."""


def read(ctx):
    d = ctx["delta"]
    programs = d.get("refined_programs")
    return d["refine_sweeps"] / programs if programs else None
