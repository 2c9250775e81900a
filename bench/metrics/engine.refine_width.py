"""Rounding chains swept together per batched sweep
(``EngineStats.refine_chains / refine_batches``): how wide the start
portfolios' one-sweep-per-shape batching runs. Nothing where the program
does not count batches, or rounded nothing."""


def read(ctx):
    d = ctx["delta"]
    batches = d.get("refine_batches")
    return d["refine_chains"] / batches if batches else None
