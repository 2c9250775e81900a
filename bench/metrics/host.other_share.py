"""Share of the window outside the engine's three phases: the event loop,
Algorithm 1 placement and the fleet driver's bookkeeping (the process is
single-threaded, so the phases and this share add up to the window)."""


def read(ctx):
    d = ctx["delta"]
    engine = (
        d["build_seconds"] + d["cache_seconds"] + d["dispatch_seconds"] + d["finalize_seconds"]
    )
    return 100.0 * (1.0 - engine / ctx["window_s"])
