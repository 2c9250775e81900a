"""Share of the window inside the relaxation dispatch
(``EngineStats.dispatch_seconds``: upload, the kernel's chunk loop, and the
wait for its result)."""


def read(ctx):
    return 100.0 * ctx["delta"]["dispatch_seconds"] / ctx["window_s"]
