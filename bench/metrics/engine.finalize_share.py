"""Share of the window spent rounding relaxations and recovering Eq. 15
bandwidths on the host (``EngineStats.finalize_seconds``)."""


def read(ctx):
    return 100.0 * ctx["delta"]["finalize_seconds"] / ctx["window_s"]
