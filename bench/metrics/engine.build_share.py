"""Share of the window spent building JRBA programs: Yen's candidate paths
and program tensors (``EngineStats.build_seconds``) and program-cache
replays (``cache_seconds``)."""


def read(ctx):
    d = ctx["delta"]
    return 100.0 * (d["build_seconds"] + d["cache_seconds"]) / ctx["window_s"]
