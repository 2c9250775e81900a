"""Share of the window in Yen's k-shortest-paths enumeration on path-cache
misses (``EngineStats.paths_seconds``, the program's ``engine/paths``
spans), in program builds and candidate-link lookups alike. Nothing where
the program does not count it."""


def read(ctx):
    seconds = ctx["delta"].get("paths_seconds")
    return None if seconds is None else 100.0 * seconds / ctx["window_s"]
