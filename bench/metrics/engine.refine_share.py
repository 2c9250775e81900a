"""Share of the window inside the rounding start portfolio
(``EngineStats.refine_seconds``, the program's ``engine/refine`` spans):
the best-response chains of ``_round_and_refine``, a part of
``engine.finalize_share``. Nothing where the program does not count it."""


def read(ctx):
    seconds = ctx["delta"].get("refine_seconds")
    return None if seconds is None else 100.0 * seconds / ctx["window_s"]
