"""Share of the window staging relaxations on the host
(``EngineStats.stage_seconds``, the program's ``engine/stage`` spans):
stacking each group's operands and uploading them, a part of
``engine.dispatch_share``. Nothing where the program does not count it."""


def read(ctx):
    seconds = ctx["delta"].get("stage_seconds")
    return None if seconds is None else 100.0 * seconds / ctx["window_s"]
