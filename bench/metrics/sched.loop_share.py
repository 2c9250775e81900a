"""Share of the window inside the lanes' event loops outside Algorithm 1:
each pass's ``host.lane_step_seconds`` (the program's ``lane/step`` spans,
every resume of a lane's stepper) less its ``host.alloc_seconds``, summed
over the window's passes. Nothing where the program does not report it."""


def read(ctx):
    hosts = [s.get("host") for s in ctx["telemetry"]]
    if not hosts or None in hosts:
        return None
    loop = sum(h["lane_step_seconds"] - h["alloc_seconds"] for h in hosts)
    return 100.0 * loop / ctx["window_s"]
