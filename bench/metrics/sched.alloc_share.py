"""Share of the window inside Algorithm 1 (``host.alloc_seconds`` of each
pass's telemetry, the program's ``sched/alloc`` spans), summed over the
window's passes. Nothing where the program does not report it."""


def read(ctx):
    hosts = [s.get("host") for s in ctx["telemetry"]]
    if not hosts or None in hosts:
        return None
    return 100.0 * sum(h["alloc_seconds"] for h in hosts) / ctx["window_s"]
