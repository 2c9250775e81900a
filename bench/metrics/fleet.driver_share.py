"""Share of the window in the fleet driver itself: each pass's
``host.driver_seconds`` (queue picks, enqueues and bookkeeping, timed in the
program's ``fleet/pick`` blocks), summed over the window's passes. Nothing
where the program does not report it."""


def read(ctx):
    hosts = [s.get("host") for s in ctx["telemetry"]]
    if not hosts or None in hosts:
        return None
    return 100.0 * sum(h["driver_seconds"] for h in hosts) / ctx["window_s"]
