"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, from the profiler trace."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else trace["idle_share"]
