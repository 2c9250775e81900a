"""Share of the lanes' time in the engine that went to waiting on other
lanes' work (``latency.barrier``: barrier wait under lockstep, queue wait
under async), summed over the window's passes."""


def read(ctx):
    stall = wall = 0.0
    for summary in ctx["telemetry"]:
        barrier = summary["latency"]["barrier"]
        stall += barrier["stall_seconds"]
        wall += sum(lane["wall_seconds"] for lane in barrier["per_lane"])
    return 100.0 * stall / wall if wall else None
