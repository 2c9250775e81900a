"""Programs per compiled batch call over the window (the fleet driver's
cross-lane batching): the engine's batched instances over its batch calls,
the same ratio as ``FleetTelemetry.mean_batch_occupancy``, pooled over
passes."""


def read(ctx):
    calls = ctx["delta"]["batched_solves"]
    return ctx["delta"]["batched_instances"] / calls if calls else None
