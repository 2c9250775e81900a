"""New compiled-shape signatures the engine met inside the window
(``EngineStats.cache_misses``): shapes the warm-up pass did not cover."""


def read(ctx):
    return float(ctx["delta"]["cache_misses"])
