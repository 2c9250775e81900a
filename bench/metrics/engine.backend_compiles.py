"""Backend compiles inside the window, whatever asked for them
(``EngineStats.backend_compiles``, from JAX's compile monitoring, persistent
cache loads included): 0 when the warm-up covered every shape. Nothing
where the program does not count them."""


def read(ctx):
    n = ctx["delta"].get("backend_compiles")
    return None if n is None else float(n)
