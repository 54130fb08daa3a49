"""Layer: compile.  Programs compiled inside the window (jax's compile log:
persistent-cache MISS records).  Expected 0: every program of the cell's
traffic is in the cache after set-up.  Moves ``trials_per_hour``.  Source: a
counter."""


def read(ctx):
    return float(ctx["compile_log"]["compilations"])
