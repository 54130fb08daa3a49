"""Layer: compile.  Executables loaded from the persistent cache inside the
window (jax's compile log: hit records), per completed trial: what every trial
pays because it builds its closures anew.  Moves ``trials_per_hour``.  Source:
a counter."""


def read(ctx):
    return ctx["compile_log"]["loads"] / len(ctx["done"])
