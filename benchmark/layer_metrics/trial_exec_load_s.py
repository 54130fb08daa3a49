"""Layer: compile.  Seconds of a trial inside jax's
``backend_compile_duration`` events, summed.  The event wraps
``compile_or_get_cached``: where ``window_compilations`` is 0 it is the
persistent cache's lookup plus the executable's load.  The counter
``jit_backend_s`` that ``utils/tracing.py``'s jax listener adds to the open
spans, read from the ``train_fn`` spans (``runner/trial_runner.py``) that lie
whole inside the traced slice, per trial.  Moves ``trials_per_hour``.  Source:
a counter of the program."""


def read(ctx):
    sl = ctx["slice"]
    values = [
        s["args"]["jit_backend_s"] for s in ctx["spans"]
        if s["name"] == "train_fn" and s["t0"] >= sl.t0 and s["t1"] <= sl.t1
        and "jit_backend_s" in s["args"]
    ]
    if not values:
        return None
    return sum(values) / len(values)
