"""Layer: compile.  Seconds a trial spends tracing its programs: the union of
jax's ``jaxpr_trace_duration`` events on the trial's thread (a function traced
inside another is counted once).  The counter ``jit_trace_s`` that
``utils/tracing.py``'s jax listener adds to the open spans, read from the
``train_fn`` spans (``runner/trial_runner.py``) that lie whole inside the
traced slice, per trial.  Moves ``trials_per_hour``.  Source: a counter of the
program."""


def read(ctx):
    sl = ctx["slice"]
    values = [
        s["args"]["jit_trace_s"] for s in ctx["spans"]
        if s["name"] == "train_fn" and s["t0"] >= sl.t0 and s["t1"] <= sl.t1
        and "jit_trace_s" in s["args"]
    ]
    if not values:
        return None
    return sum(values) / len(values)
