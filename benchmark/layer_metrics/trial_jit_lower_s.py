"""Layer: compile.  Seconds a trial spends lowering its programs to MLIR
modules: jax's ``jaxpr_to_mlir_module_duration`` events, summed.  The counter
``jit_lower_s`` that ``utils/tracing.py``'s jax listener adds to the open
spans, read from the ``train_fn`` spans (``runner/trial_runner.py``) that lie
whole inside the traced slice, per trial.  Moves ``trials_per_hour``.  Source:
a counter of the program."""


def read(ctx):
    sl = ctx["slice"]
    values = [
        s["args"]["jit_lower_s"] for s in ctx["spans"]
        if s["name"] == "train_fn" and s["t0"] >= sl.t0 and s["t1"] <= sl.t1
        and "jit_lower_s" in s["args"]
    ]
    if not values:
        return None
    return sum(values) / len(values)
