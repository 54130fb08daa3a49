"""Layer: trial runner.  Seconds of the first call of the step program, call
to return: trace, lower, cache lookup, executable load and dispatch, not the
device's execution (the call is asynchronous).  The ``trial.first_step`` span
of ``models/transformer.train_lm``, per trial, over the ``train_fn`` spans
that lie whole inside the traced slice.  Moves ``trials_per_hour``.  Source:
the program's spans."""


def read(ctx):
    sl = ctx["slice"]
    trials = {
        s["args"].get("trial") for s in ctx["spans"]
        if s["name"] == "train_fn" and s["t0"] >= sl.t0 and s["t1"] <= sl.t1
    }
    parts = [
        s for s in ctx["spans"]
        if s["name"] == "trial.first_step" and s["args"].get("trial") in trials
    ]
    if not parts:
        return None
    return sum(s["t1"] - s["t0"] for s in parts) / len(trials)
