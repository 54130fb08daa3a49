"""Layer: trial runner.  Seconds inside ``TrialContext.report``: heartbeat,
store write, rule evaluation (the ``report`` span of ``runner/context.py``),
summed per trial over the ``train_fn`` spans that lie whole inside the traced
slice.  Moves ``trials_per_hour``.  Source: the program's spans."""


def read(ctx):
    sl = ctx["slice"]
    trials = {
        s["args"].get("trial") for s in ctx["spans"]
        if s["name"] == "train_fn" and s["t0"] >= sl.t0 and s["t1"] <= sl.t1
    }
    parts = [
        s for s in ctx["spans"] if s["name"] == "report" and s["args"].get("trial") in trials
    ]
    if not parts:
        return None
    return sum(s["t1"] - s["t0"] for s in parts) / len(trials)
