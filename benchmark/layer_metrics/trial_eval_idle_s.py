"""Layer: trial runner.  Seconds inside a trial's ``trial.eval`` spans
(``models/transformer.train_lm``: each ``float(eval_fn(...))``) in which no
operation ran on the device: the eval program's own trace, lower and load
before the first, and the fetch, without the wait for the steps still queued.
Per trial, over the ``train_fn`` spans that lie whole inside the traced slice.
Moves ``trials_per_hour``.  Source: spans and the device trace."""


def read(ctx):
    sl = ctx["slice"]
    trials = {
        s["args"].get("trial") for s in ctx["spans"]
        if s["name"] == "train_fn" and s["t0"] >= sl.t0 and s["t1"] <= sl.t1
    }
    evals = [
        s for s in ctx["spans"] if s["name"] == "trial.eval" and s["args"].get("trial") in trials
    ]
    if not evals:
        return None
    plane = sorted(sl.busy_intervals)[0]
    idle = 0.0
    for s in evals:
        busy = sum(
            min(b, s["t1"]) - max(a, s["t0"])
            for a, b in sl.busy_intervals[plane]
            if b > s["t0"] and a < s["t1"]
        )
        idle += (s["t1"] - s["t0"]) - busy
    return idle / len(trials)
