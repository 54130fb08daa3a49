"""Layer: trial runner.  Seconds a trial spends before its loop: the
``trial.data`` span (attention function, model object, Markov tokens on the
host; ``models/transformer.transformer_trial``) plus the ``trial.init`` span
(``model.init`` op by op, schedule and optimizer, ``TrainState.create``,
placing the eval tokens; ``models/transformer.train_lm``).  Per trial, over
the ``train_fn`` spans that lie whole inside the traced slice and the records
that carry their trial's name.  Moves ``trials_per_hour``.  Source: the
program's spans."""


def read(ctx):
    sl = ctx["slice"]
    trials = {
        s["args"].get("trial") for s in ctx["spans"]
        if s["name"] == "train_fn" and s["t0"] >= sl.t0 and s["t1"] <= sl.t1
    }
    parts = [
        s for s in ctx["spans"]
        if s["name"] in ("trial.data", "trial.init") and s["args"].get("trial") in trials
    ]
    if not parts:
        return None
    return sum(s["t1"] - s["t0"] for s in parts) / len(trials)
