"""Layer: model step (models/dsa_moe.py ``DsaMoeLM.training_loss``).  The
indexer's loss ``L_I``, the mean over the layers of the step before a report
(the ``indexer_kl`` counter on the ``trial.eval`` spans inside the traced
slice), averaged: how far each indexer's softmax over its selection lies from
the attention's head-mean probabilities there.  It falls as the indexer
trains, and the keys a trained indexer picks are the ones attention weighs,
so it says how much of the selected work is wasted; it moves
``trials_per_hour`` only through what a later change makes of the selection.
Source: the program's counters.  On a program without it there is nothing to
read."""


def read(ctx):
    sl = ctx["slice"]
    values = [
        s["args"]["indexer_kl"]
        for s in ctx["spans"]
        if s["name"] == "trial.eval" and s["t0"] >= sl.t0 and s["t1"] <= sl.t1 and "indexer_kl" in s["args"]
    ]
    if not values:
        return None
    return sum(values) / len(values)
