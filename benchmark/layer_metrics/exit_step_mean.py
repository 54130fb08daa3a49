"""Layer: model step (models/looped.py LoopedLM.training_loss).  The exit a
token leaves at, in the mean: ``exit_step_mean`` (``sum_t t q_t``, averaged
over the positions of the step before a report) on the ``trial.eval`` spans
inside the traced slice, averaged.  With ``T`` exits it lies between 1 and
``T``: a gate whose ``lambda`` is a half everywhere reads 1.875 of 4, the
initial weights of ``ouro-2.6b-l6`` read 1.49, and ten updates move it to
between 2.6 and 4.0 by the learning rate (the hidden states of all tokens
share a component, so the gate's 2048 weights move its logit together).  It
says how far the gate has moved from its start and so which exits the loss's
weight lies on: the exits that weigh little are passes whose head and backward
the step still pays for in full, so it moves ``trials_per_hour`` only through
what a later change makes of it.  Source: the program's counters.  On a
program whose span has no such counter there is nothing to read."""


def read(ctx):
    sl = ctx["slice"]
    means = [
        s["args"]["exit_step_mean"]
        for s in ctx["spans"]
        if s["name"] == "trial.eval"
        and s["t0"] >= sl.t0
        and s["t1"] <= sl.t1
        and "exit_step_mean" in s["args"]
    ]
    if not means:
        return None
    return sum(means) / len(means)
