"""Layer: experts (models/mla_moe.py ExpertLayer).  How uneven the router's
load is over the experts held: the busiest held expert's tokens in one layer
over the mean over held experts and layers (``moe_expert_tokens_max`` /
``moe_expert_tokens_mean`` on a ``trial.eval`` span: the routing counts of the
step before that report), averaged over the reports inside the traced slice.
1 is an even load.  The grouped product's time follows the busiest tiles, so
it moves ``trials_per_hour``.  Source: the program's counters."""


def read(ctx):
    sl = ctx["slice"]
    ratios = [
        s["args"]["moe_expert_tokens_max"] / s["args"]["moe_expert_tokens_mean"]
        for s in ctx["spans"]
        if s["name"] == "trial.eval"
        and s["t0"] >= sl.t0
        and s["t1"] <= sl.t1
        and s["args"].get("moe_expert_tokens_mean")
    ]
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
