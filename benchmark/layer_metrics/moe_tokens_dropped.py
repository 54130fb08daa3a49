"""Layer: experts (models/mla_moe.py ExpertLayer).  Assignments of a token to
an expert held here that the grouped product did not compute
(``moe_tokens_dropped`` on a ``trial.eval`` span: assignments counted before
the sort less the rows the product was given), summed over the reports inside
the traced slice.  Expected 0, as ``window_compilations`` is: the layer has no
capacity factor.  A dropped token is a wrong result, not a slower one; the
metric is listed under ``trials_per_hour`` because dropping is how an expert
layer buys speed.  Source: the program's counters."""


def read(ctx):
    sl = ctx["slice"]
    counts = [
        s["args"]["moe_tokens_dropped"]
        for s in ctx["spans"]
        if s["name"] == "trial.eval"
        and s["t0"] >= sl.t0
        and s["t1"] <= sl.t1
        and "moe_tokens_dropped" in s["args"]
    ]
    if not counts:
        return None
    return float(sum(counts))
