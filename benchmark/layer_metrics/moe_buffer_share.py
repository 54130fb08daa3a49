"""Layer: experts (models/mla_moe.py ExpertLayer).  How long the sorted buffer
was that the gathers, the clears and the grouped products ran over, against
the worst case: ``moe_buffer_rows`` (the rows of the rung each expert layer
ran, summed over the layers) over ``moe_assignments_total`` (all ``T x k``
assignments of those layers) on a ``trial.eval`` span: the step before that
report; averaged over the reports inside the traced slice.  1 is a buffer
sized for every assignment whatever the routing; a share that holds 1/8 of
the experts reads 0.25 on its short rung.  Rows that are gathered, cleared
and multiplied cost their time whether an expert holds them or not, so it
moves ``trials_per_hour``.  Source: the program's counters.  On a program
whose span has no such counter (the parent of the PR that brought it) there
is nothing to read."""


def read(ctx):
    sl = ctx["slice"]
    shares = [
        s["args"]["moe_buffer_rows"] / s["args"]["moe_assignments_total"]
        for s in ctx["spans"]
        if s["name"] == "trial.eval"
        and s["t0"] >= sl.t0
        and s["t1"] <= sl.t1
        and "moe_buffer_rows" in s["args"]
        and s["args"].get("moe_assignments_total")
    ]
    if not shares:
        return None
    return sum(shares) / len(shares)
