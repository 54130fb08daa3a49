"""Layer: suggest.  Milliseconds of ``suggest`` spans per completed trial, up
to the last completed trial's end.  Moves ``trials_per_hour``.  Source: the
program's spans."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s["name"] == "suggest" and s["t1"] <= ctx["last_end"]]
    if not spans:
        return None
    return 1000.0 * sum(s["t1"] - s["t0"] for s in spans) / len(ctx["done"])
