"""Layer: orchestrator.  Seconds per completed trial that lie outside every
``trial`` span: suggest, materialise, schedule, harvest, journal.  Window
start to the last completed trial's end, minus the ``trial`` spans, over the
completed trials.  Moves ``trials_per_hour``.  Source: the program's spans."""


def read(ctx):
    done = ctx["done"]
    inside = sum(s["t1"] - max(s["t0"], ctx["t0"]) for s in done)
    return (ctx["last_end"] - ctx["t0"] - inside) / len(done)
