"""Layer: orchestrator.  Milliseconds per completed trial inside the experiment
journal's ``append`` / ``append_group`` calls (encode, write, flush, fsync):
the counter ``journal_s`` that ``Orchestrator._journal_write`` adds to the span
open on the calling thread, summed over the ``orch.dispatch``, ``orch.settle``
and ``trial`` spans that end by the last completed trial's end.  Over the
whole window.  (A settle that runs inside a dispatch, as an early stopper's
rule refresh makes it, counts in both spans.)  Moves ``trials_per_hour``.
Source: a counter of the program."""


def read(ctx):
    values = [
        s["args"]["journal_s"] for s in ctx["spans"]
        if s["name"] in ("orch.dispatch", "orch.settle", "trial")
        and s["t1"] <= ctx["last_end"] and "journal_s" in s["args"]
    ]
    if not values:
        return None
    return 1000.0 * sum(values) / len(ctx["done"])
