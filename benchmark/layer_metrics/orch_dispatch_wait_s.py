"""Layer: orchestrator.  The counter ``slot_free_s`` on the ``orch.dispatch``
span of each kept boundary's second trial (``_handover.py``), a mean: for how
long the unit was queued AND a slot was free before the schedule loop took it
(``orchestrator/async_loops.py:_dispatch_units``: its poll, and its wait for
the locks a harvest holds).  The synchronous loop writes 0.  Moves
``trials_per_hour``.  Source: a counter of the program."""

import importlib.util
import os

# ``_handover.py`` beside this file, loaded by path as ``run.py`` loads this one
_spec = importlib.util.spec_from_file_location(
    "benchmark_layer_metrics__handover",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_handover.py"),
)
h = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(h)


def read(ctx):
    return h.mean([
        None if s is None else s["args"].get("slot_free_s")
        for s in h.spans_beside(ctx, "orch.dispatch", 1)
    ])
