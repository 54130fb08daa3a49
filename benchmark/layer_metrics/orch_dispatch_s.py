"""Layer: orchestrator.  Seconds of the ``orch.dispatch`` span of each kept
boundary's second trial (``_handover.py``), a mean: from the schedule loop
taking the unit with a slot free to ``pool.submit`` returned (rules
refreshed, prewarm queued, the ``started`` record appended and fsync'd, the
submit).  Moves ``trials_per_hour``.  Source: the program's spans."""

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
        None if s is None else s["t1"] - s["t0"] for s in h.spans_beside(ctx, "orch.dispatch", 1)
    ])
