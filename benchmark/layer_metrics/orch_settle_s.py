"""Layer: orchestrator.  Seconds of the ``orch.settle`` span of each kept
boundary's first trial (``_handover.py``), a mean: everything
``Orchestrator._harvest`` does for one finished future (result, the store's
read-back, counters, clean-up, the ``reported`` and ``settled`` records with
their fsync).  On the harvest thread: off the path to the next trial unless it
holds the locks the schedule loop waits for.  Moves ``trials_per_hour``.
Source: the program's spans."""

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
        None if s is None else s["t1"] - s["t0"] for s in h.spans_beside(ctx, "orch.settle", 0)
    ])
