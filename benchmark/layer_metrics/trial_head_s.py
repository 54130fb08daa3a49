"""Layer: trial runner.  Seconds from the start of a trial's ``trial`` span to
the end of its ``trial.programs`` span: ``trial.setup`` (watchdogs, compile
signature, context), ``trial.data`` (model object, the synthetic tokens) and
the start of ``trial.init`` up to the call that hands ``init`` to the device.
The last part of the hand-over (``_handover.py``), a mean over the boundaries
it keeps (each boundary's SECOND trial).  Moves ``trials_per_hour``.  Source:
the program's spans."""

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
    return h.mean([head for _tail, _gap, head in h.parts(ctx)])
