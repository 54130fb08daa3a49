"""Layer: trial runner.  Seconds from the end of a trial's last ``trial.eval``
span to the end of its ``trial`` span: the last ``report``, the rest of
``train_fn``, ``trial.finalize`` (the store's read-back) and the way out of
the runner.  The first part of the hand-over (``_handover.py``), a mean over
the boundaries it keeps.  Moves ``trials_per_hour``.  Source: the program's
spans."""

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
    return h.mean([tail for tail, _gap, _head in h.parts(ctx)])
