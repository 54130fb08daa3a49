"""Layer: orchestrator.  Seconds from the end of a trial's last ``trial.eval``
span (the device holds no work) to the end of the next trial's
``trial.programs`` span (the next thing handed to the device is ``init``):
tail + gap + head of ``_handover.py``, a mean over the boundaries between
completed trials that it keeps, over the whole window.  Host time with an
empty device by construction.  Moves ``trials_per_hour``.  Source: the
program's spans."""

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
    whole = [
        None if tail is None or head is None else tail + gap + head
        for tail, gap, head in h.parts(ctx)
    ]
    return h.mean(whole)
