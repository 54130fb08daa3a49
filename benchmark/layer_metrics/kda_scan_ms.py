"""Layer: kernel (ops/delta_rule.py, the chunked scan).  Device milliseconds a
train step spends in the delta rule's kernels, forward and the backward's
reverse walk of every delta-rule layer: the events the family's ``KDA_MARK``
finds, summed over the step executions whole inside the traced slice, over
their number.  Moves ``trials_per_hour``.  Source: the device trace.  On a
program without the kernels there is nothing to read."""

import importlib.util
import os


# ``_kda.py`` beside this file, loaded by path as ``run.py`` loads this one
_spec = importlib.util.spec_from_file_location(
    "benchmark_layer_metrics__kda", os.path.join(os.path.dirname(os.path.abspath(__file__)), "_kda.py")
)
h = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(h)


def read(ctx):
    found = h.scan_events(ctx)
    if found is None:
        return None
    events, steps = found
    return 1000.0 * sum(b - a for _n, a, b in events) / len(steps)
