"""Layer: kernel (ops/sparse_attention.py ``_select_kernel``).  Device
milliseconds a train step spends in the selection pass: the indexer's scores of
every visible pair and each query's exact top-``k`` by 32 counting passes over
the scores' bits, then the bitmask; the events found by the family's
``SELECT_MARK`` (the one kernel whose first result is int32), summed over the
step executions whole inside the traced slice, over their number.  Moves
``trials_per_hour``.  Source: the device trace.  On a program without the
kernel there is nothing to read."""

import importlib.util
import os


# ``_selection.py`` beside this file, loaded by path as ``run.py`` loads this one
_spec = importlib.util.spec_from_file_location(
    "benchmark_layer_metrics__selection",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_selection.py"),
)
h = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(h)


def read(ctx):
    found = h.select_events(ctx)
    if found is None:
        return None
    events, steps = found
    return 1000.0 * sum(b - a for _n, a, b in events) / len(steps)
