"""Layer: kernel (ops/sparse_attention.py ``_select_kernel``).  The selection
pass's share of its roofline: the least time the chip could take for it (the
larger of operations over the bf16 peak and bytes over the HBM bandwidth, from
shapes: the family's ``select_cost``: two operations per indexer head and
width per visible pair; the indexer's queries, key and weights read, the
bitmask written) over the device time of its events inside the traced step
executions.  The counting passes are the kernel's own way to an exact top-k
and are not counted as work.  Moves ``trials_per_hour``.  Source: the device
trace.  On a program without the kernel there is nothing to read."""

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
    cell, pk = ctx["cell"], ctx["peaks"]
    cost = cell.family.select_cost(cell.sizes)
    least = max(cost["flops"] / pk["bf16_flops"], cost["bytes"] / pk["hbm_bytes_per_s"])
    least *= cost["calls_per_step"] * len(steps)
    return 100.0 * least / sum(b - a for _n, a, b in events)
