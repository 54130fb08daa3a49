"""Layer: kernel (ops/delta_rule.py, the chunked scan).  The scan kernels'
share of their roofline: the least time the chip could take for the delta
rule of the traced steps (the larger of operations over the bf16 peak and
bytes over the HBM bandwidth, from shapes: the family's ``kda_scan_cost``, the
chunk algorithm's operations at chunk 64 forward and backward, q, k, v, g and
beta read and o written, and the backward's reads and writes) over the device
time of their events inside the step executions.  Moves ``trials_per_hour``.
Source: the device trace.  On a program without the kernels there is nothing
to read."""

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
    cell, pk = ctx["cell"], ctx["peaks"]
    cost = cell.family.kda_scan_cost(cell.sizes)
    least = max(cost["flops"] / pk["bf16_flops"], cost["bytes"] / pk["hbm_bytes_per_s"])
    least *= cost["calls_per_step"] * len(steps)
    return 100.0 * least / sum(b - a for _n, a, b in events)
