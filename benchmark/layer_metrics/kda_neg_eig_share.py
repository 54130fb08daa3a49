"""Layer: model step (models/kda_moe.py, the delta rule's step).  The share of
(token, head) pairs of the delta-rule layers whose step ``beta`` passes 1, so
that ``I - beta k k^T`` has a negative eigenvalue along ``k``: how much of the
range (0, 2) the model uses.  ``kda_neg_eig_pairs / kda_pairs``, the counters
``KdaMoeLM.step_counters`` puts on the ``trial.eval`` spans (summed over the
layers of the step before the report), over the spans inside the traced slice.
Moves ``trials_per_hour`` only through what the model learns.  Source: the
program's counters.  On a program without them there is nothing to read."""

import importlib.util
import os


# ``_selection.py`` beside this file (its ``eval_counters``), loaded by path
_spec = importlib.util.spec_from_file_location(
    "benchmark_layer_metrics__selection", os.path.join(os.path.dirname(os.path.abspath(__file__)), "_selection.py")
)
h = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(h)


def read(ctx):
    spans = h.eval_counters(ctx, "kda_neg_eig_pairs", "kda_pairs")
    if not spans or not sum(a["kda_pairs"] for a in spans):
        return None
    return sum(a["kda_neg_eig_pairs"] for a in spans) / sum(a["kda_pairs"] for a in spans)
