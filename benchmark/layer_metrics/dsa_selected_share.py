"""Layer: kernel (ops/sparse_attention.py, the selection).  Selected over
visible (query, key) pairs: ``dsa_selected_pairs / dsa_visible_pairs``, the
counters ``models/dsa_moe.py:DsaMoeLM.step_counters`` puts on the ``trial.eval``
spans (summed over the layers of the step before the report), over the spans
inside the traced slice.  The shapes fix it: ``min(t + 1, topk)`` keys a
query, 0.234 at 16384 positions and a top-2048; a change that widens the
selection or drops part of it moves it, and the attention's work with it, so
it moves ``trials_per_hour``.  Source: the program's counters.  On a program
without them there is nothing to read."""

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
    spans = h.eval_counters(ctx, "dsa_selected_pairs", "dsa_visible_pairs")
    if not spans:
        return None
    return sum(a["dsa_selected_pairs"] for a in spans) / sum(a["dsa_visible_pairs"] for a in spans)
