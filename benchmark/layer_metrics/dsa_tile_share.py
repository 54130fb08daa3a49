"""Layer: kernel (ops/flash_attention.py with a selection).  The k tiles the
selecting kernels' loops walk over the causal tiles: ``dsa_tiles_visited /
dsa_tiles_causal`` (a head's walk, every layer, batch row and q tile) on the
``trial.eval`` spans inside the traced slice.  A tile with no selected pair
at either end of a q tile's row of tiles is skipped by the loop bounds; with
random weights every causal tile holds a selected pair and the share reads
about 1.  A later change that gathers the selection into fewer tiles shows
here first, and a walked tile costs its products whether or not it holds a
selected pair, so it moves ``trials_per_hour``.  Source: the program's
counters.  On a program without them there is nothing to read."""

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
    spans = h.eval_counters(ctx, "dsa_tiles_visited", "dsa_tiles_causal")
    if not spans:
        return None
    return sum(a["dsa_tiles_visited"] for a in spans) / sum(a["dsa_tiles_causal"] for a in spans)
