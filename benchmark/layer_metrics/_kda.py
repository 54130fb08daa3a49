"""What the delta rule's readers share (``families/kda_moe.py``; names no
metric, loaded by path): the scan kernels' device events inside the traced step
executions, found by the family's ``KDA_MARK`` (a regular expression over an
operation's HLO text).  Where the program has no such kernel (the parent of the
PR that brought it) it finds nothing."""

import re


def scan_events(ctx):
    """``(events, steps)``: the scan kernels' events whole inside a step
    execution, and the step executions; ``None`` where either is missing."""
    sl, family = ctx["slice"], ctx["cell"].family
    mark = getattr(family, "KDA_MARK", None)
    steps = sl.module_events(family.STEP_MODULE)
    if mark is None or not steps:
        return None
    pattern = re.compile(mark)
    events = [
        ev
        for ev in sl.kernel_events("tpu_custom_call")
        if pattern.search(ev[0]) and any(a <= ev[1] and ev[2] <= b for _n, a, b in steps)
    ]
    return (events, steps) if events else None
