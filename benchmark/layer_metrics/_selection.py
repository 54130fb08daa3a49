"""What the sparse-attention readers share (``families/dsa_moe.py``; names no
metric, loaded by path): the selection pass's device events inside the traced
step executions, and the selection counters on the ``trial.eval`` spans inside
the traced slice.  Where the program has no such kernel or counter (the
parent of the PR that brought them) both find nothing."""


def select_events(ctx):
    """``(events, steps)``: the selection pass's events whole inside a step
    execution, and the step executions; ``None`` where either is missing."""
    sl, family = ctx["slice"], ctx["cell"].family
    mark = getattr(family, "SELECT_MARK", None)
    steps = sl.module_events(family.STEP_MODULE)
    if mark is None or not steps:
        return None
    events = [
        ev
        for ev in sl.kernel_events(family.FLASH_KERNEL_MARK)
        if mark in ev[0] and any(a <= ev[1] and ev[2] <= b for _n, a, b in steps)
    ]
    return (events, steps) if events else None


def eval_counters(ctx, *names):
    """The counters ``names`` of every ``trial.eval`` span inside the traced
    slice that carries them all."""
    sl = ctx["slice"]
    return [
        s["args"]
        for s in ctx["spans"]
        if s["name"] == "trial.eval"
        and s["t0"] >= sl.t0
        and s["t1"] <= sl.t1
        and all(n in s["args"] for n in names)
    ]
