"""Layer: model step.  Device milliseconds of one train step: the executions
of the family's step program in the traced slice, start to end on the device,
averaged.  Moves ``trials_per_hour``.  Source: the device trace."""


def read(ctx):
    sl = ctx["slice"]
    steps = sl.module_events(ctx["cell"].family.STEP_MODULE)
    if not steps:
        return None
    return 1000.0 * sum(b - a for _n, a, b in steps) / len(steps)
