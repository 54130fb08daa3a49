"""Layer: model step.  The whole train step's share of the chip's bf16 peak:
operations of forward and backward from shapes (the family's ``step_flops``;
recomputation not counted) over the step's device time.  Moves
``trials_per_hour``.  Source: the device trace."""


def read(ctx):
    sl = ctx["slice"]
    cell = ctx["cell"]
    steps = sl.module_events(cell.family.STEP_MODULE)
    if not steps:
        return None
    seconds = sum(b - a for _n, a, b in steps) / len(steps)
    peak = ctx["peaks"]["bf16_flops"]
    return 100.0 * cell.family.step_flops(cell.sizes) / (seconds * peak)
