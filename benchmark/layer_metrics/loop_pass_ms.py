"""Layer: model step (models/looped.py LoopedPass under ``nn.scan``).  Device
milliseconds of one pass of the stack, forward and backward: what is left of a
step execution outside the exits' heads and cross entropies (``exit_loss_ms``)
and outside the optimizer's update (from the first fusion that reads one of
AdamW's moments to the step's end), over the configuration's
``total_ut_steps``; a step's mean over the traced slice
(``families/looped.py:step_parts``).  Every pass costs it again, so it moves
``trials_per_hour``.  Source: the device trace.  A family whose step is not a
loop of passes has nothing to read."""


def read(ctx):
    cell = ctx["cell"]
    parts = getattr(cell.family, "step_parts", None)
    found = parts(ctx["slice"], cell.sizes) if parts else None
    return found["loop_pass_ms"] if found else None
