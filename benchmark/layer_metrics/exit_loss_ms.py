"""Layer: model step (models/looped.py LoopedLM.training_loss, models/lm_head.py
weighted_token_losses).  Device milliseconds of one train step in the heads'
products and the cross entropies of all exits: the operations the family's
``exit_loss_mark`` finds inside the step executions of the traced slice (an
array with a sequence's positions beside the whole vocabulary, as a result or
as an operand), before the optimizer's update, a step's mean
(``families/looped.py:step_parts``).  A model with ``T`` exits runs the head
``T`` times a step, so it moves ``trials_per_hour``.  Source: the device
trace.  A family that names no such mark has nothing to read."""


def read(ctx):
    cell = ctx["cell"]
    parts = getattr(cell.family, "step_parts", None)
    found = parts(ctx["slice"], cell.sizes) if parts else None
    return found["exit_loss_ms"] if found else None
