"""Layer: kernel (the grouped products of models/mla_moe.py ExpertLayer, which
``jax.lax.ragged_dot`` compiles to).  The least time the chip could take for
the grouped products of the traced steps (the larger of operations over the
bf16 peak and bytes over the HBM bandwidth: the family's
``expert_product_cost`` from the COUNTED assignments to the experts held,
``moe_assignments_held`` on the ``trial.eval`` spans, a step's mean) over the
summed device time of the operations the family's ``EXPERT_PRODUCT_MARK``
finds inside the step executions: forward, the rematerialised forward, and the
two products of the backward pass.  Moves ``trials_per_hour``.  Source: the
device trace."""


def read(ctx):
    sl = ctx["slice"]
    cell = ctx["cell"]
    mark = getattr(cell.family, "EXPERT_PRODUCT_MARK", None)
    steps = sl.module_events(cell.family.STEP_MODULE)
    held = [
        s["args"]["moe_assignments_held"]
        for s in ctx["spans"]
        if s["name"] == "trial.eval"
        and s["t0"] >= sl.t0
        and s["t1"] <= sl.t1
        and "moe_assignments_held" in s["args"]
    ]
    if mark is None or not steps or not held:
        return None
    inside = [
        ev
        for ev in sl.kernel_events(mark)
        if any(a <= ev[1] and ev[2] <= b for _n, a, b in steps)
    ]
    if not inside:
        return None
    cost = cell.family.expert_product_cost(cell.sizes, sum(held) / len(held))
    pk = ctx["peaks"]
    least = max(cost["flops"] / pk["bf16_flops"], cost["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least * len(steps) / sum(b - a for _n, a, b in inside)
