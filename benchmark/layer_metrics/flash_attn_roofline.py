"""Layer: kernel (ops/flash_attention.py).  The least time the chip could take
for the attention of the traced steps (the larger of operations over the bf16
peak and bytes over the HBM bandwidth, from shapes: the family's
``flash_attention_cost``) over the summed device time of the kernel's forward,
dq and dkv events.  At d_head 64 and 1024 causal positions the two bounds are
within a tenth of each other; the compute bound is the larger.  Moves
``trials_per_hour``.  Source: the device trace."""


def read(ctx):
    sl = ctx["slice"]
    cell = ctx["cell"]
    events = sl.kernel_events(cell.family.FLASH_KERNEL_MARK)
    steps = sl.module_events(cell.family.STEP_MODULE)
    if not events or not steps:
        return None
    # only whole steps: kernel events inside a step execution
    inside = [
        ev for ev in events if any(a <= ev[1] and ev[2] <= b for _n, a, b in steps)
    ]
    if not inside:
        return None
    cost = cell.family.flash_attention_cost(cell.sizes)
    pk = ctx["peaks"]
    least = max(cost["flops"] / pk["bf16_flops"], cost["bytes"] / pk["hbm_bytes_per_s"])
    least *= cost["calls_per_step"] * len(steps)
    return 100.0 * least / sum(b - a for _n, a, b in inside)
