"""Layer: device.  Share of the traced slice in which no operation ran on the
device: 1 minus the union of the device-operation intervals over the slice.
Moves ``trials_per_hour``.  Source: the device trace."""


def read(ctx):
    sl = ctx["slice"]
    return 100.0 * (1.0 - sl.busy_s / sl.window_s)
