"""Layer: device.  Peak bytes in use on the cell's fullest device after the
window (``memory_stats()['peak_bytes_in_use']``), in GB.  Moves
``trials_per_hour`` only where a PR trades memory for time; reported so that
the trade shows.  Source: a counter."""


def read(ctx):
    return ctx["device"]["memory_peak_bytes"] / 1e9
