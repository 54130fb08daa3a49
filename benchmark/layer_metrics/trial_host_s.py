"""Layer: trial runner.  Seconds of a trial's ``train_fn`` span in which no
operation ran on the device: model.init, trace, lower, cache load, eval
fetches, reports.  Read for the ``train_fn`` spans that lie whole inside the
traced slice.  Moves ``trials_per_hour``.  Source: spans and the device
trace."""


def read(ctx):
    sl = ctx["slice"]
    whole = [
        s for s in ctx["spans"]
        if s["name"] == "train_fn" and s["t0"] >= sl.t0 and s["t1"] <= sl.t1
    ]
    if not whole:
        return None
    plane = sorted(sl.busy_intervals)[0]
    host = 0.0
    for s in whole:
        busy = sum(
            min(b, s["t1"]) - max(a, s["t0"])
            for a, b in sl.busy_intervals[plane]
            if b > s["t0"] and a < s["t1"]
        )
        host += (s["t1"] - s["t0"]) - busy
    return host / len(whole)
