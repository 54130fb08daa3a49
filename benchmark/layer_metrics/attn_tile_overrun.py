"""Layer: kernel (ops/flash_attention.py).  How many tiles the attention
kernels' loops walk in a step over the least they could: ``attn_tiles_run /
attn_tiles_needed``, the two counters ``models/transformer.attention_plan``
adds to a trial's ``trial.init`` span (the kernels' own loop bounds evaluated
over every layer, head and batch row, against the tiles of the planned size
that hold a visible pair), summed over the trials that started inside the
window.  1 is exact skipping; window layers that walked the whole causal
triangle would read 1.64 for a period of one full and three 4096-key window
layers at 16384 positions.  A walked tile costs its products whether or not a
key in it is visible, so it moves ``trials_per_hour``.  Source: the program's
counters.  On a program whose span has no such counters (the parent of the PR
that brought them) there is nothing to read."""


def read(ctx):
    inits = [
        s["args"]
        for s in ctx["spans"]
        if s["name"] == "trial.init"
        and ctx["t0"] <= s["t0"]
        and s["t1"] <= ctx["last_end"]
        and s["args"].get("attn_tiles_needed")
        and "attn_tiles_run" in s["args"]
    ]
    if not inits:
        return None
    return sum(a["attn_tiles_run"] for a in inits) / sum(a["attn_tiles_needed"] for a in inits)
