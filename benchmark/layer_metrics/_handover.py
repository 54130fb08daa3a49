"""What the seven readers of the hand-over between two trials share (it names
no metric, so ``run.py`` never loads it as a reader; they load it by path).

For two trials that follow each other, the **hand-over** runs from the end of
the first trial's last ``trial.eval`` span (``train_lm`` has fetched the last
number: every step is done, the device holds no work) to the end of the next
trial's ``trial.programs`` span (the next thing ``train_lm`` does is hand
``init`` to the device).  Host time with an empty device, in three parts whose
ends are span ends:

- **tail**: last ``trial.eval`` end -> ``trial`` end (``report``, the rest of
  ``train_fn``, ``trial.finalize``);
- **gap**: ``trial`` end -> next ``trial`` start (the orchestrator:
  ``orch.settle``, ``orch.dispatch``, the pool);
- **head**: ``trial`` start -> ``trial.programs`` end (``trial.setup``,
  ``trial.data``, the start of ``trial.init``).

A **boundary** is a pair of ``trial`` spans, both ``Succeeded`` and complete
inside the window (``ctx["done"]``), that follow each other in time with no
other ``trial`` span between.  Read over the WHOLE window up to
``ctx["last_end"]``, not over the traced slice.  Left out: a boundary whose
first trial ends before the traced slice does (``ctx["slice"].t1``): the
profiler is being stopped from there on, which takes seconds and holds the
interpreter (on the chip that hand-over read up to 0.03 s longer than the
later ones', in the tail and in ``trial.data``).  The boundary after it is
kept: it read no longer than the later ones in any cell (PERF.md section 6,
PR 39).  Every metric is a mean over the boundaries kept that have the spans
it needs, so the parts add up to the whole where every boundary has them all.
"""


def _of_trial(ctx, name):
    """``{trial: [spans of that name, by start]}``."""
    by_trial = {}
    for s in sorted(ctx["spans"], key=lambda s: s["t0"]):
        if s["name"] == name:
            by_trial.setdefault(s["args"].get("trial"), []).append(s)
    return by_trial


def boundaries(ctx):
    """The boundaries kept: ``[(first, second)]``, two ``trial`` spans each."""
    done = {id(s) for s in ctx["done"]}
    trials = sorted((s for s in ctx["spans"] if s["name"] == "trial"), key=lambda s: s["t0"])
    return [
        (a, b) for a, b in zip(trials, trials[1:])
        if id(a) in done and id(b) in done and b["t0"] >= a["t1"] > ctx["slice"].t1
    ]


def parts(ctx):
    """``[(tail, gap, head)]`` in seconds, one a boundary kept; a part is
    ``None`` where the program wrote no span to end it (a trial function that
    is not ``train_lm`` has no ``trial.eval``; the parent commit has no
    ``trial.programs``)."""
    evals, programs = _of_trial(ctx, "trial.eval"), _of_trial(ctx, "trial.programs")
    out = []
    for a, b in boundaries(ctx):
        last_eval = [s for s in evals.get(a["args"].get("trial"), []) if s["t1"] <= a["t1"]]
        built = [s for s in programs.get(b["args"].get("trial"), []) if s["t0"] >= b["t0"]]
        out.append((
            a["t1"] - last_eval[-1]["t1"] if last_eval else None,
            b["t0"] - a["t1"],
            built[0]["t1"] - b["t0"] if built else None,
        ))
    return out


def mean(values):
    """Mean of the values that are there; ``None`` where none is."""
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def spans_beside(ctx, name, which):
    """The ``name`` span (``orch.dispatch`` | ``orch.settle``) of each kept
    boundary's first (``which`` 0) or second (1) trial; ``None`` where the
    program wrote none."""
    by_trial = _of_trial(ctx, name)
    return [
        (by_trial.get(pair[which]["args"].get("trial")) or [None])[0] for pair in boundaries(ctx)
    ]
