"""The per-layer readers that take a trial's start apart
(``benchmark/layer_metrics/trial_*.py``): each is given a hand-made ``ctx``
(the program's spans on the wall clock and a stand-in for the traced slice) and
must give the number, divide it by the trials that lie whole inside the slice,
and give ``None`` where the program wrote no such span or counter.  And the
seven that take the hand-over between two trials apart (PR 39:
``trial_handover_s``, ``trial_tail_s``, ``trial_head_s``, ``orch_*``): means
over the boundaries between completed trials, over the whole window, the
boundary that the traced slice's end touches left out."""

import importlib.util
import os
import types

import pytest

METRICS = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark", "layer_metrics")


def reader(metric):
    spec = importlib.util.spec_from_file_location(metric, os.path.join(METRICS, f"{metric}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, t0, t1, trial, **args):
    return {"name": name, "t0": t0, "t1": t1, "args": {"trial": trial, **args}}


def trial_spans(trial, t0, scale=1.0):
    """One trial as the program journals it, ``scale`` stretching its parts."""
    k = scale
    return [
        span("trial", t0 - 0.5, t0 + 20.5, trial),
        span(
            "train_fn", t0, t0 + 20.0, trial,
            jit_trace_s=3.0 * k, jit_lower_s=2.0 * k, jit_backend_s=1.5 * k, jit_programs=23,
        ),
        span("trial.data", t0, t0 + 0.5 * k, trial),
        span("trial.init", t0 + 1.0, t0 + 1.0 + 4.0 * k, trial),
        span("trial.first_step", t0 + 6.0, t0 + 6.0 + 3.0 * k, trial),
        span("jit.trace", t0 + 6.0, t0 + 8.0, trial, program="step_fn"),
        span("trial.eval", t0 + 10.0, t0 + 12.0, trial, step=0, first=True),
        span("report", t0 + 12.0, t0 + 12.0 + 0.01 * k, trial, step=0),
        span("trial.eval", t0 + 15.0, t0 + 16.0, trial, step=10),
        span("report", t0 + 16.0, t0 + 16.0 + 0.03 * k, trial, step=10),
    ]


def make_ctx(spans, t0=100.0, t1=150.0, busy=()):
    sl = types.SimpleNamespace(t0=t0, t1=t1, busy_intervals={"/device:TPU:0": list(busy)})
    return {"spans": spans, "slice": sl}


# one trial at 100 s; the device is busy over half of the first eval, across
# the second eval's start, and in a stretch that touches no eval
BUSY = [(103.0, 104.0), (111.0, 112.5), (114.5, 115.25)]

ONE_TRIAL = {
    "trial_init_s": 4.5,
    "trial_first_step_s": 3.0,
    "trial_jit_trace_s": 3.0,
    "trial_jit_lower_s": 2.0,
    "trial_exec_load_s": 1.5,
    # eval 1: 2.0 s less 1.0 s busy; eval 2: 1.0 s less 0.25 s busy
    "trial_eval_idle_s": 1.75,
    "trial_report_s": 0.04,
}


# -- the hand-over between two trials ------------------------------------------

# four trials of 20 s, (name, start, k): k stretches every part of a trial's
# own ends.  t1 ends inside the traced slice, so the boundary t1 -> t2 is left
# out; t2 -> t3 and t3 -> t4 are kept.  The gaps: 0.2, 0.1, 0.3 s.
FOUR_TRIALS = (("t1", 100.0, 10.0), ("t2", 120.2, 1.0), ("t3", 140.3, 2.0), ("t4", 160.6, 3.0))
SLICE_END = 120.03


def journaled(trial, s, k, condition="Succeeded"):
    """One trial as the program journals it since PR 39, with the
    orchestrator's two spans beside it: tail 0.2 k, head 0.5 k,
    ``slot_free_s`` 0.02 k, dispatch 0.03 k, settle 0.05 k."""
    return [
        span("orch.dispatch", s - 0.03 * k, s, trial, members=1, slot_free_s=0.02 * k, journal_s=0.003 * k),
        span("trial", s, s + 20.0, trial, condition=condition, **({"journal_s": 0.01} if trial == "t2" else {})),
        span("trial.setup", s, s + 0.01, trial),
        span("train_fn", s + 0.01, s + 19.99, trial),
        span("trial.data", s + 0.01, s + 0.3 * k, trial),
        span("trial.init", s + 0.3 * k, s + 0.5 * k + 1.0, trial),
        span("trial.programs", s + 0.3 * k, s + 0.5 * k, trial),
        span("trial.eval", s + 5.0, s + 6.0, trial, step=0, first=True),
        span("trial.eval", s + 19.0 - 0.2 * k, s + 20.0 - 0.2 * k, trial, step=11),
        span("report", s + 20.0 - 0.2 * k, s + 20.0 - 0.2 * k + 0.001, trial, step=11),
        span("trial.finalize", s + 19.995, s + 19.999, trial),
        span("orch.settle", s + 20.01, s + 20.01 + 0.05 * k, trial, members=1, journal_s=0.004 * k),
    ]


def handover_ctx(slice_end=SLICE_END, drop=(), failed=()):
    """``ctx`` as ``run.py`` builds it over the four trials: ``done`` is the
    ``trial`` spans that Succeeded; ``drop`` names spans the program did not
    write (and with ``journal_s`` the counter), ``failed`` trials that did not
    succeed."""
    spans = [{"name": "suggest", "t0": 99.0, "t1": 99.1, "args": {}}]
    for trial, s, k in FOUR_TRIALS:
        spans += journaled(trial, s, k, "Failed" if trial in failed else "Succeeded")
    spans = [s for s in spans if s["name"] not in drop]
    if "journal_s" in drop:
        for s in spans:
            s["args"].pop("journal_s", None)
    done = [s for s in spans if s["name"] == "trial" and s["args"]["condition"] == "Succeeded"]
    ctx = make_ctx(spans, t1=slice_end)
    ctx.update(t0=99.0, done=done, last_end=done[-1]["t1"])
    return ctx


# t2 -> t3: tail 0.2, gap 0.1, head 1.0; t3 -> t4: tail 0.4, gap 0.3, head 1.5
HANDOVER = {
    "trial_handover_s": (1.3 + 2.2) / 2,
    "trial_tail_s": 0.3,
    "trial_head_s": 1.25,
    "orch_dispatch_wait_s": 0.05,  # slot_free_s of t3 and t4
    "orch_dispatch_s": 0.075,  # the dispatch spans of t3 and t4
    "orch_settle_s": 0.075,  # the settle spans of t2 and t3
    # journal_s on four dispatches (0.003 x 16), on the three settles that end
    # by the last trial's end (0.004 x 13) and on one trial (a retry), a trial
    "orch_journal_ms": 1000.0 * (0.048 + 0.052 + 0.01) / 4,
}
# what each reader cannot do without
NEEDS = {
    "trial_handover_s": "trial.programs",
    "trial_tail_s": "trial.eval",
    "trial_head_s": "trial.programs",
    "orch_dispatch_wait_s": "orch.dispatch",
    "orch_dispatch_s": "orch.dispatch",
    "orch_settle_s": "orch.settle",
    "orch_journal_ms": "journal_s",
}
WANT = {**ONE_TRIAL, **HANDOVER}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reads_the_number(metric):
    if metric in HANDOVER:
        ctx = handover_ctx()
    else:
        ctx = make_ctx(trial_spans("t1", 100.0), busy=BUSY)
    assert reader(metric)(ctx) == pytest.approx(WANT[metric], abs=1e-9)


@pytest.mark.parametrize("metric", sorted(HANDOVER))
def test_leaves_out_the_boundary_the_slice_end_touches(metric):
    """A slice that ends before any trial does keeps all three boundaries
    (t1's long tail and t2's head among them); one that ends after t2 keeps
    t3 -> t4 alone.  ``orch_journal_ms`` reads the whole window either way."""
    every = {
        "trial_handover_s": (2.7 + 1.3 + 2.2) / 3, "trial_tail_s": 2.6 / 3, "trial_head_s": 1.0,
        "orch_dispatch_wait_s": 0.04, "orch_dispatch_s": 0.06, "orch_settle_s": 0.65 / 3,
    }
    last = {
        "trial_handover_s": 2.2, "trial_tail_s": 0.4, "trial_head_s": 1.5,
        "orch_dispatch_wait_s": 0.06, "orch_dispatch_s": 0.09, "orch_settle_s": 0.10,
    }
    read = reader(metric)
    assert read(handover_ctx(slice_end=99.5)) == pytest.approx(every.get(metric, HANDOVER[metric]), abs=1e-9)
    assert read(handover_ctx(slice_end=141.0)) == pytest.approx(last.get(metric, HANDOVER[metric]), abs=1e-9)


def test_the_hand_over_is_the_sum_of_its_parts():
    """tail + gap + head, the gap from the ``trial`` spans of the boundaries
    kept; and what the schedule loop did lies inside the gap."""
    ctx = handover_ctx()
    gaps = [140.3 - 140.2, 160.6 - 160.3]
    gap = sum(gaps) / len(gaps)
    parts = reader("trial_tail_s")(ctx) + gap + reader("trial_head_s")(ctx)
    assert parts == pytest.approx(reader("trial_handover_s")(ctx), abs=1e-9)
    assert reader("orch_dispatch_wait_s")(ctx) + reader("orch_dispatch_s")(ctx) <= gap + 1e-9


@pytest.mark.parametrize("metric", sorted(ONE_TRIAL))
def test_divides_by_the_trials_whole_inside_the_slice(metric):
    """Two trials whole inside the slice, the second with every part twice as
    long, and a third that the slice cuts: the mean of the first two."""
    spans = (
        trial_spans("t1", 100.0) + trial_spans("t2", 125.0, scale=2.0)
        + trial_spans("t3", 140.0, scale=100.0)
    )
    busy = BUSY + [(a + 25.0, b + 25.0) for a, b in BUSY]
    value = reader(metric)(make_ctx(spans, t1=150.0, busy=busy))
    if metric == "trial_eval_idle_s":
        want = ONE_TRIAL[metric]  # the evals and the busy stretches do not scale
    else:
        want = 1.5 * ONE_TRIAL[metric]
    assert value == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("metric", sorted(ONE_TRIAL))
def test_none_without_the_span(metric):
    """The parent commit's journal: ``trial`` and ``train_fn`` with no
    counters, nothing inside.  And a slice that holds no whole trial."""
    old = [
        span("trial", 99.5, 120.5, "t1"),
        span("train_fn", 100.0, 120.0, "t1"),
        {"name": "suggest", "t0": 99.0, "t1": 99.1, "args": {}},
    ]
    assert reader(metric)(make_ctx(old, busy=BUSY)) is None
    assert reader(metric)(make_ctx(trial_spans("t1", 100.0), t1=110.0, busy=BUSY)) is None


@pytest.mark.parametrize("metric", sorted(HANDOVER))
def test_none_without_the_span_or_a_boundary(metric):
    """The journal without the span (or counter) the reader needs: a trial
    function that is not ``train_lm`` writes no ``trial.eval``, the parent
    commit no ``orch.dispatch``.  And a window with no boundary between two
    completed trials after the slice's end."""
    read = reader(metric)
    assert read(handover_ctx(drop=(NEEDS[metric],))) is None
    if metric == "trial_handover_s":
        assert read(handover_ctx(drop=("trial.eval",))) is None
    if metric != "orch_journal_ms":
        assert read(handover_ctx(failed=("t3",))) is None  # t2 -> t3 -> t4 both gone
        assert read(handover_ctx(slice_end=161.0)) is None


def test_every_new_metric_has_its_entry_and_reader():
    import json

    root = os.path.join(os.path.dirname(__file__), os.pardir)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    rows = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    compile_layer = {"trial_jit_trace_s", "trial_jit_lower_s", "trial_exec_load_s"}
    for metric in ONE_TRIAL:
        row = rows[metric]
        assert row["moves"] == "trials_per_hour" and row["better"] == "lower"
        assert row["layer"] == ("compile" if metric in compile_layer else "trial runner")
        assert set(row["workloads"]) <= cells
        assert callable(reader(metric))
    # PR 39's seven: every cell reports them (no ``workloads``), a counter's
    # source says so, and they stand together in this order (later PRs append
    # after them)
    order = [
        "trial_handover_s", "trial_tail_s", "trial_head_s", "orch_dispatch_wait_s",
        "orch_dispatch_s", "orch_settle_s", "orch_journal_ms",
    ]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(order[0])
    assert names[first : first + 7] == order and set(order) == set(HANDOVER)
    for metric in order:
        row = rows[metric]
        assert row["moves"] == "trials_per_hour" and row["better"] == "lower"
        assert "workloads" not in row
        assert row["layer"] == ("trial runner" if metric in ("trial_tail_s", "trial_head_s") else "orchestrator")
        assert row["unit"] == ("ms" if metric.endswith("_ms") else "s")
        counter = metric in ("orch_dispatch_wait_s", "orch_journal_ms")
        assert row["source"] == ("program_counter" if counter else "program_span")
        assert callable(reader(metric))
    # the helper names no metric, so run.py never loads it as a reader
    assert "_handover" not in rows and os.path.exists(os.path.join(METRICS, "_handover.py"))
