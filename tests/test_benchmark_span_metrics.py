"""The per-layer readers that take a trial's start apart
(``benchmark/layer_metrics/trial_*.py``): each is given a hand-made ``ctx``
(the program's spans on the wall clock and a stand-in for the traced slice) and
must give the number, divide it by the trials that lie whole inside the slice,
and give ``None`` where the program wrote no such span or counter."""

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


@pytest.mark.parametrize("metric", sorted(ONE_TRIAL))
def test_reads_the_number(metric):
    ctx = make_ctx(trial_spans("t1", 100.0), busy=BUSY)
    assert reader(metric)(ctx) == pytest.approx(ONE_TRIAL[metric], abs=1e-9)


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
