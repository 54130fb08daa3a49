"""Span tracing: journal semantics, restart resume, Chrome-trace export, and
the orchestrator producing matching spans for every trial of a CPU run."""

import json
import os
import threading

import pytest

from katib_tpu.core.types import (
    AlgorithmSpec,
    ExperimentSpec,
    FeasibleSpace,
    ObjectiveSpec,
    ObjectiveType,
    ParameterSpec,
    ParameterType,
)
from katib_tpu.utils import tracing


class TestTracer:
    def test_span_records_jsonl(self, tmp_path):
        path = tracing.trace_path(str(tmp_path), "exp")
        tracer = tracing.Tracer(path, experiment="exp")
        with tracer.span("work", trial="t1") as sp:
            sp.set(condition="Succeeded")
        tracer.close()
        (rec,) = tracing.read_journal(path)
        assert rec["name"] == "work"
        assert rec["dur"] >= 0
        assert rec["args"] == {
            "trial": "t1",
            "condition": "Succeeded",
            "experiment": "exp",
        }

    def test_span_tags_error_and_reraises(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)
        try:
            with tracer.span("boom"):
                raise ValueError("x")
        except ValueError:
            pass
        tracer.close()
        (rec,) = tracing.read_journal(path)
        assert rec["args"]["error"] == "ValueError"

    def test_resume_continues_elapsed_base(self, tmp_path):
        """A reopened journal appends with ts past the prior max(ts+dur) —
        the restart-safe monotonic base (darts elapsed_s pattern)."""
        path = str(tmp_path / "t.jsonl")
        t1 = tracing.Tracer(path)
        t1.record("first", 0.0, 5.0)
        t1.close()
        t2 = tracing.Tracer(path)
        with t2.span("second"):
            pass
        t2.close()
        first, second = tracing.read_journal(path)
        assert second["ts"] >= first["ts"] + first["dur"] - 1e-6

    def test_corrupt_lines_skipped(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        with open(path, "w") as f:
            f.write('{"name": "ok", "ts": 0.0, "dur": 1.0}\n')
            f.write("{torn half-wri\n")
            f.write("null\n")
        assert [r["name"] for r in tracing.read_journal(path)] == ["ok"]
        t = tracing.Tracer(path)  # resume over the corrupt tail must not raise
        t.close()

    def test_ambient_tracer_noop_without_activation(self, tmp_path):
        # must not raise, and sp.set must be absorbed
        with tracing.span("orphan") as sp:
            sp.set(x=1)
        tracing.record_span("orphan", 0.1)
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)
        with tracing.use_tracer(tracer):
            assert tracing.current_tracer() is tracer
            with tracing.span("seen"):
                pass
            tracing.record_span("timed", 0.25, tag="x")
        assert tracing.current_tracer() is None
        tracer.close()
        recs = tracing.read_journal(path)
        assert [r["name"] for r in recs] == ["seen", "timed"]
        assert abs(recs[1]["dur"] - 0.25) < 1e-6


class TestCauses:
    """``id`` / ``parent`` / inherited ``trial``: which span caused a record,
    and which trial it belongs to."""

    def test_nested_and_recorded_spans_name_their_parent_and_trial(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path, experiment="e")
        with tracing.use_tracer(tracer):
            with tracing.span("trial", trial="t1"):
                with tracing.span("train_fn"):
                    with tracing.span("inner", trial="other"):
                        pass
                    tracing.record_span("timed", 0.001)
            tracing.record_span("root", 0.001)
        tracer.close()
        recs = {r["name"]: r for r in tracing.read_journal(path)}
        ids = [r["id"] for r in recs.values()]
        assert len(set(ids)) == len(ids) and all(isinstance(i, int) for i in ids)
        assert "parent" not in recs["trial"] and "parent" not in recs["root"]
        assert recs["train_fn"]["parent"] == recs["trial"]["id"]
        assert recs["inner"]["parent"] == recs["train_fn"]["id"]
        assert recs["timed"]["parent"] == recs["train_fn"]["id"]
        # a child that names no trial takes its parent's; one that does keeps it
        assert recs["train_fn"]["args"]["trial"] == "t1"
        assert recs["timed"]["args"]["trial"] == "t1"
        assert recs["inner"]["args"]["trial"] == "other"
        assert "trial" not in recs["root"]["args"]
        assert all(r["args"]["experiment"] == "e" for r in recs.values())

    def test_parent_is_per_thread(self, tmp_path):
        """A span on another thread is no child of what this thread has open."""
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)

        def other():
            with tracing.use_tracer(tracer), tracing.span("elsewhere"):
                pass

        with tracer.span("here"):
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        tracer.close()
        recs = {r["name"]: r for r in tracing.read_journal(path)}
        assert "parent" not in recs["elsewhere"]

    def test_ids_unique_and_parents_own_under_many_threads(self, tmp_path):
        """More threads than cores on one tracer: no id is handed out twice
        and every child names a parent opened by its own thread."""
        import sys

        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)
        n_threads, n_spans = 32, 50

        def work(k):
            with tracing.use_tracer(tracer):
                for i in range(n_spans):
                    with tracing.span("outer", trial=f"t{k}") as outer:
                        with tracing.span("inner"):
                            outer.add("n", 1)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        tracer.close()
        recs = tracing.read_journal(path)
        assert len(recs) == 2 * n_threads * n_spans
        by_id = {r["id"]: r for r in recs}
        assert len(by_id) == len(recs)
        for r in recs:
            if r["name"] == "inner":
                up = by_id[r["parent"]]
                assert up["name"] == "outer" and up["tid"] == r["tid"]
                assert r["args"]["trial"] == up["args"]["trial"]
            else:
                assert r["args"]["n"] == 1  # its own add, nobody else's

    def test_ids_continue_over_a_resume(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        t1 = tracing.Tracer(path)
        with t1.span("a"), t1.span("b"):
            pass
        t1.close()
        t2 = tracing.Tracer(path)
        with t2.span("c"):
            pass
        t2.close()
        ids = [r["id"] for r in tracing.read_journal(path)]
        assert len(set(ids)) == 3 and max(ids) == ids[-1]

    def test_wall_is_the_microsecond_of_ts(self, tmp_path):
        """``wall - ts`` is one anchor for every record, to the microsecond:
        what the benchmark places spans against the device trace with."""
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)
        for i in range(20):
            with tracer.span("s"):
                pass
            tracer.record("r", 0.1234567 * i, 0.0)
        tracer.close()
        recs = tracing.read_journal(path)
        anchors = [r["wall"] - r["ts"] for r in recs]
        assert max(anchors) - min(anchors) < 3e-6
        assert any(round(r["wall"] * 1e6) % 1000 for r in recs)  # not rounded to ms

    def test_add_reaches_every_open_span_of_the_thread_only(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)
        started, done = threading.Event(), threading.Event()

        def other():
            with tracer.span("other_thread"):
                started.set()
                assert done.wait(timeout=10)

        t = threading.Thread(target=other)
        t.start()
        assert started.wait(timeout=10)
        with tracer.span("outer") as outer:
            with tracer.span("middle"):
                with tracer.span("inner") as inner:
                    inner.add("n", 2)
                    inner.add("seconds", 0.25)
                outer.add("n", 1)  # not inside middle
            with tracer.span("sibling"):
                pass
        done.set()
        t.join(timeout=10)
        assert not t.is_alive()
        tracer.close()
        recs = {r["name"]: r for r in tracing.read_journal(path)}
        assert recs["inner"]["args"] == {"n": 2, "seconds": 0.25}
        assert recs["middle"]["args"] == {"n": 2, "seconds": 0.25}
        assert recs["outer"]["args"] == {"n": 3, "seconds": 0.25}
        assert "args" not in recs["sibling"] and "args" not in recs["other_thread"]
        # the null span absorbs it
        with tracing.span("orphan") as sp:
            sp.add("n", 1)

    def test_summarize_self_time(self):
        recs = [
            {"name": "trial", "id": 1, "ts": 0.0, "dur": 10.0},
            {"name": "train_fn", "id": 2, "parent": 1, "ts": 1.0, "dur": 8.0},
            # two children that overlap (a jitted function traced inside
            # another) and one that ends after its parent: the union counts
            {"name": "jit.trace", "id": 3, "parent": 2, "ts": 2.0, "dur": 1.0},
            {"name": "jit.trace", "id": 4, "parent": 2, "ts": 1.5, "dur": 2.5},
            {"name": "late", "id": 5, "parent": 2, "ts": 8.0, "dur": 3.0},
            {"name": "old", "ts": 0.0, "dur": 1.0},  # a journal from before ids
        ]
        by = {s["name"]: s for s in tracing.summarize(recs)}
        assert by["trial"]["self_s"] == 2.0
        assert by["train_fn"]["self_s"] == 8.0 - 2.5 - 1.0
        assert by["jit.trace"]["self_s"] == 3.5 and by["jit.trace"]["total_s"] == 3.5
        assert by["old"]["self_s"] == 1.0

    def test_chrome_trace_carries_id_and_parent(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)
        with tracer.span("a", trial="t"), tracer.span("b"):
            pass
        tracer.close()
        events = {
            e["name"]: e
            for e in tracing.to_chrome_trace(tracing.read_journal(path))["traceEvents"]
            if e["ph"] == "X"
        }
        assert events["b"]["args"]["parent"] == events["a"]["args"]["id"]
        assert events["b"]["args"]["trial"] == "t" and "parent" not in events["a"]["args"]


class TestJaxListeners:
    """jax's compile events as counters on the open spans (CPU)."""

    @staticmethod
    def _fresh_jit():
        import jax
        import jax.numpy as jnp

        def step_fn(x):  # a new function object: jax traces it anew
            return jnp.tanh(x) @ x

        return jax.jit(step_fn), jnp.ones((4, 4))

    def test_fresh_jit_inside_a_span_is_counted(self, tmp_path):
        fn, x = self._fresh_jit()
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)
        with tracing.use_tracer(tracer):
            with tracing.span("train_fn", trial="t1"):
                with tracing.span("trial.first_step"):
                    fn(x).block_until_ready()
                with tracing.span("after"):
                    fn(x).block_until_ready()  # cached: nothing built
        tracer.close()
        recs = tracing.read_journal(path)
        by = {r["name"]: r for r in recs}
        for name in ("trial.first_step", "train_fn"):
            args = by[name]["args"]
            assert args["jit_programs"] >= 1
            assert args["jit_trace_s"] > 0 and args["jit_lower_s"] > 0
            assert args["jit_backend_s"] > 0
        assert not set(by["after"]["args"]) & set(tracing.JIT_COUNTERS)
        # tracing nests (tanh and matmul inside step_fn): the union of the
        # intervals cannot outlast the span they lie in
        assert by["trial.first_step"]["args"]["jit_trace_s"] <= by["trial.first_step"]["dur"]
        # a long event is journaled by program name, inside the open span
        for r in recs:
            if r["name"].startswith("jit."):
                assert r["dur"] >= tracing.JIT_SPAN_MIN_S
                assert "step_fn" in r["args"]["program"]
                assert r["parent"] == by["trial.first_step"]["id"]
                assert r["args"]["trial"] == "t1"

    def test_long_events_become_spans_at_their_own_ends(self, tmp_path):
        """The listener, driven as jax drives it: ends are time.time() values."""
        import time

        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)
        trace, lower, backend = (
            "/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/jaxpr_to_mlir_module_duration",
            "/jax/core/compile/backend_compile_duration",
        )
        with tracing.use_tracer(tracer), tracing.span("train_fn", trial="t1"):
            now = time.time()
            # inner functions report first and lie inside the outer interval
            tracing._on_jax_time_span(trace, now + 0.10, now + 0.12, fun_name="matmul")
            tracing._on_jax_time_span(trace, now + 0.20, now + 0.23, fun_name="_reduce_sum")
            tracing._on_jax_time_span(trace, now + 0.05, now + 0.30, fun_name="step_fn")
            tracing._on_jax_time_span(lower, now + 0.30, now + 0.40, fun_name="jit(step_fn)")
            tracing._on_jax_event("/jax/compilation_cache/cache_misses")
            tracing._on_jax_time_span(backend, now + 0.40, now + 0.90, fun_name="jit(step_fn)")
            tracing._on_jax_event("/jax/compilation_cache/cache_hits")
            tracing._on_jax_time_span(backend, now + 0.90, now + 0.91, fun_name="jit(eval_fn)")
            tracing._on_jax_time_span("/jax/some/other/event", now, now + 9.0)
        tracer.close()
        recs = tracing.read_journal(path)
        (train,) = [r for r in recs if r["name"] == "train_fn"]
        args = train["args"]
        assert args["jit_trace_s"] == pytest.approx(0.25, abs=1e-5)  # union, not 0.30
        assert args["jit_lower_s"] == pytest.approx(0.10, abs=1e-5)
        assert args["jit_backend_s"] == pytest.approx(0.51, abs=1e-5)
        assert (args["jit_programs"], args["cache_hits"], args["cache_misses"]) == (1, 1, 1)
        spans = {(r["name"], r["args"]["program"]): r for r in recs if r["name"].startswith("jit.")}
        assert set(spans) == {
            ("jit.trace", "step_fn"), ("jit.lower", "jit(step_fn)"), ("jit.backend", "jit(step_fn)")
        }
        back = spans[("jit.backend", "jit(step_fn)")]
        assert back["args"]["cache"] == "miss" and back["parent"] == train["id"]
        assert back["wall"] == pytest.approx(now + 0.40, abs=2e-5)
        assert back["dur"] == pytest.approx(0.50, abs=1e-5)

    def test_no_tracer_no_record_no_error(self, tmp_path):
        fn, x = self._fresh_jit()
        assert tracing.current_tracer() is None
        fn(x).block_until_ready()
        tracing._on_jax_event("/jax/compilation_cache/cache_hits")
        tracing._on_jax_time_span("/jax/core/compile/backend_compile_duration", 0.0, 1.0, fun_name="f")
        assert not hasattr(tracing._active, "cache")

    def test_registered_once_over_two_tracers(self, tmp_path):
        from jax._src import monitoring

        a = tracing.Tracer(str(tmp_path / "a.jsonl"))
        b = tracing.Tracer(str(tmp_path / "b.jsonl"))
        with a.span("x"), b.span("y"):
            pass
        a.close()
        b.close()
        assert monitoring.get_event_time_span_listeners().count(tracing._on_jax_time_span) == 1
        assert monitoring.get_event_listeners().count(tracing._on_jax_event) == 1

    def test_open_span_is_a_profiler_annotation(self, tmp_path, monkeypatch):
        """While jax is imported a span is also a TraceAnnotation of its name
        that carries the journal record's ``id`` and the trial's name."""
        import jax

        seen = []

        class Annotation:
            def __init__(self, name, **kwargs):
                self.name = name
                seen.append(("made", name, kwargs))

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path)
        with tracer.span("trial.init", trial="t-1"), tracer.span("inner"):
            pass
        with tracer.span("orch.settle"):
            pass
        tracer.close()
        ids = {r["name"]: r["id"] for r in tracing.read_journal(path)}
        assert seen == [
            ("made", "trial.init", {"id": ids["trial.init"], "trial": "t-1"}),
            ("enter", "trial.init"),
            # a child takes its parent's trial before the annotation is made
            ("made", "inner", {"id": ids["inner"], "trial": "t-1"}),
            ("enter", "inner"), ("exit", "inner"), ("exit", "trial.init"),
            ("made", "orch.settle", {"id": ids["orch.settle"]}),
            ("enter", "orch.settle"), ("exit", "orch.settle"),
        ]

    def test_current_span_is_the_innermost_of_the_ambient_tracer(self, tmp_path):
        tracer = tracing.Tracer(str(tmp_path / "t.jsonl"))
        assert tracing.current_span() is tracing._NULL_SPAN  # no ambient tracer
        with tracing.use_tracer(tracer):
            assert tracing.current_span() is tracing._NULL_SPAN  # nothing open
            with tracing.span("outer") as outer, tracing.span("inner") as inner:
                assert tracing.current_span() is inner
                tracing.current_span().add("journal_s", 0.25)
            assert (outer.counters, inner.counters) == ({"journal_s": 0.25},) * 2
        tracer.close()


class TestChromeTraceExport:
    def test_export_validity(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        tracer = tracing.Tracer(path, experiment="e")
        with tracer.span("a", trial="t1"):
            pass
        tracer.record("b", 1.0, 2.5, step=3)
        tracer.close()
        out = str(tmp_path / "trace.json")
        assert tracing.export_chrome_trace(path, out) == 2
        doc = json.loads(open(out).read())
        assert doc["displayTimeUnit"] == "ms"
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(events) == 2
        for e in events:
            assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid", "args"}
            assert e["ts"] >= 0 and e["dur"] >= 0
        b = next(e for e in events if e["name"] == "b")
        assert b["ts"] == 1.0e6 and b["dur"] == 2.5e6
        # metadata rows label the emitting process
        assert any(e["ph"] == "M" for e in doc["traceEvents"])

    def test_export_empty_journal(self, tmp_path):
        out = str(tmp_path / "trace.json")
        assert tracing.export_chrome_trace(str(tmp_path / "missing.jsonl"), out) == 0
        assert not os.path.exists(out)

    def test_summarize(self):
        recs = [
            {"name": "a", "ts": 0, "dur": 1.0},
            {"name": "a", "ts": 1, "dur": 3.0},
            {"name": "b", "ts": 2, "dur": 0.5},
        ]
        summary = tracing.summarize(recs)
        assert [s["name"] for s in summary] == ["a", "b"]  # by total desc
        a = summary[0]
        assert a["count"] == 2 and a["total_s"] == 4.0 and a["mean_s"] == 2.0
        assert a["max_s"] == 3.0
        assert a["self_s"] == 4.0  # no children: all of it is its own


def _spec(name: str, n_trials: int = 3) -> ExperimentSpec:
    def train_fn(ctx):
        ctx.report(accuracy=float(ctx.params["x"]))

    return ExperimentSpec(
        name=name,
        algorithm=AlgorithmSpec(name="random"),
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
        ),
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1"))
        ],
        max_trial_count=n_trials,
        parallel_trial_count=2,
        train_fn=train_fn,
    )


class TestOrchestratorTracing:
    def test_every_trial_has_a_span(self, tmp_path):
        from katib_tpu.orchestrator.orchestrator import Orchestrator
        from katib_tpu.utils import observability as obs

        orch = Orchestrator(workdir=str(tmp_path))
        exp = orch.run(_spec("trace-e2e"))
        assert exp.condition.is_terminal()

        journal = tracing.trace_path(str(tmp_path), "trace-e2e")
        recs = tracing.read_journal(journal)
        trial_spans = {
            r["args"]["trial"]: r for r in recs if r["name"] == "trial"
        }
        # one complete (start+end → single "X" record) span per trial
        assert set(trial_spans) == set(exp.trials)
        for name, rec in trial_spans.items():
            assert rec["dur"] >= 0 and rec["ts"] >= 0
            assert rec["args"]["condition"] == exp.trials[name].condition.value
            assert rec["args"]["experiment"] == "trace-e2e"
        # train_fn spans nest inside trial spans (whitebox path)
        assert sum(1 for r in recs if r["name"] == "train_fn") == len(exp.trials)
        # suggestion-service spans + the terminal experiment span
        assert any(r["name"] == "suggest" for r in recs)
        exp_spans = [r for r in recs if r["name"] == "experiment"]
        assert len(exp_spans) == 1
        assert exp_spans[0]["args"]["trials"] == len(exp.trials)
        # ambient tracer is cleaned up after the run
        assert tracing.current_tracer() is None

        # exported Chrome trace is valid and complete
        out = str(tmp_path / "trace.json")
        assert tracing.export_chrome_trace(journal, out) == len(recs)
        doc = json.loads(open(out).read())
        names = [e["name"] for e in doc["traceEvents"]]
        assert "experiment" in names and "trial" in names

        # duration histograms on the global registry (cross-test counts can
        # only grow, so assert >= via the rendered series)
        text = obs.REGISTRY.render()
        assert "katib_trial_duration_seconds_bucket" in text
        assert "katib_suggestion_latency_seconds_bucket" in text
        assert obs.trial_duration.get_count(condition="Succeeded") >= len(exp.trials)

    def test_journal_survives_resume(self, tmp_path):
        """A resumed experiment appends to the same journal with a monotonic
        elapsed base: a second experiment span lands after the first."""
        from katib_tpu.core.types import ResumePolicy
        from katib_tpu.orchestrator.orchestrator import Orchestrator

        spec = _spec("trace-resume", n_trials=2)
        spec.resume_policy = ResumePolicy.LONG_RUNNING
        orch = Orchestrator(workdir=str(tmp_path))
        orch.run(spec)

        spec2 = _spec("trace-resume", n_trials=4)
        spec2.resume_policy = ResumePolicy.LONG_RUNNING
        orch2 = Orchestrator(workdir=str(tmp_path))
        exp2 = orch2.run(spec2, resume=True)
        assert len(exp2.trials) == 4

        recs = tracing.read_journal(tracing.trace_path(str(tmp_path), "trace-resume"))
        exp_spans = [r for r in recs if r["name"] == "experiment"]
        assert len(exp_spans) == 2
        # second run's span starts at or after the first run's span end
        assert (
            exp_spans[1]["ts"]
            >= exp_spans[0]["ts"] + exp_spans[0]["dur"] - 1e-6
        )
        assert len([r for r in recs if r["name"] == "trial"]) == 4

    def test_katib_trace_0_leaves_no_journal(self, tmp_path, monkeypatch):
        from katib_tpu.orchestrator.orchestrator import Orchestrator

        monkeypatch.setenv(tracing.TRACE_ENV, "0")
        exp = Orchestrator(workdir=str(tmp_path)).run(_spec("trace-off", n_trials=2))
        assert exp.condition.is_terminal() and len(exp.trials) == 2
        assert not os.path.exists(tracing.trace_path(str(tmp_path), "trace-off"))

    def test_transformer_trial_start_has_parts(self, tmp_path):
        """The spans inside a white-box trial's start: each carries the
        trial's name and has ``train_fn`` and ``trial`` above it."""
        from katib_tpu.models.transformer import transformer_trial
        from katib_tpu.orchestrator.orchestrator import Orchestrator

        def fixed(name, value):
            return ParameterSpec(
                name, ParameterType.DISCRETE, FeasibleSpace(list=[str(value)])
            )

        sizes = dict(
            d_model=16, n_heads=2, n_layers=1, seq_len=8, vocab_size=16,
            n_seq=32, batch_size=2, steps=12, lr=0.001,
        )
        spec = ExperimentSpec(
            name="trace-lm",
            algorithm=AlgorithmSpec(name="random"),
            objective=ObjectiveSpec(
                type=ObjectiveType.MINIMIZE, objective_metric_name="eval_loss"
            ),
            parameters=[fixed(k, v) for k, v in sizes.items()],
            max_trial_count=2,
            parallel_trial_count=1,
            train_fn=transformer_trial,
        )
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert [t.condition.value for t in exp.trials.values()] == ["Succeeded"] * 2

        recs = tracing.read_journal(tracing.trace_path(str(tmp_path), "trace-lm"))
        by_id = {r["id"]: r for r in recs}

        def ancestors(rec):
            names = []
            while "parent" in rec:
                rec = by_id[rec["parent"]]
                names.append(rec["name"])
            return names

        for nth, trial in enumerate(exp.trials):
            mine = [r for r in recs if r.get("args", {}).get("trial") == trial]
            count = lambda name: sum(r["name"] == name for r in mine)  # noqa: E731
            assert count("trial.data") == count("trial.init") == count("trial.first_step") == 1
            # 12 steps report at 0, 10 and 11
            assert count("trial.eval") == count("report") == 3
            for r in mine:
                if r["name"] in ("trial.data", "trial.init", "trial.first_step", "trial.eval", "report"):
                    assert ancestors(r) == ["train_fn", "trial"]
                elif r["name"].startswith("jit."):
                    assert ancestors(r)[-2:] == ["train_fn", "trial"]
            (first_eval,) = [r for r in mine if r["name"] == "trial.eval" and r["args"].get("first")]
            assert first_eval["args"]["step"] == 0
            (train,) = [r for r in mine if r["name"] == "train_fn"]
            assert set(tracing.JIT_COUNTERS) <= set(train["args"])
            # init, step_fn and eval_fn are built by the process's first
            # trial of a structure and found again by every later one
            (init,) = [r for r in mine if r["name"] == "trial.init"]
            (first_step,) = [r for r in mine if r["name"] == "trial.first_step"]
            if init["args"]["programs"] == "built":
                assert nth == 0
                assert train["args"]["jit_programs"] >= 3
                assert first_step["args"]["jit_programs"] == 1
            else:
                assert init["args"]["programs"] == "reused"
                assert train["args"]["jit_programs"] == 0
                assert not any(r["name"].startswith("jit.") for r in mine)

    @pytest.mark.parametrize("async_orch", [True, False], ids=["async-loops", "sync-loop"])
    def test_the_hand_over_between_two_trials_has_spans(self, tmp_path, async_orch):
        """Both loops journal, once a trial and under the trial's name:
        ``orch.dispatch`` (``slot_free_s``, ``journal_s``), ``orch.settle``
        (``journal_s``), ``trial.setup`` and ``trial.finalize`` below
        ``trial``, ``trial.programs`` below ``trial.init``."""
        from katib_tpu.models.transformer import transformer_trial
        from katib_tpu.orchestrator.orchestrator import Orchestrator

        sizes = dict(
            d_model=16, n_heads=2, n_layers=1, seq_len=8, vocab_size=16,
            n_seq=32, batch_size=2, steps=3, lr=0.001,
        )
        spec = ExperimentSpec(
            name="trace-handover",
            algorithm=AlgorithmSpec(name="random"),
            objective=ObjectiveSpec(
                type=ObjectiveType.MINIMIZE, objective_metric_name="eval_loss"
            ),
            parameters=[
                ParameterSpec(k, ParameterType.DISCRETE, FeasibleSpace(list=[str(v)]))
                for k, v in sizes.items()
            ],
            max_trial_count=3,
            parallel_trial_count=1,
            train_fn=transformer_trial,
            async_orch=async_orch,
        )
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert [t.condition.value for t in exp.trials.values()] == ["Succeeded"] * 3

        recs = tracing.read_journal(tracing.trace_path(str(tmp_path), "trace-handover"))
        by_id = {r["id"]: r for r in recs}
        parent = lambda r: by_id[r["parent"]]["name"] if "parent" in r else None  # noqa: E731
        for trial in exp.trials:
            mine = {}
            for r in recs:
                if r.get("args", {}).get("trial") == trial:
                    mine.setdefault(r["name"], []).append(r)
            for name in ("orch.dispatch", "orch.settle", "trial.setup", "trial.finalize", "trial.programs"):
                assert len(mine[name]) == 1, (trial, name)
            (dispatch,), (settle,) = mine["orch.dispatch"], mine["orch.settle"]
            assert dispatch["args"]["members"] == settle["args"]["members"] == 1
            assert dispatch["args"]["slot_free_s"] >= 0
            assert dispatch["args"]["journal_s"] > 0 and settle["args"]["journal_s"] > 0
            assert parent(dispatch) is None and parent(settle) is None
            (span,) = mine["trial"]
            assert parent(mine["trial.setup"][0]) == parent(mine["trial.finalize"][0]) == "trial"
            assert parent(mine["trial.programs"][0]) == "trial.init"
            # in order: dispatched, set up, trained, read back, settled
            (train,) = mine["train_fn"]
            end = lambda r: r["ts"] + r["dur"]  # noqa: E731
            assert dispatch["ts"] <= span["ts"] <= mine["trial.setup"][0]["ts"]
            assert end(mine["trial.setup"][0]) <= train["ts"] + 1e-6
            assert end(train) <= mine["trial.finalize"][0]["ts"] + 1e-6
            assert end(mine["trial.finalize"][0]) <= end(span) + 1e-6 <= end(settle) + 2e-6
            assert end(mine["trial.programs"][0]) <= end(mine["trial.init"][0]) + 1e-6
        # ``trace summary``: the two new children count against ``trial``'s
        # own time, which is what no span below it accounts for
        rows = {row["name"]: row for row in tracing.summarize(recs)}
        below = sum(rows[n]["total_s"] for n in ("trial.setup", "train_fn", "trial.finalize"))
        assert rows["trial"]["self_s"] == pytest.approx(rows["trial"]["total_s"] - below, abs=1e-5)
        assert rows["trial.setup"]["total_s"] > 0 and rows["trial.finalize"]["total_s"] > 0
