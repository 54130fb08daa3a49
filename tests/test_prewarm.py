"""AOT prewarm service + cohort shape bucketing (``katib_tpu/compile/``).

Covers the acceptance properties of the compile-amortization layer:
- bucket derivation: K -> padded power-of-two bucket, including the
  trial-axis interaction (bucket then round up to the axis multiple);
- the shape registry classifies first steps warm/cold and feeds the
  hit/miss counters exactly once per execution;
- the prewarm worker compiles a queued signature exactly once under
  duplicate submission, and a failing (or killed) worker never fails or
  stalls a trial/experiment — prewarm is strictly best-effort;
- ``init_compile_cache`` warns (instead of silently ignoring) when a
  second caller asks for a different directory.

CPU-only: conftest forces 8 virtual CPU devices, so the trial-axis cases
run on the same mesh shapes the TPU path uses.
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np
import pytest

from katib_tpu.compile.buckets import (
    bucket_size,
    bucket_table,
    bucketed_cohort_size,
    next_pow2,
)
from katib_tpu.compile.prewarm import (
    PrewarmRequest,
    PrewarmWorker,
    attach_prewarm_fn,
    prewarm_fn_of,
)
from katib_tpu.compile.registry import (
    REGISTRY,
    CompileSignature,
    ShapeRegistry,
    cohort_signature,
    shared_structural,
    trial_signature,
)
from katib_tpu.core.types import (
    ExperimentCondition,
    ObjectiveSpec,
    ObjectiveType,
    ParameterAssignment,
    Trial,
    TrialCondition,
    TrialSpec,
)
from katib_tpu.orchestrator.orchestrator import Orchestrator
from katib_tpu.parallel.mesh import TRIAL_AXIS, make_mesh
from katib_tpu.runner.cohort import CohortContext, attach_cohort_fn, run_cohort
from katib_tpu.store.base import MemoryObservationStore
from katib_tpu.utils import observability as obs
from tests.helpers import make_spec

OBJECTIVE = ObjectiveSpec(type=ObjectiveType.MINIMIZE, objective_metric_name="loss")

# normal terminal conditions for a run that completed without error
_DONE = (
    ExperimentCondition.SUCCEEDED,
    ExperimentCondition.MAX_TRIALS_REACHED,
    ExperimentCondition.GOAL_REACHED,
)


def _make_trial(name, spec_kw=None, **params):
    return Trial(
        name=name,
        experiment_name="prewarm-test",
        spec=TrialSpec(
            assignments=[ParameterAssignment(k, v) for k, v in params.items()],
            **(spec_kw or {}),
        ),
    )


def _sig(program: str, k: int = 2) -> CompileSignature:
    return CompileSignature(program=program, shapes=(("units", "8"),), k=k)


def _total(metric) -> float:
    return sum(v for _, v in metric.samples())


class TestBuckets:
    def test_next_pow2(self):
        assert [next_pow2(k) for k in (1, 2, 3, 4, 5, 7, 8, 9)] == [
            1, 2, 4, 4, 8, 8, 8, 16,
        ]

    def test_bucket_table(self):
        # the K -> bucket map the whole layer hangs off: 3- and 4-member
        # cohorts share one executable, 5..8 share the next
        assert bucket_table(9) == [
            (1, 1), (2, 2), (3, 4), (4, 4),
            (5, 8), (6, 8), (7, 8), (8, 8), (9, 16),
        ]

    def test_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            bucket_size(0)

    def test_trial_axis_multiple(self):
        # pow2 first, then round up to the axis multiple: with 3 devices on
        # the trial axis, K=3 -> pow2 4 -> 6 (2 members per device)
        assert bucket_size(3, multiple=3) == 6
        assert bucket_size(8, multiple=3) == 9

    def test_bucketed_cohort_size_on_mesh(self):
        import jax

        mesh = make_mesh({TRIAL_AXIS: 4}, devices=jax.devices()[:4])
        assert bucketed_cohort_size(2, mesh) == 4  # pow2 2, axis multiple 4
        assert bucketed_cohort_size(3, mesh) == 4
        assert bucketed_cohort_size(5, mesh) == 8
        assert bucketed_cohort_size(3, None) == 4

    def test_cohort_context_padded_size(self):
        trials = [_make_trial(f"b{i}", lr=0.1) for i in range(3)]
        store = MemoryObservationStore()
        assert CohortContext(trials, store, OBJECTIVE).padded_size == 3
        assert CohortContext(trials, store, OBJECTIVE, buckets=True).padded_size == 4

    def test_ghost_rows_dropped_from_store(self):
        """A bucketed cohort pads K=3 to 4; the ghost row must never reach
        the observation store."""

        def train_fn(tctx):  # pragma: no cover - cohort path used
            tctx.report(loss=0.0)

        def cohort(cctx):
            assert cctx.padded_size == 4
            lrs = np.asarray(cctx.stacked("lr"))
            cctx.report(step=0, loss=list(lrs * 10))

        attach_cohort_fn(train_fn, cohort)
        trials = [
            _make_trial(f"g{i}", spec_kw={"train_fn": train_fn}, lr=0.1 * (i + 1))
            for i in range(3)
        ]
        store = MemoryObservationStore()
        results = run_cohort(trials, store, OBJECTIVE, buckets=True)
        assert all(
            r.condition is TrialCondition.SUCCEEDED for r in results.values()
        )
        for i, t in enumerate(trials):
            got = store.observation_for(t.name, OBJECTIVE)
            np.testing.assert_allclose(float(got.metrics[0].value), i + 1.0, rtol=1e-6)


class TestShapeRegistry:
    def test_float_params_excluded(self):
        """lr/momentum are runtime operands — two trials differing only in
        floats share one signature; a structural int splits them."""
        t1 = _make_trial("r1", lr=0.01, units=32)
        t2 = _make_trial("r2", lr=0.2, units=32)
        t3 = _make_trial("r3", lr=0.01, units=64)
        assert trial_signature(None, t1).key() == trial_signature(None, t2).key()
        assert trial_signature(None, t1).key() != trial_signature(None, t3).key()

    def test_shared_structural_drops_varying(self):
        shared = shared_structural(
            [{"units": 32, "lr": 0.1, "seedish": 1}, {"units": 32, "lr": 0.5, "seedish": 2}]
        )
        assert shared == {"units": 32}

    def test_cohort_signature_uses_padded_k(self):
        trials = [_make_trial(f"k{i}", lr=0.1, units=8) for i in range(3)]
        sig3 = cohort_signature(None, trials, 4)
        sig4 = cohort_signature(None, trials + [_make_trial("k3", lr=0.9, units=8)], 4)
        # 3 and 4 members in the same bucket -> identical signature
        assert sig3.key() == sig4.key()

    def test_classify_then_record_flips_warm(self):
        reg = ShapeRegistry()
        sig = CompileSignature(program="test_classify_prog", k=2)
        assert reg.classify(sig) == "cold"
        assert reg.record(sig) is True
        assert reg.record(sig) is False  # dedupe
        assert reg.classify(sig) == "warm"

    def test_note_first_step_counts_once_each(self):
        reg = ShapeRegistry()
        sig = CompileSignature(program="test_note_prog_unique", k=1)
        h0 = obs.compile_cache_hits.get(program=sig.program)
        m0 = obs.compile_cache_misses.get(program=sig.program)
        assert reg.note_first_step(sig, 0.5) == "cold"
        assert reg.note_first_step(sig, 0.1) == "warm"
        assert obs.compile_cache_misses.get(program=sig.program) == m0 + 1
        assert obs.compile_cache_hits.get(program=sig.program) == h0 + 1


class TestRegistryCompaction:
    def _registry_file(self, tmp_path, monkeypatch):
        import katib_tpu.compile.registry as registry_mod

        monkeypatch.setattr(registry_mod, "_cache_dir", lambda: str(tmp_path))
        return tmp_path / "shape_registry.jsonl"

    def test_duplicate_rows_compact_on_open(self, tmp_path, monkeypatch):
        path = self._registry_file(tmp_path, monkeypatch)
        sig = _sig("compact.step")
        row = {
            "key": sig.key(), "program": sig.program, "k": sig.k,
            "mesh": sig.mesh, "shapes": dict(sig.shapes),
            "donation": sig.donation, "source": "trial",
        }
        lines = [dict(row), dict(row, cost={"flops": 1.0}),
                 dict(row, cost={"flops": 2.0})]
        path.write_text("".join(json.dumps(r) + "\n" for r in lines))
        reg = ShapeRegistry()
        assert reg.seen(sig)  # triggers load + compaction
        # the freshest cost won the merge
        assert reg.cost_of(sig) == {"flops": 2.0}
        kept = [json.loads(l) for l in path.read_text().splitlines()]
        assert len(kept) == 1
        assert kept[0]["cost"] == {"flops": 2.0}
        # no temp residue from the durable rewrite
        assert os.listdir(tmp_path) == ["shape_registry.jsonl"]

    def test_unique_rows_left_alone(self, tmp_path, monkeypatch):
        path = self._registry_file(tmp_path, monkeypatch)
        rows = [
            {"key": _sig(f"p{i}.step").key(), "program": f"p{i}.step",
             "k": 2, "mesh": "", "shapes": {}, "donation": True,
             "source": "trial"}
            for i in range(3)
        ]
        body = "".join(json.dumps(r) + "\n" for r in rows)
        path.write_text(body)
        reg = ShapeRegistry()
        assert len(reg.signatures()) == 3
        assert path.read_text() == body  # byte-identical: no rewrite

    def test_torn_tail_with_dupes_heals(self, tmp_path, monkeypatch):
        path = self._registry_file(tmp_path, monkeypatch)
        sig = _sig("torn.step")
        row = {
            "key": sig.key(), "program": sig.program, "k": sig.k,
            "mesh": sig.mesh, "shapes": dict(sig.shapes),
            "donation": sig.donation, "source": "trial",
        }
        path.write_text(
            json.dumps(row) + "\n" + json.dumps(row) + "\n" + '{"key": "to'
        )
        with pytest.warns(RuntimeWarning, match="torn"):
            reg = ShapeRegistry()
            assert reg.seen(sig)
        # compaction rewrote the file: dupes merged, torn tail gone
        kept = path.read_text()
        assert kept.endswith("\n") and len(kept.splitlines()) == 1
        assert ShapeRegistry().seen(sig)


class TestPrewarmWorker:
    def test_compiles_queued_signature_exactly_once(self):
        calls = []
        done = threading.Event()

        def train_fn(ctx):  # pragma: no cover - never run here
            pass

        def prewarm(shared, k, mesh=None):
            calls.append((dict(shared), k))
            done.set()

        attach_prewarm_fn(train_fn, prewarm)
        assert prewarm_fn_of(train_fn) is prewarm
        reg = ShapeRegistry()
        worker = PrewarmWorker(registry=reg)
        req = PrewarmRequest(train_fn=train_fn, shared={"units": 16}, k=4)
        try:
            assert worker.submit(req) is True
            # duplicate submits race the first compile; at most one runs
            worker.submit(req)
            worker.submit(req)
            assert worker.drain(timeout=10.0)
            assert done.wait(5.0)
            assert calls == [({"units": 16}, 4)]
            assert worker.compiled == 1
            # once registered, submission short-circuits to False
            assert worker.submit(req) is False
            assert reg.seen(req.signature())
        finally:
            worker.stop()

    def test_no_prewarm_twin_is_noop(self):
        worker = PrewarmWorker(registry=ShapeRegistry())
        assert worker.submit(PrewarmRequest(train_fn=lambda ctx: None)) is False

    def test_failure_is_contained(self):
        """A blowing-up prewarm fn is logged and swallowed; the worker keeps
        serving later requests."""
        ok = threading.Event()

        def bad_train(ctx):  # pragma: no cover
            pass

        def good_train(ctx):  # pragma: no cover
            pass

        attach_prewarm_fn(bad_train, lambda s, k, m=None: 1 / 0)
        attach_prewarm_fn(good_train, lambda s, k, m=None: ok.set())
        reg = ShapeRegistry()
        worker = PrewarmWorker(registry=reg)
        try:
            assert worker.submit(PrewarmRequest(train_fn=bad_train, k=2))
            assert worker.submit(PrewarmRequest(train_fn=good_train, k=2))
            assert worker.drain(timeout=10.0)
            assert ok.wait(5.0)
            assert worker.failed == 1
            assert worker.compiled == 1
            # the failed signature stays unregistered: the trial compiles
            # live and classifies honestly cold
            assert not reg.seen(PrewarmRequest(train_fn=bad_train, k=2).signature())
        finally:
            worker.stop()

    def test_stop_mid_compile_is_bounded(self):
        """stop() while a compile is in flight returns within its timeout
        and never raises — the daemon thread is abandoned by design."""
        release = threading.Event()

        def train_fn(ctx):  # pragma: no cover
            pass

        attach_prewarm_fn(train_fn, lambda s, k, m=None: release.wait(10.0))
        worker = PrewarmWorker(registry=ShapeRegistry())
        assert worker.submit(PrewarmRequest(train_fn=train_fn, k=2))
        t0 = time.monotonic()
        worker.stop(timeout=0.5)
        assert time.monotonic() - t0 < 5.0
        release.set()  # let the abandoned thread finish promptly


class TestWarmClassification:
    def test_second_cohort_same_bucket_is_hit(self):
        """Two cohorts of different K in the same bucket: the first first
        step classifies cold, the second warm — the tentpole property."""
        REGISTRY.reset()

        def train_fn(tctx):  # pragma: no cover - cohort path used
            tctx.report(loss=0.0)

        def cohort(cctx):
            lrs = np.asarray(cctx.stacked("lr"))
            cctx.report(step=0, loss=list(lrs))

        attach_cohort_fn(train_fn, cohort)

        def trials(tag, k):
            return [
                _make_trial(
                    f"{tag}{i}", spec_kw={"train_fn": train_fn}, lr=0.1, units=32
                )
                for i in range(k)
            ]

        hits0 = _total(obs.compile_cache_hits)
        misses0 = _total(obs.compile_cache_misses)
        r1 = run_cohort(trials("w", 3), MemoryObservationStore(), OBJECTIVE, buckets=True)
        r2 = run_cohort(trials("x", 4), MemoryObservationStore(), OBJECTIVE, buckets=True)
        assert all(
            r.condition is TrialCondition.SUCCEEDED
            for r in list(r1.values()) + list(r2.values())
        )
        assert _total(obs.compile_cache_misses) == misses0 + 1
        assert _total(obs.compile_cache_hits) == hits0 + 1

    def test_different_bucket_is_miss(self):
        REGISTRY.reset()

        def train_fn(tctx):  # pragma: no cover
            tctx.report(loss=0.0)

        def cohort(cctx):
            cctx.report(step=0, loss=list(np.asarray(cctx.stacked("lr"))))

        attach_cohort_fn(train_fn, cohort)
        misses0 = _total(obs.compile_cache_misses)
        for tag, k in (("d", 2), ("e", 5)):  # buckets 2 and 8
            run_cohort(
                [
                    _make_trial(f"{tag}{i}", spec_kw={"train_fn": train_fn}, lr=0.1)
                    for i in range(k)
                ],
                MemoryObservationStore(),
                OBJECTIVE,
                buckets=True,
            )
        assert _total(obs.compile_cache_misses) == misses0 + 2


class TestOrchestratorPrewarm:
    def _run(self, tmp_path, train_fn, **spec_kw):
        spec = make_spec(
            name=f"prewarm-{spec_kw.get('cohort_width', 1)}",
            train_fn=train_fn,
            max_trial_count=4,
            parallel_trial_count=2,
            **spec_kw,
        )
        orch = Orchestrator(workdir=str(tmp_path))
        return orch.run(spec)

    def test_failing_prewarm_never_fails_experiment(self, tmp_path):
        """The acceptance contract: a prewarm twin that blows up on every
        call degrades to cold first steps, nothing else."""

        def train_fn(tctx):
            tctx.report(loss=float(tctx.params["x"]))

        def cohort(cctx):
            cctx.report(step=0, loss=list(np.asarray(cctx.stacked("x"))))

        attach_cohort_fn(train_fn, cohort)
        attach_prewarm_fn(train_fn, lambda s, k, m=None: 1 / 0)
        exp = self._run(tmp_path, train_fn, cohort_width=2)
        assert exp.condition in _DONE
        assert all(
            t.condition is TrialCondition.SUCCEEDED for t in exp.trials.values()
        )

    def test_slow_prewarm_never_stalls_shutdown(self, tmp_path):
        """A compile still in flight at experiment end is abandoned on its
        daemon thread; run() must not wait it out."""
        hang = threading.Event()

        def train_fn(tctx):
            tctx.report(loss=float(tctx.params["x"]))

        def cohort(cctx):
            cctx.report(step=0, loss=list(np.asarray(cctx.stacked("x"))))

        attach_cohort_fn(train_fn, cohort)
        attach_prewarm_fn(train_fn, lambda s, k, m=None: hang.wait(30.0))
        t0 = time.monotonic()
        try:
            exp = self._run(tmp_path, train_fn, cohort_width=2)
        finally:
            hang.set()
        assert exp.condition in _DONE
        assert time.monotonic() - t0 < 25.0

    def test_prewarm_disabled_by_spec(self, tmp_path):
        called = threading.Event()

        def train_fn(tctx):
            tctx.report(loss=float(tctx.params["x"]))

        attach_prewarm_fn(train_fn, lambda s, k, m=None: called.set())
        exp = self._run(tmp_path, train_fn, prewarm=False)
        assert exp.condition in _DONE
        time.sleep(0.1)  # a stray worker would have fired by now
        assert not called.is_set()


class TestInitCompileCacheWarning:
    def test_second_different_dir_warns(self, tmp_path, monkeypatch):
        import katib_tpu.runner.trial_runner as tr

        monkeypatch.delenv("KATIB_COMPILE_CACHE", raising=False)
        first = tr.init_compile_cache(str(tmp_path / "a"))
        if first is None:
            pytest.skip("compile cache unavailable in this jax build")
        with pytest.warns(RuntimeWarning, match="first caller wins"):
            assert tr.init_compile_cache(str(tmp_path / "b")) == first
        # asking for the already-wired dir stays silent
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert tr.init_compile_cache(first) == first
