"""Vectorized trial cohorts: vmap-batched multi-trial execution.

Covers the four acceptance properties:
- cohort-vs-serial numerical equivalence (strict at the train-step level,
  loose at the MNIST workload level),
- a K=8 cohort executes with exactly ONE jit trace,
- a single diverging member fails alone (NaN isolation),
- cohort grouping respects the ``parallel_trial_count`` budget and a
  transient-failed member re-runs as a singleton trial.
"""

from __future__ import annotations

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from katib_tpu.core.types import (
    COHORT_KEY_LABEL,
    ExperimentSpec,
    ObjectiveSpec,
    ObjectiveType,
    ParameterAssignment,
    Trial,
    TrialAssignmentSet,
    TrialCondition,
    TrialSpec,
)
from katib_tpu.core.validation import ValidationError, validate_experiment
from katib_tpu.orchestrator.orchestrator import Orchestrator
from katib_tpu.parallel.train import (
    TrainState,
    cohort_trace_counter,
    make_cohort_train_step,
    make_train_step,
    stack_pytrees,
    unstack_pytree,
)
from katib_tpu.runner.cohort import (
    CohortContext,
    attach_cohort_fn,
    cohort_fn_of,
    run_cohort,
)
from katib_tpu.store.base import MemoryObservationStore
from katib_tpu.utils.faults import FailureKind
from tests.helpers import make_spec

OBJECTIVE = ObjectiveSpec(type=ObjectiveType.MINIMIZE, objective_metric_name="loss")


def _toy_loss(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _toy_tx():
    return optax.inject_hyperparams(optax.sgd)(learning_rate=0.0)


def _toy_state(tx, lr, dim=4, seed=0):
    k = jax.random.PRNGKey(seed)
    params = {
        "w": jax.random.normal(k, (dim,), jnp.float32) * 0.1,
        "b": jnp.zeros((), jnp.float32),
    }
    state = TrainState.create(params, tx)
    hp = dict(state.opt_state.hyperparams)
    hp["learning_rate"] = jnp.asarray(lr, jnp.float32)
    return state._replace(opt_state=state.opt_state._replace(hyperparams=hp))


def _toy_batch(dim=4, n=16, seed=1):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (n, dim), jnp.float32)
    y = jax.random.normal(k2, (n,), jnp.float32)
    return x, y


def _make_trial(name, spec_kw=None, **params):
    return Trial(
        name=name,
        experiment_name="cohort-test",
        spec=TrialSpec(
            assignments=[ParameterAssignment(k, v) for k, v in params.items()],
            **(spec_kw or {}),
        ),
    )


class TestCohortStepEquivalence:
    def test_cohort_matches_serial_float32(self):
        """K=4 members through ONE vmapped step == 4 serial runs."""
        dim, steps, lrs = 4, 10, [0.01, 0.05, 0.1, 0.2]
        batch = _toy_batch(dim)
        serial_tx = _toy_tx()
        serial_step = make_train_step(_toy_loss, serial_tx, donate=False)
        serial_final = []
        for lr in lrs:
            s = _toy_state(serial_tx, lr, dim)
            for _ in range(steps):
                s, m = serial_step(s, batch)
            serial_final.append(s)

        cohort_tx = _toy_tx()
        cohort_step = make_cohort_train_step(_toy_loss, cohort_tx, donate=False)
        states = stack_pytrees([_toy_state(cohort_tx, lr, dim) for lr in lrs])
        for _ in range(steps):
            states, metrics = cohort_step(states, batch)
        members = unstack_pytree(states, len(lrs))

        for s_serial, s_member in zip(serial_final, members):
            np.testing.assert_allclose(
                np.asarray(s_serial.params["w"]),
                np.asarray(s_member.params["w"]),
                rtol=1e-4,
                atol=1e-5,
            )
            np.testing.assert_allclose(
                float(s_serial.params["b"]), float(s_member.params["b"]), atol=1e-5
            )
        assert int(states.step[0]) == steps

    def test_single_trace_for_k8(self):
        """A K=8 cohort runs many steps with exactly ONE jit trace."""
        dim = 17  # unique shape: no earlier test shares this executable
        tx = _toy_tx()
        step = make_cohort_train_step(_toy_loss, tx, donate=False)
        states = stack_pytrees(
            [_toy_state(tx, 0.01 * (i + 1), dim) for i in range(8)]
        )
        batch = _toy_batch(dim)
        before = cohort_trace_counter.count
        for _ in range(6):
            states, _ = step(states, batch)
        assert cohort_trace_counter.count - before == 1

    def test_nan_member_frozen_others_unaffected(self):
        """An exploding member's lane freezes; healthy lanes match serial."""
        dim, lrs = 4, [0.01, float("inf"), 0.1]
        batch = _toy_batch(dim)
        tx = _toy_tx()
        step = make_cohort_train_step(_toy_loss, tx, donate=False)
        states = stack_pytrees([_toy_state(tx, lr, dim) for lr in lrs])
        for _ in range(5):
            states, metrics = step(states, batch)
        loss = np.asarray(metrics["loss"])
        assert not np.isfinite(loss[1])
        assert np.isfinite(loss[0]) and np.isfinite(loss[2])

        serial_tx = _toy_tx()
        serial_step = make_train_step(_toy_loss, serial_tx, donate=False)
        for idx, lr in ((0, 0.01), (2, 0.1)):
            s = _toy_state(serial_tx, lr, dim)
            for _ in range(5):
                s, _ = serial_step(s, batch)
            member = jax.tree_util.tree_map(lambda x: x[idx], states)
            np.testing.assert_allclose(
                np.asarray(s.params["w"]),
                np.asarray(member.params["w"]),
                rtol=1e-4,
                atol=1e-5,
            )

        # frozen: the diverged lane stops changing entirely
        before = jax.tree_util.tree_map(lambda x: np.asarray(x[1]), states.params)
        states, _ = step(states, batch)
        after = jax.tree_util.tree_map(lambda x: np.asarray(x[1]), states.params)
        np.testing.assert_array_equal(before["b"], after["b"])


class TestCohortContext:
    def _ctx(self, k=3, rules=None, **extra):
        trials = [
            _make_trial(f"t{i}", spec_kw={"early_stopping_rules": rules or []},
                        lr=0.01 * (i + 1), units=32)
            for i in range(k)
        ]
        store = MemoryObservationStore()
        return CohortContext(trials, store, OBJECTIVE, **extra), store, trials

    def test_stacked_and_shared(self):
        ctx, _, _ = self._ctx()
        lrs = np.asarray(ctx.stacked("lr"))
        np.testing.assert_allclose(lrs, [0.01, 0.02, 0.03])
        assert ctx.shared("units") == 32
        assert len(ctx) == 3

    def test_shared_disagreement_raises(self):
        trials = [_make_trial("a", units=32), _make_trial("b", units=64)]
        ctx = CohortContext(trials, MemoryObservationStore(), OBJECTIVE)
        with pytest.raises(ValueError, match="disagree"):
            ctx.shared("units")

    def test_report_unstacks_rows_per_member(self):
        ctx, store, trials = self._ctx()
        assert ctx.report(step=0, loss=[3.0, 2.0, 1.0], accuracy=[0.1, 0.2, 0.3])
        for i, t in enumerate(trials):
            obs = store.observation_for(t.name, OBJECTIVE)
            assert obs is not None
            (metric,) = [m for m in obs.metrics if m.name == "loss"]
            assert float(metric.value) == 3.0 - i

    def test_nonfinite_objective_fails_member_permanent(self):
        ctx, store, trials = self._ctx()
        ctx.report(step=0, loss=[1.0, float("nan"), 2.0])
        assert not ctx.alive(1)
        assert ctx.alive(0) and ctx.alive(2)
        res = ctx._settle(1)
        assert res.condition is TrialCondition.FAILED
        assert res.failure_kind is FailureKind.PERMANENT
        assert "diverged" in res.message
        # the NaN row never reached the store
        assert store.observation_for(trials[1].name, OBJECTIVE) is None

    def test_fail_member_transient_kind(self):
        ctx, _, _ = self._ctx()
        ctx.fail_member(0, "preempted", transient=True)
        res = ctx._settle(0)
        assert res.condition is TrialCondition.FAILED
        assert res.failure_kind is FailureKind.TRANSIENT
        # all members done -> the cohort should stop
        ctx.fail_member(1, "x")
        ctx.fail_member(2, "y")
        assert ctx.should_stop()


class TestRunCohort:
    def test_no_cohort_fn_falls_back_serial(self):
        calls = []

        def train_fn(tctx):
            calls.append(tctx.trial_name)
            tctx.report(loss=1.0)

        trials = [
            _make_trial(f"s{i}", spec_kw={"train_fn": train_fn}, lr=0.1)
            for i in range(2)
        ]
        store = MemoryObservationStore()
        results = run_cohort(trials, store, OBJECTIVE)
        assert sorted(calls) == ["s0", "s1"]
        assert all(
            r.condition is TrialCondition.SUCCEEDED for r in results.values()
        )

    def test_cohort_fn_exception_falls_back_serial(self):
        serial_calls = []

        def train_fn(tctx):
            serial_calls.append(tctx.trial_name)
            tctx.report(loss=1.0)

        def bad_cohort(cctx):
            raise RuntimeError("vectorized path exploded")

        attach_cohort_fn(train_fn, bad_cohort)
        trials = [
            _make_trial(f"f{i}", spec_kw={"train_fn": train_fn}, lr=0.1)
            for i in range(3)
        ]
        results = run_cohort(trials, MemoryObservationStore(), OBJECTIVE)
        assert sorted(serial_calls) == ["f0", "f1", "f2"]
        assert all(
            r.condition is TrialCondition.SUCCEEDED for r in results.values()
        )

    def test_success_path_results_and_metrics(self):
        def train_fn(tctx):  # pragma: no cover - cohort path used instead
            tctx.report(loss=99.0)

        def cohort(cctx):
            lrs = np.asarray(cctx.stacked("lr"))
            cctx.report(step=0, loss=list(lrs * 10))

        attach_cohort_fn(train_fn, cohort)
        assert cohort_fn_of(train_fn) is cohort
        trials = [
            _make_trial(f"c{i}", spec_kw={"train_fn": train_fn}, lr=0.1 * (i + 1))
            for i in range(4)
        ]
        store = MemoryObservationStore()
        results = run_cohort(trials, store, OBJECTIVE)
        assert all(
            r.condition is TrialCondition.SUCCEEDED for r in results.values()
        )
        for i, t in enumerate(trials):
            obs = store.observation_for(t.name, OBJECTIVE)
            np.testing.assert_allclose(
                float(obs.metrics[0].value), (i + 1.0), rtol=1e-6
            )


def _budget_fns(max_seen, lock, width):
    """train_fn/cohort_fn pair that records peak concurrent member count."""
    active = [0]

    def _enter(n):
        with lock:
            active[0] += n
            max_seen[0] = max(max_seen[0], active[0])

    def _exit(n):
        with lock:
            active[0] -= n

    def train_fn(tctx):
        _enter(1)
        try:
            import time

            time.sleep(0.05)
            tctx.report(loss=float(tctx.params["x"]))
        finally:
            _exit(1)

    def cohort_fn(cctx):
        k = len(cctx)
        _enter(k)
        try:
            import time

            time.sleep(0.05)
            cctx.report(step=0, loss=list(np.asarray(cctx.stacked("x"))))
        finally:
            _exit(k)

    attach_cohort_fn(train_fn, cohort_fn)
    return train_fn


class TestOrchestratorCohorts:
    def test_grouping_unit(self, tmp_path):
        orch = Orchestrator(workdir=str(tmp_path))
        # grouping requires a train_fn with a declared cohort twin
        train_fn = attach_cohort_fn(lambda ctx: None, lambda cctx: None)
        spec = make_spec(train_fn=train_fn, cohort_width=2, cohort_key="g")
        props = [
            TrialAssignmentSet(assignments=[ParameterAssignment("x", float(i))])
            for i in range(5)
        ]
        groups = orch._group_proposals(spec, props)
        sizes = sorted(len(g) for g in groups)
        assert sizes == [1, 2, 2]
        # every grouped proposal carries the key label for status/journal
        for g in groups:
            for p in g:
                assert p.labels.get(COHORT_KEY_LABEL) == "g"

    def test_grouping_without_key_stays_singleton(self, tmp_path):
        orch = Orchestrator(workdir=str(tmp_path))
        train_fn = attach_cohort_fn(lambda ctx: None, lambda cctx: None)
        # no cohort_key, no labels: keyless proposals stay singletons
        spec = make_spec(train_fn=train_fn, cohort_width=4)
        props = [
            TrialAssignmentSet(assignments=[ParameterAssignment("x", float(i))])
            for i in range(4)
        ]
        groups = orch._group_proposals(spec, props)
        assert sorted(len(g) for g in groups) == [1, 1, 1, 1]

    def test_cohorts_respect_parallel_budget(self, tmp_path):
        max_seen, lock = [0], threading.Lock()
        train_fn = _budget_fns(max_seen, lock, width=2)
        spec = make_spec(
            train_fn=train_fn,
            cohort_width=2,
            cohort_key="budget",
            parallel_trial_count=2,
            max_trial_count=6,
        )
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert exp.condition.is_terminal()
        assert len(exp.trials) == 6
        assert all(
            t.condition is TrialCondition.SUCCEEDED for t in exp.trials.values()
        )
        assert max_seen[0] <= 2, f"{max_seen[0]} members ran concurrently"

    def test_transient_member_rejoins_as_singleton(self, tmp_path):
        cohort_runs, serial_runs = [], []

        def train_fn(tctx):
            serial_runs.append(tctx.trial_name)
            tctx.report(loss=1.0)

        def cohort_fn(cctx):
            cohort_runs.append([t.name for t in cctx.members])
            cctx.fail_member(0, "injected preemption", transient=True)
            losses = [float("nan")] + [2.0] * (len(cctx) - 1)
            # row 0 is already failed; report settles the survivors
            cctx.report(step=0, loss=losses)

        attach_cohort_fn(train_fn, cohort_fn)
        spec = make_spec(
            train_fn=train_fn,
            cohort_width=2,
            cohort_key="rejoin",
            parallel_trial_count=2,
            max_trial_count=2,
            max_retries=1,
            retry_backoff_seconds=0.0,
        )
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert exp.condition.is_terminal()
        assert len(cohort_runs) == 1 and len(cohort_runs[0]) == 2
        # the transient-failed member re-ran serially under its own name
        assert serial_runs == [cohort_runs[0][0]]
        conditions = {t.name: t.condition for t in exp.trials.values()}
        assert all(c is TrialCondition.SUCCEEDED for c in conditions.values()), conditions
        retried = exp.trials[cohort_runs[0][0]]
        assert retried.retry_count == 1


class TestMnistCohort:
    STRUCT = dict(
        units=12, num_layers=1, epochs=1, batch_size=64,
        n_train=256, n_test=128, optimizer="momentum",
    )

    def _trial(self, name, lr):
        from katib_tpu.models.mnist import mnist_trial

        return _make_trial(
            name, spec_kw={"train_fn": mnist_trial}, lr=lr, **self.STRUCT
        )

    def test_mnist_cohort_matches_serial_k4(self):
        from katib_tpu.models.mnist import mnist_trial
        from katib_tpu.runner.trial_runner import run_trial

        lrs = [0.02, 0.05, 0.08, 0.11]
        acc_obj = ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
        )
        serial_store = MemoryObservationStore()
        for i, lr in enumerate(lrs):
            res = run_trial(self._trial(f"ser{i}", lr), serial_store, acc_obj)
            assert res.condition is TrialCondition.SUCCEEDED, res.message

        cohort_store = MemoryObservationStore()
        trials = [self._trial(f"coh{i}", lr) for i, lr in enumerate(lrs)]
        assert cohort_fn_of(mnist_trial) is not None
        results = run_cohort(trials, cohort_store, acc_obj)
        assert all(
            r.condition is TrialCondition.SUCCEEDED for r in results.values()
        ), {n: r.message for n, r in results.items()}

        for i in range(len(lrs)):
            s = serial_store.observation_for(f"ser{i}", acc_obj)
            c = cohort_store.observation_for(f"coh{i}", acc_obj)
            sv = float([m for m in s.metrics if m.name == "accuracy"][0].value)
            cv = float([m for m in c.metrics if m.name == "accuracy"][0].value)
            # bfloat16 model: identical batch schedule, small fp divergence
            assert abs(sv - cv) <= 0.1, (i, sv, cv)

    def test_mnist_cohort_single_trace_k8(self):
        lrs = [0.01 + 0.01 * i for i in range(8)]
        struct = dict(self.STRUCT, units=19)  # unique shape -> fresh trace
        from katib_tpu.models.mnist import mnist_trial

        trials = [
            _make_trial(f"tr{i}", spec_kw={"train_fn": mnist_trial}, lr=lr, **struct)
            for i, lr in enumerate(lrs)
        ]
        before = cohort_trace_counter.count
        results = run_cohort(trials, MemoryObservationStore(), OBJECTIVE_ACC)
        assert all(
            r.condition is TrialCondition.SUCCEEDED for r in results.values()
        ), {n: r.message for n, r in results.items()}
        assert cohort_trace_counter.count - before == 1


OBJECTIVE_ACC = ObjectiveSpec(
    type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
)


class TestSpecPlumbing:
    def test_validation_rejects_bad_width(self):
        spec = make_spec(cohort_width=0)
        with pytest.raises(ValidationError, match="cohort_width"):
            validate_experiment(spec)

    def test_validation_rejects_blackbox_cohorts(self):
        spec = make_spec(cohort_width=2, train_fn=None, command=["echo", "hi"])
        with pytest.raises(ValidationError, match="white-box"):
            validate_experiment(spec)

    def test_yaml_parses_cohort_fields(self):
        from katib_tpu.sdk.yaml_spec import experiment_spec_from_dict

        doc = {
            "metadata": {"name": "y"},
            "spec": {
                "objective": {"type": "minimize", "objectiveMetricName": "loss"},
                "algorithm": {"algorithmName": "random"},
                "parameters": [
                    {
                        "name": "lr",
                        "parameterType": "double",
                        "feasibleSpace": {"min": "0.01", "max": "0.1"},
                    }
                ],
                "cohortWidth": 8,
                "cohortKey": "mlp",
                "compileCache": "/tmp/xla-cache",
                "trialTemplate": {
                    "trialSpec": {
                        "spec": {
                            "template": {
                                "spec": {
                                    "containers": [
                                        {"name": "training", "command": ["echo"]}
                                    ]
                                }
                            }
                        }
                    }
                },
            },
        }
        spec = experiment_spec_from_dict(doc)
        assert spec.cohort_width == 8
        assert spec.cohort_key == "mlp"
        assert spec.compile_cache == "/tmp/xla-cache"

    def test_init_compile_cache(self, tmp_path, monkeypatch):
        import katib_tpu.runner.trial_runner as tr
        from katib_tpu.utils import observability as obs

        monkeypatch.setattr(tr, "_COMPILE_CACHE_DIR", None)
        monkeypatch.delenv("KATIB_COMPILE_CACHE", raising=False)
        cache = tmp_path / "xla"
        assert tr.init_compile_cache(str(cache)) == str(cache)
        assert jax.config.jax_compilation_cache_dir == str(cache)
        assert obs.compile_cache_enabled.get() == 1.0
        # first writer wins: the jax config is process-global
        assert tr.init_compile_cache(str(tmp_path / "other")) == str(cache)

    def test_init_compile_cache_env(self, tmp_path, monkeypatch):
        import katib_tpu.runner.trial_runner as tr

        monkeypatch.setattr(tr, "_COMPILE_CACHE_DIR", None)
        cache = tmp_path / "env-xla"
        monkeypatch.setenv("KATIB_COMPILE_CACHE", str(cache))
        assert tr.init_compile_cache(None) == str(cache)
        assert tr.compile_cache_dir() == str(cache)

    @pytest.mark.parametrize("rival", ["KATIB_COMPILE_CACHE", "compileCache", None])
    def test_cache_placed_from_outside_cannot_be_moved(
        self, tmp_path, monkeypatch, rival
    ):
        """JAX_COMPILATION_CACHE_DIR places the cache: jax read the variable
        itself, so the directory is used as is, the jax config is never
        updated, and neither the env knob nor the spec field moves it."""
        import katib_tpu.runner.trial_runner as tr

        monkeypatch.setattr(tr, "_COMPILE_CACHE_DIR", None)
        placed, other = tmp_path / "placed", tmp_path / "other"
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
        monkeypatch.delenv("KATIB_COMPILE_CACHE", raising=False)
        if rival == "KATIB_COMPILE_CACHE":
            monkeypatch.setenv("KATIB_COMPILE_CACHE", str(other))
        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda name, value: (updates.append(name), real_update(name, value)),
        )
        arg = str(other) if rival == "compileCache" else None
        assert tr.init_compile_cache(arg) == str(placed)
        assert tr.compile_cache_dir() == str(placed)
        assert "jax_compilation_cache_dir" not in updates
        assert placed.is_dir() and not other.exists()

    def test_compile_cache_last_resort_is_the_checkout(self, tmp_path, monkeypatch):
        """Nothing asked for: a fixed <checkout>/.jax_cache, not "off"."""
        import katib_tpu.runner.trial_runner as tr

        monkeypatch.setattr(tr, "_COMPILE_CACHE_DIR", None)
        monkeypatch.delenv("KATIB_COMPILE_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert tr.DEFAULT_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
        # resolved, but not wired here: this test must not point the
        # worker's jax at the checkout
        monkeypatch.setattr(tr, "DEFAULT_COMPILE_CACHE_DIR", str(tmp_path / "last"))
        assert tr.init_compile_cache(None) == str(tmp_path / "last")
