"""Per-trial wall-clock deadlines + bounded metrics-unavailable retry
(VERDICT r1 item 7; reference parity: e2e 40-min bound
``run-e2e-experiment.py:11``, metrics-not-reported requeue
``trial_controller.go:182-185``)."""

from __future__ import annotations

import sys
import threading
import time

from katib_tpu.core.types import (
    AlgorithmSpec,
    ExperimentCondition,
    ExperimentSpec,
    FeasibleSpace,
    MetricsCollectorKind,
    MetricsCollectorSpec,
    ObjectiveSpec,
    ObjectiveType,
    ParameterSpec,
    ParameterType,
    Trial,
    TrialCondition,
    TrialSpec,
)
from katib_tpu.orchestrator import Orchestrator
from katib_tpu.runner.trial_runner import run_trial
from katib_tpu.store.base import MemoryObservationStore

OBJECTIVE = ObjectiveSpec(
    type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
)


def make_trial(name="t", **spec_kw) -> Trial:
    spec_kw.setdefault("assignments", [])
    return Trial(name=name, spec=TrialSpec(**spec_kw))


class TestWhiteboxDeadline:
    def test_cooperative_deadline_fails_trial(self):
        def slow(ctx):
            for step in range(1000):
                if not ctx.report(step=step, accuracy=0.5):
                    return
                time.sleep(0.02)

        trial = make_trial(train_fn=slow, max_runtime_seconds=0.15)
        result = run_trial(trial, MemoryObservationStore(), OBJECTIVE)
        assert result.condition is TrialCondition.FAILED
        assert "max_runtime" in result.message

    def test_raise_if_stopped_deadline_classified_failed(self):
        def slow(ctx):
            for step in range(1000):
                ctx.report(step=step, accuracy=0.5)
                ctx.raise_if_stopped()
                time.sleep(0.02)

        trial = make_trial(train_fn=slow, max_runtime_seconds=0.15)
        result = run_trial(trial, MemoryObservationStore(), OBJECTIVE)
        assert result.condition is TrialCondition.FAILED
        assert "max_runtime" in result.message

    def test_fast_trial_unaffected(self):
        def fast(ctx):
            ctx.report(step=0, accuracy=0.9)

        trial = make_trial(train_fn=fast, max_runtime_seconds=30.0)
        result = run_trial(trial, MemoryObservationStore(), OBJECTIVE)
        assert result.condition is TrialCondition.SUCCEEDED


class TestBlackboxDeadline:
    def test_hung_subprocess_is_terminated(self):
        trial = make_trial(
            command=[sys.executable, "-c", "import time; time.sleep(60)"],
            max_runtime_seconds=0.5,
            metrics_collector=MetricsCollectorSpec(kind=MetricsCollectorKind.STDOUT),
        )
        t0 = time.monotonic()
        result = run_trial(trial, MemoryObservationStore(), OBJECTIVE)
        assert time.monotonic() - t0 < 15.0  # SIGTERM, not the full 60s
        assert result.condition is TrialCondition.FAILED
        assert "max_runtime" in result.message

    def test_fast_subprocess_unaffected(self):
        trial = make_trial(
            command=[sys.executable, "-c", "print('accuracy=0.8')"],
            max_runtime_seconds=30.0,
            metrics_collector=MetricsCollectorSpec(kind=MetricsCollectorKind.STDOUT),
        )
        result = run_trial(trial, MemoryObservationStore(), OBJECTIVE)
        assert result.condition is TrialCondition.SUCCEEDED


class TestMetricsRetry:
    def test_flaky_metrics_retried_to_success(self, tmp_path):
        """First run reports nothing; the bounded retry re-runs the trial
        and the second attempt reports — the trial ends SUCCEEDED."""
        attempts = {"n": 0}

        def flaky(ctx):
            attempts["n"] += 1
            if attempts["n"] >= 2:
                ctx.report(step=0, accuracy=0.7)

        spec = ExperimentSpec(
            name="retry-exp",
            algorithm=AlgorithmSpec(name="random"),
            objective=OBJECTIVE,
            parameters=[
                ParameterSpec("lr", ParameterType.DOUBLE, FeasibleSpace(min=0.0, max=1.0))
            ],
            max_trial_count=1,
            parallel_trial_count=1,
            metrics_retries=2,
            train_fn=flaky,
        )
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert exp.succeeded_count == 1
        assert attempts["n"] == 2

    def test_no_retry_by_default(self, tmp_path):
        attempts = {"n": 0}

        def silent(ctx):
            attempts["n"] += 1

        spec = ExperimentSpec(
            name="noretry-exp",
            algorithm=AlgorithmSpec(name="random"),
            objective=OBJECTIVE,
            parameters=[
                ParameterSpec("lr", ParameterType.DOUBLE, FeasibleSpace(min=0.0, max=1.0))
            ],
            max_trial_count=1,
            parallel_trial_count=1,
            train_fn=silent,
        )
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert exp.metrics_unavailable_count == 1
        assert attempts["n"] == 1

    def test_retry_budget_exhausts(self, tmp_path):
        attempts = {"n": 0}

        def never(ctx):
            attempts["n"] += 1

        spec = ExperimentSpec(
            name="exhaust-exp",
            algorithm=AlgorithmSpec(name="random"),
            objective=OBJECTIVE,
            parameters=[
                ParameterSpec("lr", ParameterType.DOUBLE, FeasibleSpace(min=0.0, max=1.0))
            ],
            max_trial_count=1,
            parallel_trial_count=1,
            metrics_retries=2,
            train_fn=never,
        )
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert exp.metrics_unavailable_count == 1
        assert attempts["n"] == 3  # initial + 2 retries


class TestRetryStopResponsiveness:
    def test_stop_interrupts_retry_backoff(self, tmp_path):
        """A stop() issued while a transient retry is sleeping out its
        backoff (30s here) must return promptly — the backoff waits on the
        stop event instead of a blind sleep."""

        attempted = threading.Event()

        def boom(ctx):
            attempted.set()
            raise OSError("preempted")

        spec = ExperimentSpec(
            name="stop-backoff",
            algorithm=AlgorithmSpec(name="random"),
            objective=OBJECTIVE,
            parameters=[
                ParameterSpec("lr", ParameterType.DOUBLE, FeasibleSpace(min=0.0, max=1.0))
            ],
            max_trial_count=1,
            parallel_trial_count=1,
            max_retries=3,
            retry_backoff_seconds=30.0,
            train_fn=boom,
        )
        orch = Orchestrator(workdir=str(tmp_path))

        def stop_mid_backoff():
            # half a second after the first attempt failed, however long the
            # experiment took to reach it (a fixed timer from run()'s start
            # fired before the first attempt on a loaded machine)
            if attempted.wait(timeout=20.0):
                time.sleep(0.5)
            orch.stop()

        stopper = threading.Thread(target=stop_mid_backoff, daemon=True)
        stopper.start()
        t0 = time.monotonic()
        exp = orch.run(spec)
        assert time.monotonic() - t0 < 10.0
        stopper.join(timeout=5.0)
        assert not stopper.is_alive()
        assert exp.condition is ExperimentCondition.FAILED
        trial = next(iter(exp.trials.values()))
        assert trial.retry_count >= 1  # it was mid-backoff when stopped


class TestYamlFields:
    def test_yaml_round_trip(self, tmp_path):
        from katib_tpu.sdk.yaml_spec import experiment_spec_from_dict

        spec = experiment_spec_from_dict(
            {
                "metadata": {"name": "y"},
                "spec": {
                    "objective": {
                        "type": "maximize",
                        "objectiveMetricName": "acc",
                    },
                    "algorithm": {"algorithmName": "random"},
                    "parameters": [
                        {
                            "name": "lr",
                            "parameterType": "double",
                            "feasibleSpace": {"min": "0.1", "max": "0.2"},
                        }
                    ],
                    "maxTrialRuntimeSeconds": 120,
                    "metricsRetries": 3,
                    "trialTemplate": {"command": ["true"]},
                },
            }
        )
        assert spec.max_trial_runtime_seconds == 120.0
        assert spec.metrics_retries == 3

    def test_fault_tolerance_fields_round_trip(self):
        from katib_tpu.sdk.yaml_spec import experiment_spec_from_dict

        spec = experiment_spec_from_dict(
            {
                "metadata": {"name": "f"},
                "spec": {
                    "objective": {
                        "type": "maximize",
                        "objectiveMetricName": "acc",
                    },
                    "algorithm": {"algorithmName": "random"},
                    "parameters": [
                        {
                            "name": "lr",
                            "parameterType": "double",
                            "feasibleSpace": {"min": "0.1", "max": "0.2"},
                        }
                    ],
                    "maxRetries": 2,
                    "retryBackoffSeconds": 0.5,
                    "suggesterMaxErrors": 7,
                    "trialTemplate": {"command": ["true"]},
                },
            }
        )
        assert spec.max_retries == 2
        assert spec.retry_backoff_seconds == 0.5
        assert spec.suggester_max_errors == 7

    def test_fault_tolerance_defaults(self):
        from katib_tpu.sdk.yaml_spec import experiment_spec_from_dict

        spec = experiment_spec_from_dict(
            {
                "metadata": {"name": "d"},
                "spec": {
                    "objective": {
                        "type": "maximize",
                        "objectiveMetricName": "acc",
                    },
                    "algorithm": {"algorithmName": "random"},
                    "parameters": [
                        {
                            "name": "lr",
                            "parameterType": "double",
                            "feasibleSpace": {"min": "0.1", "max": "0.2"},
                        }
                    ],
                    "trialTemplate": {"command": ["true"]},
                },
            }
        )
        assert spec.max_retries == 0  # opt-in: no silent re-runs
        assert spec.retry_backoff_seconds == 1.0
        assert spec.suggester_max_errors == 5
