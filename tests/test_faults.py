"""Fault-tolerant trial lifecycle: failure classification, retry with
backoff, suggester circuit breaking, and the deterministic FaultInjector
(seeded chaos scenarios run by CI's fault-injection smoke step)."""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import pytest

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
from katib_tpu.utils.faults import (
    Backoff,
    CircuitBreaker,
    FailureKind,
    FaultInjector,
    InjectedFault,
    classify_exception,
    classify_exit_code,
    classify_traceback,
)

OBJECTIVE = ObjectiveSpec(type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy")


def make_spec(name, train_fn, **kw) -> ExperimentSpec:
    kw.setdefault("max_trial_count", 1)
    kw.setdefault("parallel_trial_count", 1)
    kw.setdefault("retry_backoff_seconds", 0.01)
    return ExperimentSpec(
        name=name,
        algorithm=AlgorithmSpec(name="random", settings={"seed": "0"}),
        objective=OBJECTIVE,
        parameters=[
            ParameterSpec("lr", ParameterType.DOUBLE, FeasibleSpace(min=0.0, max=1.0))
        ],
        train_fn=train_fn,
        **kw,
    )


class _StubTrial:
    def __init__(self, name, checkpoint_dir=None):
        self.name = name
        self.checkpoint_dir = checkpoint_dir


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


class TestClassifyException:
    def test_oserror_family_is_transient(self):
        for exc in (OSError("disk"), ConnectionResetError(), TimeoutError(),
                    MemoryError(), InterruptedError(), FileNotFoundError("x")):
            assert classify_exception(exc) is FailureKind.TRANSIENT

    def test_deterministic_bugs_are_permanent(self):
        for exc in (ValueError("bad shape"), TypeError(), AssertionError(),
                    KeyError("k"), ZeroDivisionError()):
            assert classify_exception(exc) is FailureKind.PERMANENT

    def test_xla_style_text_markers(self):
        # XlaRuntimeError is a RuntimeError whose message carries the status
        assert classify_exception(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating ...")
        ) is FailureKind.TRANSIENT
        assert classify_exception(
            RuntimeError("UNAVAILABLE: slice preempted")
        ) is FailureKind.TRANSIENT

    def test_unknown_defaults_permanent(self):
        assert classify_exception(RuntimeError("some bug")) is FailureKind.PERMANENT

    def test_value_error_mentioning_marker_stays_permanent(self):
        # type check runs before text markers: a ValueError is a bug even if
        # its message happens to say "unavailable"
        assert classify_exception(
            ValueError("metric unavailable in dict")
        ) is FailureKind.PERMANENT

    def test_injected_fault_carries_its_kind(self):
        assert classify_exception(InjectedFault("x")) is FailureKind.TRANSIENT
        assert classify_exception(
            InjectedFault("x", FailureKind.PERMANENT)
        ) is FailureKind.PERMANENT


class TestClassifyTraceback:
    def test_oserror_raise_line(self):
        tb = 'Traceback ...\n  File "t.py", line 3\nOSError: [Errno 5] I/O error'
        assert classify_traceback(tb) is FailureKind.TRANSIENT

    def test_value_error_is_permanent(self):
        tb = "Traceback ...\nValueError: shapes (3,) and (4,) not aligned"
        assert classify_traceback(tb) is FailureKind.PERMANENT

    def test_preemption_text(self):
        assert classify_traceback(
            "RuntimeError: TPU worker preempted"
        ) is FailureKind.TRANSIENT


class TestClassifyExitCode:
    def test_signal_killed_is_transient(self):
        assert classify_exit_code(-9) is FailureKind.TRANSIENT
        assert classify_exit_code(-15) is FailureKind.TRANSIENT

    def test_retryable_shell_codes(self):
        for rc in (75, 134, 137, 143):
            assert classify_exit_code(rc) is FailureKind.TRANSIENT

    def test_plain_nonzero_is_permanent(self):
        for rc in (1, 2, 42):
            assert classify_exit_code(rc) is FailureKind.PERMANENT


class TestBlackboxExitClassification:
    def test_tempfail_exit_code_marks_transient(self):
        trial = Trial(name="t", spec=TrialSpec(
            assignments=[],
            command=[sys.executable, "-c", "import sys; sys.exit(75)"],
            metrics_collector=MetricsCollectorSpec(kind=MetricsCollectorKind.STDOUT),
        ))
        result = run_trial(trial, MemoryObservationStore(), OBJECTIVE)
        assert result.condition is TrialCondition.FAILED
        assert result.failure_kind is FailureKind.TRANSIENT

    def test_ordinary_failure_exit_marks_permanent(self):
        trial = Trial(name="t", spec=TrialSpec(
            assignments=[],
            command=[sys.executable, "-c", "import sys; sys.exit(2)"],
            metrics_collector=MetricsCollectorSpec(kind=MetricsCollectorKind.STDOUT),
        ))
        result = run_trial(trial, MemoryObservationStore(), OBJECTIVE)
        assert result.condition is TrialCondition.FAILED
        assert result.failure_kind is FailureKind.PERMANENT


# ---------------------------------------------------------------------------
# backoff
# ---------------------------------------------------------------------------


class TestBackoff:
    def test_exponential_growth_and_cap(self):
        b = Backoff(base=1.0, factor=2.0, cap=30.0, jitter=0.0)
        assert b.delay(1) == 1.0
        assert b.delay(2) == 2.0
        assert b.delay(3) == 4.0
        assert b.delay(6) == 30.0  # 32 clamped

    def test_jitter_bounded(self):
        b = Backoff(base=1.0, jitter=0.25, seed=7)
        for _ in range(50):
            assert 0.75 <= b.delay(1) <= 1.25

    def test_same_seed_same_schedule(self):
        a = Backoff(seed="exp:trial")
        b = Backoff(seed="exp:trial")
        assert [a.delay(i) for i in range(1, 6)] == [b.delay(i) for i in range(1, 6)]

    def test_wait_interrupted_by_stop_event(self):
        ev = threading.Event()
        ev.set()
        b = Backoff(base=30.0, jitter=0.0)
        t0 = time.monotonic()
        assert b.wait(1, ev) is False
        assert time.monotonic() - t0 < 1.0

    def test_wait_completes_without_event(self):
        assert Backoff(base=0.0, jitter=0.0).wait(1) is True


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def test_state_machine(self):
        clock = {"t": 0.0}
        br = CircuitBreaker(threshold=3, base_cooldown=1.0, clock=lambda: clock["t"])
        assert br.state == "closed" and br.allow()
        assert br.record_failure("e1") is False
        assert br.state == "cooling" and not br.allow()
        clock["t"] += 1.0
        assert br.state == "half-open" and br.allow()
        br.record_failure("e2")  # cooldown doubles to 2.0
        clock["t"] += 1.0
        assert not br.allow()
        clock["t"] += 1.0
        assert br.allow()
        br.record_success()
        assert br.state == "closed" and br.failures == 0 and br.last_failure == ""

    def test_trips_open_at_threshold(self):
        clock = {"t": 0.0}
        br = CircuitBreaker(threshold=3, base_cooldown=0.0, clock=lambda: clock["t"])
        for i in range(2):
            assert br.record_failure(f"e{i}") is False
        assert br.record_failure("last") is True
        assert br.tripped and br.state == "open" and not br.allow()
        assert br.last_failure == "last"


# ---------------------------------------------------------------------------
# fault injector seams
# ---------------------------------------------------------------------------


class TestFaultInjector:
    def test_fail_trial_by_creation_index(self):
        inj = FaultInjector().fail_trial(0, 2)
        t = _StubTrial("a")
        inj.on_trial_attempt(t)  # attempt 1 passes
        with pytest.raises(InjectedFault) as ei:
            inj.on_trial_attempt(t)  # attempt 2 fires
        assert ei.value.kind is FailureKind.TRANSIENT
        assert classify_exception(ei.value) is FailureKind.TRANSIENT
        assert inj.attempts_of("a") == 2
        assert inj.log == [
            {"seam": "trial", "trial": "a", "attempt": 2, "kind": "Transient"}
        ]

    def test_fail_trial_by_name_permanent(self):
        inj = FaultInjector().fail_trial("b", 1, FailureKind.PERMANENT)
        inj.on_trial_attempt(_StubTrial("other"))  # different trial untouched
        with pytest.raises(InjectedFault) as ei:
            inj.on_trial_attempt(_StubTrial("b"))
        assert ei.value.kind is FailureKind.PERMANENT

    def test_fail_suggester_nth_call(self):
        inj = FaultInjector().fail_suggester(2)
        inj.on_suggester_call()  # call 1 passes
        with pytest.raises(InjectedFault):
            inj.on_suggester_call()
        inj.on_suggester_call()  # call 3 passes again

    def test_flake_with_rate_one_always_fires(self):
        inj = FaultInjector(seed=1).flake(1.0)
        with pytest.raises(InjectedFault):
            inj.on_trial_attempt(_StubTrial("x"))

    def test_corrupt_checkpoint_step(self, tmp_path):
        step_dir = tmp_path / "ckpt" / "5"
        step_dir.mkdir(parents=True)
        (step_dir / "weights").write_bytes(b"precious")
        inj = FaultInjector().corrupt_checkpoint(0, 5)
        inj.on_trial_attempt(_StubTrial("t", str(tmp_path / "ckpt")))
        assert (step_dir / "weights").read_bytes().startswith(b"\x00CORRUPTED")
        assert {"seam": "checkpoint", "trial": "t", "step": 5} in inj.log

    def test_metrics_delay_respects_stop_event(self):
        inj = FaultInjector().delay_metrics(0, 30.0)
        t = _StubTrial("t")
        inj.on_trial_attempt(t)
        ev = threading.Event()
        ev.set()
        t0 = time.monotonic()
        inj.apply_metrics_delay(t, ev)
        assert time.monotonic() - t0 < 1.0
        assert inj.log[-1] == {"seam": "metrics", "trial": "t", "delay": 30.0}


# ---------------------------------------------------------------------------
# orchestrator-level chaos scenarios (CI fault-injection smoke: -m chaos)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestTransientRetry:
    def test_transient_twice_then_succeed_one_budget_slot(self, tmp_path):
        """The acceptance scenario: a trial failing transiently twice then
        succeeding consumes exactly one budget slot, retries under the same
        checkpoint dir, and resumes its own progress on attempt 3."""
        progress_seen = []

        def trainer(ctx):
            os.makedirs(ctx.checkpoint_dir, exist_ok=True)
            marker = os.path.join(ctx.checkpoint_dir, "progress.txt")
            prev = 0
            if os.path.exists(marker):
                with open(marker) as f:
                    prev = int(f.read())
            progress_seen.append(prev)
            with open(marker, "w") as f:
                f.write(str(prev + 1))
            if len(progress_seen) <= 2:
                raise OSError("preempted")  # transient by classification
            ctx.report(step=0, accuracy=0.9)

        spec = make_spec("chaos-retry", trainer, max_retries=3)
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        # one budget slot despite three executions
        assert len(exp.trials) == 1
        assert exp.succeeded_count == 1
        trial = next(iter(exp.trials.values()))
        assert trial.condition is TrialCondition.SUCCEEDED
        assert trial.retry_count == 2
        # attempt 3 read the progress attempt 2 wrote: same checkpoint dir
        assert progress_seen == [0, 1, 2]

    def test_injector_driven_transient_recovery(self, tmp_path):
        ran = []

        def trainer(ctx):
            ran.append(1)
            ctx.report(step=0, accuracy=0.5)

        inj = FaultInjector(seed=0).fail_trial(0, 1)
        spec = make_spec("chaos-inj", trainer, max_retries=2)
        exp = Orchestrator(workdir=str(tmp_path), fault_injector=inj).run(spec)
        trial = next(iter(exp.trials.values()))
        assert trial.condition is TrialCondition.SUCCEEDED
        assert trial.retry_count == 1
        # attempt 1 raised inside the seam before the body ran
        assert len(ran) == 1
        assert inj.attempts_of(trial.name) == 2
        assert [e["seam"] for e in inj.log] == ["trial"]

    def test_budget_exhausts_and_kind_journaled(self, tmp_path):
        def trainer(ctx):
            raise OSError("preempted")

        spec = make_spec("chaos-exhaust", trainer, max_retries=2)
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        trial = next(iter(exp.trials.values()))
        assert trial.condition is TrialCondition.FAILED
        assert trial.retry_count == 2
        assert trial.failure_kind == FailureKind.TRANSIENT.value
        assert exp.failed_count == 1


@pytest.mark.chaos
class TestPermanentNoRetry:
    def test_permanent_failure_never_retried(self, tmp_path):
        calls = []

        def trainer(ctx):
            calls.append(1)
            raise ValueError("bad hyperparameter")

        spec = make_spec("chaos-perm", trainer, max_retries=5)
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        trial = next(iter(exp.trials.values()))
        assert trial.condition is TrialCondition.FAILED
        assert trial.retry_count == 0
        assert trial.failure_kind == FailureKind.PERMANENT.value
        assert len(calls) == 1

    def test_injected_permanent_not_retried(self, tmp_path):
        inj = FaultInjector().fail_trial(0, 1, FailureKind.PERMANENT)
        spec = make_spec("chaos-perm-inj", lambda ctx: ctx.report(step=0, accuracy=1), max_retries=5)
        exp = Orchestrator(workdir=str(tmp_path), fault_injector=inj).run(spec)
        trial = next(iter(exp.trials.values()))
        assert trial.condition is TrialCondition.FAILED
        assert trial.retry_count == 0
        assert inj.attempts_of(trial.name) == 1


@pytest.mark.chaos
class TestRetryStateSurvivesRestart:
    def test_journaled_retry_count_not_reset_on_resume(self, tmp_path):
        """Process 1 'crashed' mid-trial with 2 of 3 retries spent (forged
        journal).  The resumed process grants exactly 1 more retry — the
        budget survives the restart instead of resetting to 3."""
        from katib_tpu.orchestrator.status import write_status

        attempts = []

        def trainer(ctx):
            attempts.append(1)
            raise OSError("preempted")

        spec = make_spec("chaos-resume", trainer, max_retries=3)
        # forge process 1's journal: experiment Running, trial mid-flight
        # with retry_count already at 2
        from katib_tpu.core.types import Experiment

        exp1 = Experiment(spec=spec, condition=ExperimentCondition.RUNNING)
        exp1.start_time = time.time()
        exp1.trials["chaos-resume-aaaa0000"] = Trial(
            name="chaos-resume-aaaa0000",
            experiment_name=spec.name,
            spec=TrialSpec(assignments=[], train_fn=trainer, max_retries=3,
                           retry_backoff_seconds=0.01),
            condition=TrialCondition.RUNNING,
            start_time=time.time(),
            checkpoint_dir=str(tmp_path / spec.name / "chaos-resume-aaaa0000"),
            retry_count=2,
            failure_kind=FailureKind.TRANSIENT.value,
        )
        write_status(exp1, str(tmp_path))

        exp = Orchestrator(workdir=str(tmp_path)).run(spec, resume=True)
        trial = exp.trials["chaos-resume-aaaa0000"]
        assert trial.condition is TrialCondition.FAILED
        assert trial.retry_count == 3
        # process 2 ran the resubmitted attempt + exactly 1 remaining retry
        assert len(attempts) == 2

    def test_retry_count_round_trips_through_journal(self, tmp_path):
        from katib_tpu.orchestrator.resume import trial_from_dict
        from katib_tpu.orchestrator.status import trial_to_dict

        spec = make_spec("rt", None)
        trial = Trial(
            name="t1", experiment_name="rt",
            spec=TrialSpec(assignments=[]),
            condition=TrialCondition.FAILED,
            retry_count=2, failure_kind="Transient",
        )
        back = trial_from_dict(spec, trial_to_dict(trial))
        assert back.retry_count == 2
        assert back.failure_kind == "Transient"


@pytest.mark.chaos
class TestSuggesterCircuitBreaker:
    def test_sub_threshold_errors_absorbed(self, tmp_path):
        """suggester_max_errors - 1 consecutive exceptions are counted and
        cooled down; the experiment still completes."""
        inj = FaultInjector().fail_suggester(1).fail_suggester(2)
        spec = make_spec(
            "chaos-breaker-ok",
            lambda ctx: ctx.report(step=0, accuracy=0.5),
            max_trial_count=2,
            suggester_max_errors=3,
        )
        exp = Orchestrator(workdir=str(tmp_path), fault_injector=inj).run(spec)
        assert exp.condition is ExperimentCondition.MAX_TRIALS_REACHED
        assert exp.succeeded_count == 2
        assert sum(1 for e in inj.log if e["seam"] == "suggester") == 2

    def test_threshold_errors_fail_experiment_with_traceback(self, tmp_path):
        inj = (
            FaultInjector()
            .fail_suggester(1)
            .fail_suggester(2)
            .fail_suggester(3)
        )
        spec = make_spec(
            "chaos-breaker-trip",
            lambda ctx: ctx.report(step=0, accuracy=0.5),
            max_trial_count=2,
            suggester_max_errors=3,
        )
        exp = Orchestrator(workdir=str(tmp_path), fault_injector=inj).run(spec)
        assert exp.condition is ExperimentCondition.FAILED
        assert "suggester failed 3 consecutive times" in exp.message
        assert "injected suggester fault" in exp.message  # last traceback

    def test_success_resets_consecutive_count(self, tmp_path):
        """Failures interleaved with successes never trip the breaker:
        calls 1 and 3 fail, call 2 succeeds — threshold 2 is never reached
        consecutively."""
        inj = FaultInjector().fail_suggester(1).fail_suggester(3)
        spec = make_spec(
            "chaos-breaker-reset",
            lambda ctx: ctx.report(step=0, accuracy=0.5),
            max_trial_count=2,
            suggester_max_errors=2,
        )
        exp = Orchestrator(workdir=str(tmp_path), fault_injector=inj).run(spec)
        assert exp.condition is ExperimentCondition.MAX_TRIALS_REACHED
        assert exp.succeeded_count == 2


class TestProcessGroupCleanup:
    def test_grandchild_killed_with_process_group(self, tmp_path):
        """A black-box trial that spawns its own subprocess must not leak it
        when the deadline kills the trial: the runner signals the whole
        process group (start_new_session=True)."""
        if os.name != "posix":
            pytest.skip("process groups are POSIX-only")
        pidfile = tmp_path / "grandchild.pid"
        script = (
            "import os, subprocess, sys, time\n"
            "g = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(60)'])\n"
            f"open({str(pidfile)!r}, 'w').write(str(g.pid))\n"
            "time.sleep(60)\n"
        )
        trial = Trial(name="pg", spec=TrialSpec(
            assignments=[],
            command=[sys.executable, "-c", script],
            max_runtime_seconds=1.0,
            metrics_collector=MetricsCollectorSpec(kind=MetricsCollectorKind.STDOUT),
        ))
        result = run_trial(trial, MemoryObservationStore(), OBJECTIVE)
        assert result.condition is TrialCondition.FAILED
        assert pidfile.exists(), "trial never started its grandchild"
        pid = int(pidfile.read_text())
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if not _alive(pid):
                break
            time.sleep(0.1)
        assert not _alive(pid), f"grandchild {pid} leaked past the trial kill"


def _alive(pid: int) -> bool:
    """Is pid a live (non-zombie) process?  A reparented-but-unreaped
    grandchild shows as Z in /proc — that counts as dead."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().split(")")[-1].split()[0] != "Z"
    except OSError:
        return False


class TestValidation:
    def test_negative_retry_knobs_rejected(self):
        from katib_tpu.core.validation import validate_experiment

        spec = make_spec("bad", lambda ctx: None, max_retries=-1)
        with pytest.raises(Exception):
            validate_experiment(spec)

    def test_zero_suggester_max_errors_rejected(self):
        from katib_tpu.core.validation import validate_experiment

        spec = make_spec("bad2", lambda ctx: None, suggester_max_errors=0)
        with pytest.raises(Exception):
            validate_experiment(spec)
