"""Where a trial's compiled program comes from (``katib_tpu/compile``'s
docstring): jax's persistent cache below, the models' tables of jitted
programs above, and nothing between.

On the CPU, each test with a cache directory of its own, placed the way a
process's first experiment places it.  What a run hit and missed is read
where PR 29 put it: the counters jax's own events add to the ``train_fn``
span (``cache_hits``, ``cache_misses``, ``jit_programs``; a cohort's are on
its ``cohort`` span) and the ``jit.backend`` spans, one a program, that say
``cache: hit|miss``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import subprocess
import sys
import urllib.request
import warnings

import jax
import pytest
import yaml
from jax.experimental.compilation_cache import compilation_cache as jax_cache

import katib_tpu.runner.trial_runner as trial_runner
from katib_tpu.cli import main
from katib_tpu.compile.prewarm import PrewarmRequest, PrewarmWorker
from katib_tpu.compile.registry import ShapeRegistry
from katib_tpu.core.types import (
    ExperimentSpec,
    ObjectiveSpec,
    ObjectiveType,
    ParameterAssignment,
    Trial,
    TrialCondition,
    TrialSpec,
)
from katib_tpu.models import mnist, transformer
from katib_tpu.orchestrator.fsck import fsck_experiment
from katib_tpu.parallel.train import cohort_trace_counter
from katib_tpu.runner.cohort import cohort_fn_of, run_cohort
from katib_tpu.sdk.yaml_spec import experiment_spec_from_dict
from katib_tpu.store.base import MemoryObservationStore
from katib_tpu.utils import observability as obs
from katib_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ACCURACY = ObjectiveSpec(type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy")
EVAL_LOSS = ObjectiveSpec(type=ObjectiveType.MINIMIZE, objective_metric_name="eval_loss")

# the tiny sizes of each program's own tests (test_cohort, test_tracing,
# test_mla_moe)
MNIST = dict(
    units=12, num_layers=1, epochs=2, batch_size=64, n_train=256, n_test=128,
    optimizer="momentum",
)
GPT2 = dict(
    d_model=16, n_heads=2, n_layers=1, seq_len=8, vocab_size=16, n_seq=32,
    batch_size=2, steps=12, lr=0.001,
)
MLA_MOE = dict(
    block="mla_moe", vocab_size=64, seq_len=32, n_seq=40, batch_size=4, steps=3,
    d_model=64, n_heads=2, n_layers=2, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    kv_lora_rank=24, dense_width=96, expert_width=32, n_experts=8, experts_per_token=2,
    experts_held_first=2, experts_held=4, routed_scaling=2.0, lr=1e-3,
)
COHORT_LRS = (0.02, 0.05, 0.08, 0.11)


def _trial(name: str, train_fn, **params) -> Trial:
    return Trial(
        name=name,
        experiment_name="compile-cache",
        spec=TrialSpec(
            assignments=[ParameterAssignment(k, v) for k, v in params.items()],
            train_fn=train_fn,
        ),
    )


def _cohort(tag: str, **struct) -> list[Trial]:
    return [
        _trial(f"{tag}{i}", mnist.mnist_trial, lr=lr, **struct)
        for i, lr in enumerate(COHORT_LRS)
    ]


# program -> (the trials of one run, their objective, the span that carries
# the run's compile counters)
PROGRAMS = {
    "mnist_trial": (
        lambda: [_trial("m", mnist.mnist_trial, lr=0.05, **MNIST)], ACCURACY, "train_fn",
    ),
    "mnist_cohort_trial-k4": (lambda: _cohort("c", **MNIST), ACCURACY, "cohort"),
    "transformer_trial-gpt2": (
        lambda: [_trial("g", transformer.transformer_trial, **GPT2)], EVAL_LOSS, "train_fn",
    ),
    "transformer_trial-mla_moe": (
        lambda: [_trial("k", transformer.transformer_trial, **MLA_MOE)], EVAL_LOSS, "train_fn",
    ),
}


def _drop_tables() -> None:
    """What a new process would not have: every jitted program jax holds, and
    the models' tables above them."""
    jax.clear_caches()
    mnist._STEP_CACHE.clear()
    transformer._PROGRAMS.clear()


@contextlib.contextmanager
def _placed(cache_dir):
    """``cache_dir`` as the process's persistent cache, placed by
    ``init_compile_cache`` as a first experiment places it; the worker's own
    (tests/conftest.py) is back in force afterwards."""
    before = jax.config.jax_compilation_cache_dir
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("KATIB_COMPILE_CACHE", str(cache_dir))
        patch.setattr(trial_runner, "_COMPILE_CACHE_DIR", None)
        jax_cache.reset_cache()
        assert trial_runner.init_compile_cache() == str(cache_dir)
        try:
            yield cache_dir
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            jax_cache.reset_cache()


@dataclasses.dataclass
class Run:
    counters: dict  # of the run's train_fn / cohort span
    series: dict  # trial -> [(metric, value)] in the order reported
    records: list  # the whole journal


def _run(program, journal) -> Run:
    """One run of ``program`` (a name in PROGRAMS, or such a triple) in what
    is, to jax and the models, a new process: through run_trial /
    run_cohort, under a tracer."""
    make, objective, span_name = PROGRAMS.get(program, program)
    trials, store = make(), MemoryObservationStore()
    _drop_tables()
    tracer = tracing.Tracer(str(journal))
    with tracing.use_tracer(tracer):
        if span_name == "cohort":
            results = run_cohort(trials, store, objective)
        else:
            results = {t.name: trial_runner.run_trial(t, store, objective) for t in trials}
    tracer.close()
    for name, result in results.items():
        assert result.condition is TrialCondition.SUCCEEDED, (name, result.message)
    records = tracing.read_journal(str(journal))
    (span,) = [r for r in records if r["name"] == span_name]
    series = {t.name: [(m.metric_name, m.value) for m in store.get(t.name)] for t in trials}
    assert all(len(points) >= 2 for points in series.values())
    return Run(span["args"], series, records)


def _entries(cache_dir) -> list[str]:
    """jax's entries in the directory (the shape registry lives there too)."""
    return [
        os.path.join(cache_dir, name)
        for name in os.listdir(cache_dir)
        if name.endswith("-cache")
    ]


def _backend_spans(records: list[dict], programs: set[str]) -> dict[str, set]:
    """program -> the ``cache`` values of its ``jit.backend`` spans."""
    found: dict[str, set] = {}
    for r in records:
        args = r.get("args", {})
        if r["name"] == "jit.backend" and args.get("program") in programs:
            found.setdefault(args["program"], set()).add(args.get("cache"))
    return found


# ---------------------------------------------------------------------------
# a second process starts warm, and a damaged cache never fails a trial
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cold_then_warm(tmp_path_factory):
    """program -> (its cache directory, the cold run, the run after every
    table was dropped), made once a program."""
    made: dict[str, tuple] = {}

    def get(program: str):
        if program not in made:
            root = tmp_path_factory.mktemp(program)
            with _placed(root / "cache") as cache_dir:
                cold = _run(program, root / "cold.jsonl")
                warm = _run(program, root / "warm.jsonl")
            made[program] = (cache_dir, cold, warm)
        return made[program]

    return get


@pytest.mark.parametrize("program", list(PROGRAMS))
class TestWarmStart:
    def test_second_run_compiles_nothing(self, cold_then_warm, program):
        _, cold, warm = cold_then_warm(program)
        assert cold.counters["cache_misses"] >= 1  # the cache was the test's own
        assert warm.counters.get("cache_misses", 0) == 0
        assert warm.counters["cache_hits"] >= 1
        # every program the run built came out of the cache
        assert warm.counters["cache_hits"] == warm.counters["jit_programs"]

    def test_loaded_programs_give_the_same_losses(self, cold_then_warm, program):
        _, cold, warm = cold_then_warm(program)
        assert warm.series == cold.series  # floats compared bit for bit


def _truncate(path: str) -> None:
    with open(path, "rb+") as f:
        f.truncate(os.path.getsize(path) // 2)


@pytest.mark.parametrize("damage", [_truncate, os.remove], ids=["truncated", "deleted"])
@pytest.mark.parametrize("program", ["mnist_trial", "transformer_trial-gpt2"])
def test_damaged_cache_never_fails_a_trial(cold_then_warm, tmp_path, program, damage):
    """Entries cut to half, or gone (what the chip machine's cache cap does
    between runs): the program compiles, and the losses are the same."""
    cache_dir, cold, _ = cold_then_warm(program)
    entries = _entries(cache_dir)
    assert entries
    for path in entries:
        damage(path)
    with _placed(cache_dir), warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        again = _run(program, tmp_path / "damaged.jsonl")
    assert again.counters["cache_misses"] >= 1
    assert again.series == cold.series
    if damage is _truncate:
        # jax says so, once an entry, and goes on
        assert any("Error reading persistent compilation cache" in str(w.message) for w in caught)


# ---------------------------------------------------------------------------
# prewarm fills the cache the trial reads
# ---------------------------------------------------------------------------

# the model's own programs (the twin builds these; the training loop's eager
# one-operation programs, a gather or a sum, it does not)
MODEL_PROGRAMS = {
    1: {"jit(_epoch)", "jit(metric_fn)"},
    4: {"jit(step)", "jit(metric_fn)"},
}


@pytest.fixture
def own_cache(tmp_path, monkeypatch):
    # journal a jit.backend span for every program, however short
    monkeypatch.setattr(tracing, "JIT_SPAN_MIN_S", 0.0)
    with _placed(tmp_path / "cache") as cache_dir:
        yield cache_dir


@pytest.mark.parametrize("k", [4, 1], ids=["cohort-k4", "singleton"])
def test_prewarm_fills_the_cache_the_trial_reads(own_cache, tmp_path, k):
    struct = dict(MNIST, units=13)  # a structure no other test has built
    worker = PrewarmWorker(registry=ShapeRegistry())
    _drop_tables()
    assert worker.submit(
        PrewarmRequest(
            train_fn=mnist.mnist_trial,
            shared=struct,
            k=k,
            program_fn=cohort_fn_of(mnist.mnist_trial) if k > 1 else None,
        )
    )
    assert worker.drain(timeout=120)
    worker.stop()
    assert (worker.compiled, worker.failed) == (1, 0)
    assert _entries(own_cache)

    if k > 1:
        program = (lambda: _cohort("p", **struct), ACCURACY, "cohort")
    else:
        program = (
            lambda: [_trial("p", mnist.mnist_trial, lr=0.05, **struct)], ACCURACY, "train_fn",
        )
    real = _run(program, tmp_path / "real.jsonl")
    # the step the real run takes first, and its eval, were loaded
    assert _backend_spans(real.records, MODEL_PROGRAMS[k]) == {
        name: {"hit"} for name in MODEL_PROGRAMS[k]
    }


def test_prewarm_verb_then_run_verb(own_cache, tmp_path):
    """``katib-tpu prewarm <yaml>`` then ``katib-tpu run`` on the same
    document: the run's first trial loads the model's programs."""

    def pinned(name, value):
        return {
            "name": name,
            "parameterType": "int",
            "feasibleSpace": {"min": str(value), "max": str(value)},
        }

    doc = {
        "apiVersion": "kubeflow.org/v1beta1",
        "kind": "Experiment",
        "metadata": {"name": "prewarm-then-run"},
        "spec": {
            "objective": {"type": "maximize", "objectiveMetricName": "accuracy"},
            "algorithm": {"algorithmName": "random"},
            "parallelTrialCount": 1,
            "maxTrialCount": 2,
            "parameters": [
                {
                    "name": "lr",
                    "parameterType": "double",
                    "feasibleSpace": {"min": "0.01", "max": "0.1"},
                },
                *(
                    pinned(name, value)
                    for name, value in dict(MNIST, units=14).items()
                    if name != "optimizer"
                ),
            ],
            "trialTemplate": {"trainFn": "katib_tpu.models.mnist.mnist_trial"},
        },
    }
    path = tmp_path / "experiment.yaml"
    path.write_text(yaml.safe_dump(doc))
    _drop_tables()
    assert main(["prewarm", str(path)]) == 0
    assert _entries(own_cache)
    _drop_tables()
    workdir = tmp_path / "runs"
    assert main(["run", str(path), "--workdir", str(workdir), "--no-preflight"]) == 0
    records = tracing.read_journal(tracing.trace_path(str(workdir), "prewarm-then-run"))
    first, second = sorted(
        (r for r in records if r["name"] == "train_fn"), key=lambda r: r["ts"]
    )
    mine = [r for r in records if r.get("args", {}).get("trial") == first["args"]["trial"]]
    assert _backend_spans(mine, MODEL_PROGRAMS[1]) == {
        name: {"hit"} for name in MODEL_PROGRAMS[1]
    }
    assert second["args"]["jit_programs"] == 0  # and the table served the next


# ---------------------------------------------------------------------------
# the step a model calls is the table's entry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 4], ids=["train_classifier", "cohort-k4"])
def test_the_step_a_model_calls_is_the_tables_entry(monkeypatch, tmp_path, k):
    """Two trials, or two cohorts, of one structure trace once and call the
    same function object: the one ``_STEP_CACHE`` holds, with nothing
    wrapped around it."""
    struct = dict(MNIST, units=15, epochs=1)
    called = []
    observe = mnist.costmodel.observe_program

    def spy(label, fn, args, **kw):
        called.append(fn)
        return observe(label, fn, args, **kw)

    monkeypatch.setattr(mnist.costmodel, "observe_program", spy)
    _drop_tables()
    traced = cohort_trace_counter.count
    store = MemoryObservationStore()
    tracer = tracing.Tracer(str(tmp_path / "trace.jsonl"))
    with tracing.use_tracer(tracer):
        for nth in range(2):
            if k > 1:
                results = run_cohort(_cohort(f"c{nth}-", **struct), store, ACCURACY)
            else:
                trial = _trial(f"t{nth}", mnist.mnist_trial, lr=0.03 * (nth + 1), **struct)
                results = {trial.name: trial_runner.run_trial(trial, store, ACCURACY)}
            assert all(r.condition is TrialCondition.SUCCEEDED for r in results.values())
    tracer.close()

    assert len(called) == 2 and called[0] is called[1]
    (entry,) = mnist._STEP_CACHE.values()
    assert any(called[0] is fn for fn in entry)
    if k > 1:
        assert cohort_trace_counter.count - traced == 1
    else:
        records = tracing.read_journal(str(tmp_path / "trace.jsonl"))
        first, second = [r["args"] for r in records if r["name"] == "train_fn"]
        assert first["jit_programs"] >= 1 and second["jit_programs"] == 0


# ---------------------------------------------------------------------------
# the serialized-executable tier's surface is gone, and says so the ordinary way
# ---------------------------------------------------------------------------

EXAMPLE = os.path.join(REPO, "examples", "hp-tuning", "cohort-prewarm.yaml")


@pytest.mark.parametrize(
    "argv",
    [
        ["cache"],
        ["prewarm", EXAMPLE, "--publish"],
        ["prewarm", EXAMPLE, "--fetch-only"],
        ["prewarm", EXAMPLE, "--artifact-dir", "somewhere"],
    ],
    ids=["cache-verb", "publish", "fetch-only", "artifact-dir"],
)
def test_cli_refuses_what_it_no_longer_has(argv, capsys):
    with pytest.raises(SystemExit) as refused:
        main(argv)
    assert refused.value.code == 2  # argparse's own
    err = capsys.readouterr().err
    assert "invalid choice" in err or "unrecognized arguments" in err


def test_the_module_is_gone_and_nothing_stands_in_for_it():
    import katib_tpu.compile as compile_pkg

    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("katib_tpu.compile.artifacts")
    assert not {"ARTIFACTS", "ArtifactCache", "resolve", "env_fingerprint"} & set(dir(compile_pkg))
    assert not hasattr(PrewarmWorker(registry=ShapeRegistry()), "published")


@pytest.mark.parametrize("key", ["artifactDir", "noSuchKeyEither"])
def test_spec_ignores_the_key_as_it_ignores_any_unknown_key(key):
    with open(EXAMPLE) as f:
        doc = yaml.safe_load(f)
    plain = experiment_spec_from_dict(doc)
    doc["spec"][key] = "katib_runs/artifacts"
    spec = experiment_spec_from_dict(doc)
    assert "artifact_dir" not in {f.name for f in dataclasses.fields(ExperimentSpec)}
    assert not hasattr(spec, "artifact_dir")
    assert spec == plain


def test_metrics_have_no_artifact_series(tmp_path):
    from katib_tpu.orchestrator.orchestrator import Orchestrator
    from tests.helpers import make_spec

    exp = Orchestrator(workdir=str(tmp_path)).run(
        make_spec(
            max_trial_count=2,
            train_fn=lambda ctx: ctx.report(loss=float(ctx.params["x"]) ** 2),
        )
    )
    assert exp.succeeded_count == 2
    server = obs.REGISTRY.serve(port=0)
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{server.port}/metrics") as reply:
            body = reply.read().decode()
    finally:
        server.stop()
    assert "katib_trial_succeeded_total" in body
    assert "katib_artifact_" not in body


def test_fsck_of_a_former_tier_answers_as_for_any_directory(tmp_path, capsys):
    tier = tmp_path / "artifacts"
    tier.mkdir()
    (tier / ("0" * 64 + ".katibx")).write_bytes(b"KATIBART1\n{}")
    want = fsck_experiment(str(tier), repair=False)
    rc = main(["fsck", str(tier), "--dry-run"])
    assert rc == (0 if want.ok() else 1)
    assert capsys.readouterr().out.splitlines() == want.lines()
    assert os.listdir(tier) == ["0" * 64 + ".katibx"]  # and nothing was moved aside


def test_chip_smoke_rehearsal_reports_no_artifact_field(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", KATIB_COMPILE_CACHE=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--allow-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]  # its own checks passed
    fields: set[str] = set()
    phases = []
    for line in proc.stdout.splitlines():
        phase, _, body = line.partition(" ")
        if phase.startswith("[") and body.startswith("{"):
            phases.append(phase)
            fields |= set(json.loads(body))
    assert {"[start]", "[sweep]", "[done]"} <= set(phases)
    assert {"jax", "jaxlib", "libtpu", "registry_warm_first_steps"} <= fields
    assert not [name for name in fields if "artifact" in name]
