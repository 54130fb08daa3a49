"""ENAS demo: REINFORCE controller searching child CNN architectures.

Runs a full ENAS experiment through the orchestrator — the JAX LSTM
controller samples an architecture per trial, child CNNs actually train on
the (synthetic-fallback) CIFAR-10 loader, and after each round the
controller takes REINFORCE steps on the mean child validation accuracy
(reference flow: ``enas/service.py:238`` sampling + ``:400`` reward
aggregation + ``Controller.py:198`` trainer).

The committed artifact records the per-round mean reward so the
controller's learning signal is inspectable, plus trials/hour and the best
sampled architecture: ``artifacts/enas/demo_summary.json`` for the default
(synthetic-fallback CIFAR-10) children, ``artifacts/enas/digits_summary.json``
when ``ENAS_DATASET=digits`` trains them on the bundled REAL UCI digits.

Run: python scripts/run_enas_demo.py   (forces the CPU mesh; ENAS search is
controller-on-CPU + child-on-mesh, same split as the reference)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import REPO, setup_jax, write_artifact  # noqa: E402


def main() -> int:
    # a CPU demo unless ENAS_PLATFORM says otherwise
    jax = setup_jax(
        force_platform=os.environ.get("ENAS_PLATFORM", "cpu"), virtual_devices=8
    )

    from katib_tpu.core.types import (
        AlgorithmSpec,
        ExperimentSpec,
        FeasibleSpace,
        GraphConfig,
        NasConfig,
        NasOperation,
        ObjectiveSpec,
        ObjectiveType,
        ParameterSpec,
        ParameterType,
    )
    from katib_tpu.nas.enas.trial import enas_trial
    from katib_tpu.orchestrator import Orchestrator

    rounds = int(os.environ.get("ENAS_ROUNDS", "3"))
    per_round = int(os.environ.get("ENAS_PER_ROUND", "4"))
    from katib_tpu.models.data import NAMED_DATASETS, dataset_from_env

    # ENAS_DATASET=digits runs the children on the bundled REAL dataset
    # (UCI handwritten digits) instead of the synthetic CIFAR-10 fallback;
    # the cross-script KATIB_DATASET flag (models/data.py DATASET_ENV) is
    # honored when ENAS_DATASET is not set, so one env var flips the
    # flagship + hyperband + ENAS artifacts to a dropped-in real dataset
    try:
        dataset = os.environ.get("ENAS_DATASET") or dataset_from_env("cifar10")
    except ValueError as e:  # bad KATIB_DATASET
        print(f"ENAS dataset: {e}", file=sys.stderr)
        return 2
    if dataset not in NAMED_DATASETS:
        # fail now, not after a multi-minute sweep recorded a dataset name
        # that was never actually loaded
        print(
            f"ENAS dataset must be one of {NAMED_DATASETS}, got {dataset!r}",
            file=sys.stderr,
        )
        return 2

    # ENAS_SHARE=1 turns on weight sharing (the ENAS paper's efficiency
    # core, absent in the reference): children inherit the experiment's
    # shared parameter pool, so a much smaller per-child epoch budget
    # reaches comparable rewards
    from katib_tpu.utils.booleans import parse_bool

    share = parse_bool(os.environ.get("ENAS_SHARE"))

    def train(ctx):
        # small child budget so the demo finishes in minutes on CPU; the
        # digits children get more epochs — the dataset is tiny (1400
        # samples) so the extra budget is cheap and makes the reward signal
        # reflect real learning instead of initialization noise
        if share:
            ctx.params.setdefault("weight_sharing", "true")
        ctx.params.setdefault("dataset", dataset)
        ctx.params.setdefault(
            "n_train",
            os.environ.get(
                "ENAS_NTRAIN", "1400" if dataset == "digits" else "1024"
            ),
        )
        ctx.params.setdefault(
            "n_test",
            os.environ.get("ENAS_NTEST", "397" if dataset == "digits" else "256"),
        )
        # shared-pool children warm-start, so a third of the epoch budget
        # suffices for comparable rewards
        if dataset == "digits":
            default_epochs = "4" if share else "12"
        else:
            default_epochs = "2"
        ctx.params.setdefault(
            "num_epochs", os.environ.get("ENAS_EPOCHS", default_epochs)
        )
        ctx.params.setdefault("channels", "16" if dataset == "digits" else "8")
        ctx.params.setdefault("batch_size", "64")
        enas_trial(ctx)

    # ENAS_NAME_SUFFIX varies the experiment name and therefore every
    # derived seed stream — the knob multi-seed A/B studies use
    suffix = os.environ.get("ENAS_NAME_SUFFIX", "")
    base_name = ("enas-digits-shared" if share else "enas-digits") \
        if dataset == "digits" else "enas-demo"
    spec = ExperimentSpec(
        name=base_name + suffix,
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
        ),
        algorithm=AlgorithmSpec(
            name="enas",
            settings={
                "controller_hidden_size": "32",
                "controller_train_steps": "10",
                # ENAS_SEED pins the controller's stream independently of
                # the experiment name, so A/B arms can be seed-PAIRED
                **({"random_state": os.environ["ENAS_SEED"]}
                   if os.environ.get("ENAS_SEED") else {}),
            },
        ),
        nas_config=NasConfig(
            graph_config=GraphConfig(num_layers=4),
            # filter_size params expand to op names the child op library
            # builds (separable_convolution_3x3, ...) — reference search
            # space shape, `enas-cnn-cifar10` op_library
            operations=(
                NasOperation(
                    "separable_convolution",
                    parameters=(
                        ParameterSpec(
                            "filter_size",
                            ParameterType.CATEGORICAL,
                            FeasibleSpace(list=("3", "5")),
                        ),
                    ),
                ),
                NasOperation(
                    "convolution",
                    parameters=(
                        ParameterSpec(
                            "filter_size",
                            ParameterType.CATEGORICAL,
                            FeasibleSpace(list=("3",)),
                        ),
                    ),
                ),
                NasOperation("max_pooling"),
                NasOperation("avg_pooling"),
            ),
        ),
        max_trial_count=rounds * per_round,
        parallel_trial_count=per_round,
        train_fn=train,
    )
    started = time.time()
    exp = Orchestrator(workdir=os.path.join(REPO, "katib_runs")).run(spec)
    wall = time.time() - started

    # per-round mean reward = the controller's REINFORCE signal
    by_round: dict[str, list[float]] = {}
    for t in exp.trials.values():
        if t.observation is None:
            continue
        rnd = t.labels.get("enas-round", "?")
        for m in t.observation.metrics:
            if m.name == "accuracy":
                by_round.setdefault(rnd, []).append(m.max)
    # numeric rounds in order; anything unlabeled sorts last rather than
    # crashing the summary after a multi-minute run
    def round_key(kv):
        try:
            return (0, int(kv[0]))
        except ValueError:
            return (1, 0)

    reward_curve = [
        {"round": r, "trials": len(v), "mean_reward": round(sum(v) / len(v), 4)}
        for r, v in sorted(by_round.items(), key=round_key)
    ]

    best_arch = None
    if exp.optimal is not None:
        assigns = {a.name: a.value for a in exp.optimal.assignments}
        best_arch = json.loads(assigns.get("architecture", "null"))

    from katib_tpu.models.data import is_real_data

    summary = {
        "experiment": exp.spec.name,
        "condition": exp.condition.value,
        "dataset": dataset,
        "real_data": is_real_data(dataset),
        "platform": jax.devices()[0].platform,
        "trials_total": len(exp.trials),
        "trials_succeeded": exp.succeeded_count,
        "wallclock_s": round(wall, 1),
        "trials_per_hour": round(len(exp.trials) / wall * 3600.0, 1),
        "best_objective": exp.optimal.objective_value if exp.optimal else None,
        "best_architecture": best_arch,
        "controller_reward_per_round": reward_curve,
    }
    summary["weight_sharing"] = share
    if not suffix:  # A/B sweep runs must not clobber the canonical artifacts
        name = "demo_summary.json"
        if dataset == "digits":
            name = "digits_shared_summary.json" if share else "digits_summary.json"
        write_artifact("enas", name, summary)
    print(json.dumps({k: summary[k] for k in (
        "condition", "trials_total", "wallclock_s", "best_objective",
    )} | {"reward_curve": reward_curve}), flush=True)
    return 0 if exp.succeeded_count == spec.max_trial_count else 1


if __name__ == "__main__":
    sys.exit(main())
