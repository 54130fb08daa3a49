"""Accuracy-vs-wallclock DARTS search curve at the north-star INPUT scale.

Real CIFAR-10 cannot be downloaded in this zero-egress image
(``fetch_cifar10.py`` is the one-command upgrade path when an archive
lands), so convergence evidence at the reference's 32x32x3 input shape
comes from the structured synthetic CIFAR stand-in (``models/data.py``
``synthetic_classification``: smoothed class prototypes + Gaussian noise).
The artifact documents the stand-in's measured ceiling — the accuracy of
the Bayes-like nearest-class-mean classifier — so the curve cannot be
over-read as real-data capability.

Writes ``artifacts/flagship/synthetic_cifar_curve.json``.

Env knobs (defaults size the run for a ~30-45 min single-core budget;
on a TPU grant the same script runs the full flagship shape):
  CURVE_EPOCHS       search epochs (default 4)
  CURVE_LAYERS       supernet layers (default 4)
  CURVE_CHANNELS     init channels (default 8)
  CURVE_BATCH        batch size (default 32)
  CURVE_TRAIN        train samples (default 4096)
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import REPO, setup_jax, write_artifact  # noqa: E402

# CPU by default; CURVE_TPU=1 opts into the chip.
from katib_tpu.utils.booleans import parse_bool  # noqa: E402

on_tpu = parse_bool(os.environ.get("CURVE_TPU"))
jax = setup_jax(force_platform=None if on_tpu else "cpu", compile_cache=True)


def nearest_class_mean_ceiling(ds) -> float:
    """Accuracy of classifying test points by nearest class mean of the
    train set — for the prototype+noise generator this approximates the
    Bayes classifier, i.e. the stand-in's accuracy ceiling."""
    means = np.stack([
        ds.x_train[ds.y_train == c].mean(axis=0) for c in range(ds.num_classes)
    ]).reshape(ds.num_classes, -1)
    xt = ds.x_test.reshape(len(ds.x_test), -1)
    d2 = ((xt[:, None, :] - means[None, :, :]) ** 2).sum(-1)
    return float((d2.argmin(1) == ds.y_test).mean())


def main() -> None:
    from katib_tpu.models.data import load_cifar10, using_real_data
    from katib_tpu.nas.darts import DartsHyper, run_darts_search
    from katib_tpu.nas.darts.ops import DEFAULT_PRIMITIVES

    epochs = int(os.environ.get("CURVE_EPOCHS", "4"))
    layers = int(os.environ.get("CURVE_LAYERS", "4"))
    channels = int(os.environ.get("CURVE_CHANNELS", "8"))
    batch = int(os.environ.get("CURVE_BATCH", "32"))
    n_train = int(os.environ.get("CURVE_TRAIN", "4096"))

    ds = load_cifar10(n_train=n_train, n_test=1024)
    real = using_real_data("cifar10")
    assert ds.x_train.shape[1:] == (32, 32, 3), ds.x_train.shape
    ceiling = nearest_class_mean_ceiling(ds)
    print(f"dataset: {'REAL cifar10 npz' if real else 'synthetic stand-in'}, "
          f"{len(ds.x_train)} train; nearest-class-mean ceiling {ceiling:.4f}",
          flush=True)

    t0 = time.perf_counter()
    result = run_darts_search(
        ds,
        num_epochs=epochs,
        primitives=DEFAULT_PRIMITIVES,
        num_layers=layers,
        init_channels=channels,
        n_nodes=4,
        batch_size=batch,
        hyper=DartsHyper(unrolled=True),
        seed=0,
    )
    wall = time.perf_counter() - t0

    payload = {
        "what": (
            "DARTS second-order search convergence curve at the north-star "
            "32x32x3 input shape; dataset is the structured synthetic CIFAR "
            "stand-in unless real_data is true — accuracy here measures "
            "search/optimization plumbing against the documented synthetic "
            "ceiling, NOT real CIFAR-10 capability"
        ),
        "real_data": real,
        "platform": jax.devices()[0].platform,
        "input_shape": [32, 32, 3],
        "config": {
            "epochs": epochs, "layers": layers, "init_channels": channels,
            "n_nodes": 4, "batch": batch, "n_train": len(ds.x_train),
            "unrolled": True,
        },
        "ceiling_nearest_class_mean": round(ceiling, 4),
        "best_accuracy": round(result["best_accuracy"], 4),
        "fraction_of_ceiling": round(result["best_accuracy"] / max(ceiling, 1e-9), 4),
        "history": result["history"],
        "genotype": result.get("genotype"),
        "wallclock_s": round(wall, 1),
    }
    path = write_artifact("flagship", "synthetic_cifar_curve.json", payload)
    print("wrote", path, flush=True)


if __name__ == "__main__":
    main()
