"""``correct`` for the ``looped`` family, shown to pass and shown to fail.

At a tiny size on the CPU (``tests/cells_looped``: two layers run four times
over the same weights, an exit after every pass, 4 heads of 16 at 64
positions): the program through the harness comes out correct; the control
(the plain reference in fp8) and each planted fault (half the batch, the state
unchanged, one pass only, the exits weighted evenly) come out not correct at
every learning rate.

    python -m pytest benchmark/tests -q
"""

import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
import run  # noqa: E402

BENCH = os.path.join(HERE, "cells_looped", "BENCHMARK.json")
CELL = "tinylooped-lr4low-steps12"
SEEDS = (3, 2147483659)


@pytest.fixture(scope="module")
def cell():
    return run.Cell(CELL, BENCH)


def verdict(cell, gaps, lr):
    return all(
        math.isfinite(v) and v <= run.limit_of(cell.limits[k], {"lr": lr}) for k, v in gaps.items()
    )


@pytest.mark.parametrize("seed", SEEDS)
def test_program_is_correct(cell, seed):
    result = run.run_cell(cell, seed, 6.0, False)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["metrics"]["trials_per_hour"]["value"] > 0
    assert not result["not_compared"]


@pytest.mark.parametrize("seed", (1, 2, 3))
@pytest.mark.parametrize("control", ("fp8", "half_batch", "state_unchanged", "one_pass", "even_exits"))
def test_control_and_planted_faults_fail(cell, seed, control):
    """The reference in the program's place: one precision lower, or with a
    fault planted, it fails at least one number at every learning rate."""
    family = cell.family
    kw = {"precision": control} if control == "fp8" else {"fault": control}
    for lr in family.lr_values(cell.traffic):
        reference = family.reference_series(cell.sizes, cell.traffic, seed, lr)
        got = family.reference_series(cell.sizes, cell.traffic, seed, lr, **kw)
        gaps = family.compare(got, reference)
        assert not verdict(cell, gaps, lr), (control, lr, gaps)
        assert verdict(cell, family.compare(reference, reference), lr)


def test_one_pass_in_the_timed_path_is_not_correct(cell, monkeypatch):
    """The fault planted in the program underneath the harness: the model is
    built with one pass whatever the trial asks for."""
    import dataclasses

    from katib_tpu.models import transformer

    real = transformer._BLOCKS["looped"]

    def one_pass(p, vocab, mesh):
        model = real(p, vocab, mesh)
        return model.clone(sizes=dataclasses.replace(model.sizes, ut_steps=1))

    monkeypatch.setitem(transformer._BLOCKS, "looped", one_pass)
    result = run.run_cell(cell, 5, 6.0, False)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1


def test_exits_weighted_evenly_in_the_timed_path_is_not_correct(cell, monkeypatch):
    """The gate left out of the loss: every exit weighs a quarter."""
    import jax.numpy as jnp

    from katib_tpu.models import looped

    monkeypatch.setattr(looped, "exit_distribution", lambda gate: jnp.full_like(gate, 1.0 / len(gate)))
    result = run.run_cell(cell, 5, 6.0, False)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1


def test_a_checkout_without_the_block_is_refused_at_once(cell, monkeypatch):
    """On a program that has no such block (the parent of the PR that brought
    it) ``transformer_trial`` would ignore ``block`` and train GPT-2 blocks."""
    import importlib.util

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(SystemExit, match="no block 'looped'"):
        cell.family.experiment_doc("x", cell.sizes, cell.traffic, 1)
