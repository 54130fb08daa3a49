"""``correct`` for the ``gqa_moe`` family, shown to pass and shown to fail.

At a tiny size on the CPU (``tests/cells_gqa_moe``: one period of four layers,
layer 0 full attention without positions, layers 1-3 a 16-key window with
rotary positions at 64 positions, 6 query heads over 2 key-value heads, experts
4-11 of 16 held, 4 a token): the program through the harness comes out
correct; the control (the plain reference in fp8) and each planted fault (half
the batch, the state unchanged, the routed sum left out, the window left out)
come out not correct at every learning rate.

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

BENCH = os.path.join(HERE, "cells_gqa_moe", "BENCHMARK.json")
CELL = "tinygqa-lr4low-steps12"
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
@pytest.mark.parametrize("control", ("fp8", "half_batch", "state_unchanged", "no_routed", "no_window"))
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


def test_window_left_out_of_the_timed_path_is_not_correct(cell, monkeypatch):
    """The fault planted in the program underneath the harness: the window
    layers' attention sees the whole prefix."""
    from katib_tpu.models import transformer

    real = transformer.make_attention_fn
    monkeypatch.setattr(transformer, "make_attention_fn", lambda mesh=None, strategy="ring", window=None: real(mesh, strategy))
    result = run.run_cell(cell, 5, 6.0, False)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1


def test_routed_sum_left_out_of_the_timed_path_is_not_correct(cell, monkeypatch):
    """The grouped product returns nothing."""
    import jax
    import jax.numpy as jnp

    def nothing(lhs, rhs, group_sizes, **kw):
        return jnp.zeros((lhs.shape[0], rhs.shape[-1]), kw.get("preferred_element_type", lhs.dtype))

    monkeypatch.setattr(jax.lax, "ragged_dot", nothing)
    result = run.run_cell(cell, 5, 6.0, False)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1


def test_a_checkout_without_the_block_is_refused_at_once(cell, monkeypatch):
    """On a program that has no such block (the parent of the PR that brought
    it) ``transformer_trial`` would ignore ``block`` and train GPT-2 blocks."""
    import importlib.util

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(SystemExit, match="no block 'gqa_moe'"):
        cell.family.experiment_doc("x", cell.sizes, cell.traffic, 1)
