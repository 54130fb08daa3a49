"""``correct`` for the ``dsa_moe`` family, shown to pass and shown to fail.

At a tiny size on the CPU (``tests/cells_dsa_moe``: four layers, 4 query heads
over 2 key-value heads, an indexer of 2 heads of 16 selecting 16 of the
visible keys at 64 positions, experts 4-11 of 16 held, 4 a token): the
program through the harness comes out correct; the control (the plain
reference in fp8) and each planted fault (half the batch, the state
unchanged, every visible key attended to, the indexer left out of the update)
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

BENCH = os.path.join(HERE, "cells_dsa_moe", "BENCHMARK.json")
CELL = "tinydsa-lr4low-steps12"
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
    assert {"first_indexer_gap", "trained_indexer_gap"} <= set(result["checks"])


@pytest.mark.parametrize("seed", (1, 2, 3))
@pytest.mark.parametrize("control", ("fp8", "half_batch", "state_unchanged", "no_select", "indexer_frozen"))
def test_control_and_planted_faults_fail(cell, seed, control):
    """The reference in the program's place: one precision lower, or with a
    fault planted, it fails at least one number at every learning rate.  The
    indexer left out of the update moves its loss at 3e-5 by less than this
    tiny size's bfloat16 noise (512 tokens a step; the limits file has the
    readings): there it moves it, and fails at every higher rate.  At the
    chip cell's 16384 tokens it fails at 3e-5 too, by 2.4 times the limit, and
    at 3e-4 by 8.3 (``limits/keye2-ep8-lr4low-steps12.json``, my chip runs,
    PR 41)."""
    family = cell.family
    kw = {"precision": control} if control == "fp8" else {"fault": control}
    for lr in family.lr_values(cell.traffic):
        reference = family.reference_series(cell.sizes, cell.traffic, seed, lr)
        got = family.reference_series(cell.sizes, cell.traffic, seed, lr, **kw)
        gaps = family.compare(got, reference)
        if control == "indexer_frozen" and lr == 3e-05:
            assert gaps["trained_indexer_gap"] > 1e-3 and gaps["first_loss_gap"] == 0.0
        else:
            assert not verdict(cell, gaps, lr), (control, lr, gaps)
        assert verdict(cell, family.compare(reference, reference), lr)


def test_every_key_attended_in_the_timed_path_is_not_correct(cell, monkeypatch):
    """The selection left out of the program underneath the harness: each
    query attends to every visible key."""
    from katib_tpu.models import transformer

    real = transformer.sparse_attention_fn
    monkeypatch.setattr(transformer, "sparse_attention_fn", lambda kernel, topk: real(kernel, 1 << 30))
    result = run.run_cell(cell, 5, 6.0, False)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1


def test_indexer_left_out_of_the_timed_paths_update_is_not_correct(cell, monkeypatch):
    """The program trains no indexer: its loss is reported and not
    differentiated."""
    import jax
    import jax.numpy as jnp

    from katib_tpu.models import dsa_moe, lm_head

    def frozen(self, outputs, tokens):
        kl = outputs.indexer_losses
        loss = lm_head.lm_loss(outputs.head, tokens) + jnp.sum(jax.lax.stop_gradient(kl))
        return loss, {"indexer_kl": jnp.mean(kl)}

    monkeypatch.setattr(dsa_moe.DsaMoeLM, "training_loss", frozen)
    result = run.run_cell(cell, 5, 6.0, False)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1


def test_a_checkout_without_the_block_is_refused_at_once(cell, monkeypatch):
    """On a program that has no such block (the parent of the PR that brought
    it) ``transformer_trial`` would ignore ``block`` and train GPT-2 blocks."""
    import importlib.util

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(SystemExit, match="no block 'dsa_moe'"):
        cell.family.experiment_doc("x", cell.sizes, cell.traffic, 1)
