"""``correct`` for the ``mla_moe`` family, shown to pass and shown to fail.

At a tiny size on the CPU (``tests/cells_mla_moe``: one dense layer and two
expert layers, experts 4-11 of 16 held, 4 a token): the program through the
harness comes out correct; the control (the plain reference in fp8) and each
planted fault (half the batch, the state unchanged, the routed sum left out)
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

BENCH = os.path.join(HERE, "cells_mla_moe", "BENCHMARK.json")
CELL = "tinymoe-lr4low-steps12"
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
@pytest.mark.parametrize("control", ("fp8", "half_batch", "state_unchanged", "no_routed"))
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


def test_routed_sum_left_out_of_the_timed_path_is_not_correct(cell, monkeypatch):
    """The fault planted in the program underneath the harness: the grouped
    product returns nothing."""
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
    with pytest.raises(SystemExit, match="no block 'mla_moe'"):
        cell.family.experiment_doc("x", cell.sizes, cell.traffic, 1)


def test_counts_from_shapes():
    family = run.load_module("families", "mla_moe")
    config = run.load_json(os.path.join(os.path.dirname(HERE), "configs", "kanana-2-30b-a3b-ep8.json"))
    sizes = {k: config[k] for k in family.SIZE_KEYS}
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    expert = 3 * 2048 * 768
    per_token = 5 * attention + 3 * 2048 * 6144 + 4 * (2048 * 128 + 2 * expert + 0.75 * expert) + 2048 * 16032
    assert family.matmul_params(sizes) == per_token
    cost = family.flash_attention_cost(sizes)
    assert cost["flops"] == 3 * 2 * 32 * 4096 * 4096 * (192 + 128)
    assert cost["calls_per_step"] == 5
    assert family.step_flops(sizes) == 6.0 * per_token * 8192 + 5 * cost["flops"]
    assert 17.6e12 < family.step_flops(sizes) < 17.8e12
    held = 4 * 8192 * 6 / 8
    assert family.expert_product_cost(sizes, held)["flops"] == 6.0 * expert * held
    # every published key is in the file under its own name; three are cut
    changed = {k for k, v in config["source_config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
