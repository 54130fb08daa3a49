"""``correct`` for the ``kda_moe`` family, shown to pass and shown to fail.

At a tiny size on the CPU (``tests/cells_kda_moe``: four layers, one gated
grouped-query layer of 4 query heads over 2 key-value heads and three
delta-rule layers of 2 heads of 32 at 128 positions, experts 4-11 of 16 held,
4 a token, one shared): the program through the harness comes out correct;
the control (the plain reference in fp8) and each planted fault (half the
batch, the state unchanged, the step without its factor 2, no output gate on
either layer kind) come out not correct at every learning rate.  The two
faults that move this tiny cell's loss by less than its bfloat16 noise (one
decay a head; the scan's state in bfloat16) are held by the comparisons at
float32 in ``tests/test_kda_moe.py``, against the model and against the scan.

    python -m pytest benchmark/tests -q
"""

import functools
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
import run  # noqa: E402

BENCH = os.path.join(HERE, "cells_kda_moe", "BENCHMARK.json")
CELL = "tinykda-lr4low-steps12"
SEEDS = (3, 2147483659)
#: the window: a tiny trial of this family takes 2-6 s on a loaded CPU (twelve
#: steps through three delta-rule layers' chunk scans), so at least one ends
WINDOW_S = 12.0


@pytest.fixture(scope="module")
def cell():
    return run.Cell(CELL, BENCH)


def verdict(cell, gaps, lr):
    return all(
        math.isfinite(v) and v <= run.limit_of(cell.limits[k], {"lr": lr}) for k, v in gaps.items()
    )


@pytest.mark.parametrize("seed", SEEDS)
def test_program_is_correct(cell, seed):
    result = run.run_cell(cell, seed, WINDOW_S, False)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["metrics"]["trials_per_hour"]["value"] > 0
    assert not result["not_compared"]


@pytest.mark.parametrize("seed", (1, 2))
@pytest.mark.parametrize("control", ("fp8", "half_batch", "state_unchanged", "beta_one", "no_gate"))
def test_control_and_planted_faults_fail(cell, seed, control):
    """The reference in the program's place: one precision lower, or with a
    fault planted, it fails at least one number at every learning rate."""
    family = cell.family
    kw = {"precision": control} if control == "fp8" else {"fault": control}
    for lr in family.lr_values(cell.traffic):
        reference = family.reference_series(cell.sizes, cell.traffic, seed, lr)
        got = family.reference_series(cell.sizes, cell.traffic, seed, lr, **kw)
        assert not verdict(cell, family.compare(got, reference), lr), (control, lr)
        assert verdict(cell, family.compare(reference, reference), lr)


def test_the_step_without_its_factor_in_the_timed_path_is_not_correct(cell, monkeypatch):
    """The scan given half the step underneath the harness (no negative
    eigenvalue): the program is not correct."""
    from katib_tpu.models import transformer

    real = transformer.kda_scan_fn

    @functools.lru_cache(maxsize=None)  # one callable: every trial reuses its programs
    def halved(kernel):
        scan = real(kernel)
        return lambda q, k, v, g, beta: scan(q, k, v, g, beta / 2)

    monkeypatch.setattr(transformer, "kda_scan_fn", halved)
    result = run.run_cell(cell, 5, WINDOW_S, False)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1


def test_a_checkout_without_the_block_is_refused_at_once(cell, monkeypatch):
    """On a program that has no such block (the parent of the PR that brought
    it) ``transformer_trial`` would ignore ``block`` and train GPT-2 blocks."""
    import importlib.util

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(SystemExit, match="no block 'kda_moe'"):
        cell.family.experiment_doc("x", cell.sizes, cell.traffic, 1)
