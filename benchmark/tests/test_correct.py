"""The comparison that decides ``correct``, shown to pass and shown to fail.

At a tiny size on the CPU (``tests/cells``): the program through the harness
comes out correct; the control (the plain reference in fp8, put in the
program's place) and each fault a one-chip training cell can have come out not
correct.  The faults are planted in the program underneath the harness, which
then drives the rest of a run (``run.run_cell``: everything after the look for
a chip).

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

BENCH = os.path.join(HERE, "cells", "BENCHMARK.json")
CELL = "tiny-lr4-steps12"
SEEDS = (3, 2147483659)


@pytest.fixture(scope="module")
def cell():
    return run.Cell(CELL, BENCH)


def verdict(cell, gaps):
    return all(math.isfinite(v) and v <= cell.limits[k] for k, v in gaps.items())


@pytest.mark.parametrize("seed", SEEDS)
def test_program_is_correct(cell, seed):
    result = run.run_cell(cell, seed, 6.0, False)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["metrics"]["trials_per_hour"]["value"] > 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("seed", (1, 2, 3))
@pytest.mark.parametrize("control", ("fp8", "half_batch", "state_unchanged"))
def test_control_and_planted_faults_fail(cell, seed, control):
    """The reference in the program's place: one precision lower, or with a
    fault planted, it fails at least one number at every learning rate."""
    family = cell.family
    kw = {"precision": control} if control == "fp8" else {"fault": control}
    for lr in family.lr_values(cell.traffic):
        reference = family.reference_series(cell.sizes, cell.traffic, seed, lr)
        got = family.reference_series(cell.sizes, cell.traffic, seed, lr, **kw)
        gaps = family.compare(got, reference)
        assert not verdict(cell, gaps), (control, lr, gaps)
        assert verdict(cell, family.compare(reference, reference))


def _state_unchanged(monkeypatch):
    import optax

    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)


def _half_batch(monkeypatch):
    from katib_tpu.models import transformer

    inner = transformer.lm_loss
    monkeypatch.setattr(
        transformer, "lm_loss", lambda logits, tokens: inner(logits[: len(logits) // 2], tokens[: len(tokens) // 2])
    )


def _altered_answer(monkeypatch):
    from katib_tpu.runner.context import TrialContext

    inner = TrialContext.report

    def report(self, step=None, **metrics):
        if "eval_loss" in metrics:
            metrics["eval_loss"] *= 1.01
        return inner(self, step=step, **metrics)

    monkeypatch.setattr(TrialContext, "report", report)


@pytest.mark.parametrize("fault", (_state_unchanged, _half_batch, _altered_answer))
def test_broken_timed_path_is_not_correct(cell, monkeypatch, fault):
    fault(monkeypatch)
    result = run.run_cell(cell, 5, 6.0, False)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 1


def test_reference_adamw_and_schedule_match_optax():
    """The reference's hand-written schedule against optax's."""
    import optax

    family = run.load_module("families", "gpt2")
    for steps in (12, 30, 40):
        sched = optax.warmup_cosine_decay_schedule(0.0, 3e-3, max(1, int(steps * 0.1)), steps)
        for count in range(steps):
            assert family.lr_at(count, 3e-3, steps) == pytest.approx(float(sched(count)), rel=1e-5, abs=1e-12)


def test_counts_from_shapes():
    family = run.load_module("families", "gpt2")
    small = run.load_json(os.path.join(os.path.dirname(HERE), "configs", "gpt2-small.json"))
    sizes = {k: small[k] for k in family.SIZE_KEYS}
    assert family.matmul_params(sizes) == 12 * 12 * 768 * 768 + 768 * 50257
    cost = family.flash_attention_cost(sizes)
    assert cost["flops"] == 3 * 2 * 8 * 12 * 1024 * 1024 * 64
    assert family.step_flops(sizes) == 6.0 * family.matmul_params(sizes) * 8192 + cost["flops"]
