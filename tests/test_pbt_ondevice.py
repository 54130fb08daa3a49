"""On-device Population Based Training (parallel/pbt.py + pbt-ondevice).

Covers the acceptance properties:
- seeded device selection is semantically equivalent to the host
  ``PbtSuggester`` reference (same cut points, same exploit set, perturb
  factors within spec, lineage labels match the host's shape),
- ghost rows (K=5 padded to a bucket of 8) never win and never get cloned,
- drain mid-run -> resume loses no member state,
- a same-seed rerun is bit-stable,
- the pbt-ondevice suggester dispatches the population once and the
  escape hatch falls back to the exact host path.
"""

from __future__ import annotations

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.core.types import (
    COHORT_KEY_LABEL,
    AlgorithmSpec,
    ExperimentSpec,
    FeasibleSpace,
    ObjectiveSpec,
    ObjectiveType,
    ParameterSpec,
    ParameterType,
    TrialCondition,
)
from katib_tpu.parallel.pbt import (
    HyperSpec,
    decode_member_hypers,
    encode_hypers,
    exploit_explore,
    make_pbt_generation_step,
    specs_from_json,
    specs_from_parameters,
    specs_to_json,
)
from katib_tpu.suggest.base import make_suggester
from katib_tpu.suggest.pbt import (
    GENERATION_LABEL,
    ONDEVICE_COHORT_KEY,
    PARENT_LABEL,
    PbtOnDeviceSuggester,
    resolve_pbt_ondevice,
)


def new_exp(spec):
    from katib_tpu.core.types import Experiment

    return Experiment(spec=spec)


SPECS = (HyperSpec("lr", "double", lo=1e-4, hi=1.0, log=True),)
CAT_SPECS = (
    HyperSpec("lr", "double", lo=1e-4, hi=1.0, log=True),
    HyperSpec("opt", "categorical", values=("sgd", "adam", "lamb")),
)


def _hypers(k, p=None, specs=SPECS):
    params = [{"lr": 10.0 ** -(1 + i % 4), "opt": ("sgd", "adam", "lamb")[i % 3]}
              for i in range(k)]
    return encode_hypers(specs, params, p or k), params


class TestSelectionParity:
    """Device exploit/explore vs the host PbtSuggester._segment reference."""

    def test_cut_points_match_np_quantile(self):
        scores = np.array([0.1, 0.9, 0.5, 0.95, 0.2, 0.4, 0.7, 0.3])
        h, _ = _hypers(8)
        _, _, _, stats = exploit_explore(
            jax.random.PRNGKey(0), jnp.asarray(scores), h,
            specs=SPECS, k=8, truncation=0.25,
        )
        lo, hi = np.quantile(scores, (0.25, 0.75))
        assert float(stats["lo"]) == pytest.approx(lo, rel=1e-6)
        assert float(stats["hi"]) == pytest.approx(hi, rel=1e-6)

    def test_exploit_set_matches_host_segment(self):
        # exactly round_half_up(8 * 0.25) = 2 members below the quantile:
        # the host's shuffled truncation and the device's worst-first pick
        # select the SAME set
        scores = np.array([0.05, 0.9, 0.5, 0.95, 0.02, 0.4, 0.7, 0.6])
        lo, hi = np.quantile(scores, (0.25, 0.75))
        host_exploit = {i for i, s in enumerate(scores) if s < lo}
        host_upper = {i for i, s in enumerate(scores) if s >= hi}
        assert len(host_exploit) == 2  # test premise
        h, _ = _hypers(8)
        parent, _, exploited, _ = exploit_explore(
            jax.random.PRNGKey(1), jnp.asarray(scores), h,
            specs=SPECS, k=8, truncation=0.25,
        )
        device_exploit = {i for i in range(8) if bool(exploited[i])}
        assert device_exploit == host_exploit
        # every exploiter cloned a top-quantile winner
        for i in device_exploit:
            assert int(parent[i]) in host_upper
        # everyone else keeps their own row
        for i in range(8):
            if i not in device_exploit:
                assert int(parent[i]) == i

    def test_small_population_floor_of_one(self):
        # 5 members, truncation 0.2: int(5*0.2)=1 but a 3-member partial
        # refill would floor to 0 without the fix; on device k=3
        scores = np.array([0.1, 0.9, 0.8])
        h, _ = _hypers(3)
        _, _, exploited, stats = exploit_explore(
            jax.random.PRNGKey(2), jnp.asarray(scores), h,
            specs=SPECS, k=3, truncation=0.2,
        )
        assert int(stats["n_exploit"]) >= 1
        assert int(exploited.sum()) == 1 and bool(exploited[0])

    def test_exploiters_inherit_winner_hypers_verbatim(self):
        scores = np.array([0.0, 1.0, 0.5, 0.9, 0.6, 0.55, 0.55, 0.58])
        h, _ = _hypers(8)
        parent, nh, exploited, _ = exploit_explore(
            jax.random.PRNGKey(3), jnp.asarray(scores), h,
            specs=SPECS, k=8, truncation=0.25,
        )
        for i in range(8):
            if bool(exploited[i]):
                w = int(parent[i])
                assert float(nh["lr"][i]) == float(h["lr"][w])

    def test_perturb_factors_within_spec(self):
        # explorers multiply by exactly 0.8 or 1.2 (clipped to bounds)
        scores = np.linspace(0.1, 0.9, 8)
        h, _ = _hypers(8)
        _, nh, exploited, _ = exploit_explore(
            jax.random.PRNGKey(4), jnp.asarray(scores), h,
            specs=SPECS, k=8, truncation=0.25,
        )
        for i in range(8):
            if bool(exploited[i]):
                continue
            old, new = float(h["lr"][i]), float(nh["lr"][i])
            ratio = new / old
            # the hypers are float32 rows: compare with the bounds as
            # float32 too (a member sitting on ``lo`` that draws x0.8 clips
            # back to it, ratio 1.0 — whether one does depends on the key)
            at_bound = np.float32(new) in (
                np.float32(SPECS[0].lo), np.float32(SPECS[0].hi)
            )
            assert at_bound or ratio == pytest.approx(0.8, rel=1e-5) \
                or ratio == pytest.approx(1.2, rel=1e-5)
            assert np.float32(SPECS[0].lo) <= np.float32(new) <= np.float32(SPECS[0].hi)

    def test_categorical_neighbor_step(self):
        scores = np.linspace(0.1, 0.9, 6)
        h, params = _hypers(6, specs=CAT_SPECS)
        _, nh, exploited, _ = exploit_explore(
            jax.random.PRNGKey(5), jnp.asarray(scores), h,
            specs=CAT_SPECS, k=6, truncation=0.25,
        )
        n = CAT_SPECS[1].n_choices
        for i in range(6):
            if bool(exploited[i]):
                continue
            old, new = int(h["opt"][i]), int(nh["opt"][i])
            assert new in ((old - 1) % n, (old + 1) % n)

    def test_resample_mode_keeps_or_redraws(self):
        scores = np.linspace(0.1, 0.9, 8)
        h, _ = _hypers(8)
        # p=0: explorers keep hypers untouched (the host branch never
        # perturbs in resample mode)
        _, nh0, expl, _ = exploit_explore(
            jax.random.PRNGKey(6), jnp.asarray(scores), h,
            specs=SPECS, k=8, truncation=0.25, resample_p=0.0,
        )
        for i in range(8):
            if not bool(expl[i]):
                assert float(nh0["lr"][i]) == float(h["lr"][i])
        # p=1: every explorer redraws from the prior, inside bounds
        _, nh1, expl, _ = exploit_explore(
            jax.random.PRNGKey(7), jnp.asarray(scores), h,
            specs=SPECS, k=8, truncation=0.25, resample_p=1.0,
        )
        changed = 0
        for i in range(8):
            v = float(nh1["lr"][i])
            assert SPECS[0].lo <= v <= SPECS[0].hi
            if not bool(expl[i]) and v != float(h["lr"][i]):
                changed += 1
        assert changed >= 3

    def test_diverged_member_heals_through_exploit(self):
        scores = np.array([np.nan, 0.9, 0.5, 0.95, 0.2, 0.4, 0.7, 0.3])
        h, _ = _hypers(8)
        parent, _, exploited, stats = exploit_explore(
            jax.random.PRNGKey(8), jnp.asarray(scores), h,
            specs=SPECS, k=8, truncation=0.25,
        )
        assert bool(exploited[0])  # the NaN row ranks worst and exploits
        assert not bool(stats["winners"][0])
        assert int(parent[0]) != 0


class TestGhostRows:
    def test_k5_in_bucket_of_8_never_wins_or_clones(self):
        # ghost rows carry absurdly good scores on purpose: selection must
        # still ignore them entirely
        scores = np.array([0.1, 0.9, 0.5, 0.95, 0.2, 99.0, 99.0, 99.0])
        h, _ = _hypers(5, p=8)
        parent, nh, exploited, stats = exploit_explore(
            jax.random.PRNGKey(9), jnp.asarray(scores), h,
            specs=SPECS, k=5, truncation=0.25,
        )
        winners = np.asarray(stats["winners"])
        assert not winners[5:].any(), "ghost row won"
        assert not np.asarray(exploited)[5:].any(), "ghost row exploited"
        for i in range(8):
            if bool(exploited[i]):
                assert int(parent[i]) < 5, "real member cloned a ghost"
            else:
                assert int(parent[i]) == i
        # ghost hypers ride along untouched
        np.testing.assert_array_equal(
            np.asarray(nh["lr"][5:]), np.asarray(h["lr"][5:])
        )


class TestSpaceRoundTrip:
    def test_specs_json_round_trip(self):
        parameters = [
            ParameterSpec("lr", ParameterType.DOUBLE,
                          FeasibleSpace(min=1e-4, max=1.0, distribution="logUniform")),
            ParameterSpec("opt", ParameterType.CATEGORICAL,
                          FeasibleSpace(list=["sgd", "adam"])),
        ]
        specs = specs_from_parameters(parameters)
        again = specs_from_json(specs_to_json(specs))
        assert again == specs
        assert again[0].log and again[0].kind == "double"
        assert again[1].values == ("sgd", "adam")

    def test_encode_decode_members(self):
        h, params = _hypers(4, specs=CAT_SPECS)
        for i in range(4):
            d = decode_member_hypers(CAT_SPECS, h, i)
            assert d["lr"] == pytest.approx(params[i]["lr"], rel=1e-5)
            assert d["opt"] == params[i]["opt"]


class TestGenerationStep:
    def test_population_converges_and_is_bit_stable(self):
        # toy quadratic: members descend (x-3)^2 with their own lr;
        # selection propagates good lrs and the rerun is bit-identical
        def member_step(state, hrow, batch):
            g = 2.0 * (state["x"] - 3.0)
            return {"x": state["x"] - hrow["lr"] * g}

        def member_eval(state, ev):
            return -((state["x"] - 3.0) ** 2)

        def run():
            specs = (HyperSpec("lr", "double", lo=1e-3, hi=1.0),)
            gen = make_pbt_generation_step(
                member_step, member_eval, specs=specs, k=6, truncation=0.25
            )
            h = encode_hypers(
                specs, [{"lr": 0.001 * (10 ** (i % 4))} for i in range(6)], 6
            )
            states = {"x": jnp.zeros((6,))}
            key = jax.random.PRNGKey(11)
            idx = jnp.zeros((15, 4), jnp.int32)
            data = {"d": jnp.zeros((8, 2))}
            out = []
            for g in range(4):
                key_g = jax.random.fold_in(jax.random.PRNGKey(11), g)
                states, h, _, scores, parent, expl = gen(
                    states, h, key_g, idx, data, data["d"][:4]
                )
                out.append(
                    (np.asarray(scores).copy(), np.asarray(parent).copy())
                )
            return states, out

        states_a, hist_a = run()
        states_b, hist_b = run()
        err = np.abs(np.asarray(states_a["x"]) - 3.0)
        # the same 60 steps with every member keeping its first lr: how far
        # the slowest stays from the optimum without selection (~2.66)
        lrs = np.array([0.001 * (10 ** (i % 4)) for i in range(6)])
        unselected = 3.0 * np.abs(1.0 - 2.0 * lrs) ** 60
        # which member exploits which depends on the key's stream; that
        # selection pulls the laggards in, and a winner converges, does not
        assert float(err.max()) < 0.5 * float(unselected.max())
        assert float(err.min()) < 1e-3
        for (sa, pa), (sb, pb) in zip(hist_a, hist_b):
            np.testing.assert_array_equal(sa, sb)
            np.testing.assert_array_equal(pa, pb)


def _ondevice_spec(tmp_path, *, population=6, generations=3, steps=15,
                   name=None, **kw):
    from katib_tpu.models.pbt_digits import pbt_digits_trial

    settings = {
        "n_population": str(population),
        "truncation_threshold": "0.25",
        "generations": str(generations),
        "steps_per_generation": str(steps),
        "suggestion_trial_dir": str(tmp_path / "pbt"),
        "random_state": "7",
    }
    settings.update(kw.pop("settings", {}))
    return ExperimentSpec(
        name=name or "pbt-ondev-test",
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
        ),
        algorithm=AlgorithmSpec(name="pbt-ondevice", settings=settings),
        parameters=[
            ParameterSpec(
                "lr", ParameterType.DOUBLE, FeasibleSpace(min=1e-4, max=0.5)
            )
        ],
        train_fn=pbt_digits_trial,
        max_trial_count=population,
        parallel_trial_count=population,
        **kw,
    )


class TestOnDeviceSuggester:
    def test_single_dispatch_then_exhausted(self, tmp_path):
        spec = _ondevice_spec(tmp_path)
        s = make_suggester(spec)
        assert isinstance(s, PbtOnDeviceSuggester) and s.on_device
        exp = new_exp(spec)
        batch = s.get_suggestions(exp, 2)  # asked for 2, population wins
        assert len(batch) == 6
        assert all(p.labels[COHORT_KEY_LABEL] == ONDEVICE_COHORT_KEY for p in batch)
        assert all(p.labels[GENERATION_LABEL] == "0" for p in batch)
        shared = batch[0].as_dict()
        assert shared["pbt_generations"] == 3
        assert "pbt_space" in shared and "pbt_seed" in shared
        assert s.get_suggestions(exp, 6) == []
        # the grouping window was widened to hold the whole population
        assert spec.cohort_width >= 6

    def test_dispatched_survives_state_round_trip(self, tmp_path):
        spec = _ondevice_spec(tmp_path)
        s = make_suggester(spec)
        exp = new_exp(spec)
        s.get_suggestions(exp, 6)
        fresh = make_suggester(_ondevice_spec(tmp_path))
        fresh.load_state_dict(s.state_dict())
        assert fresh.get_suggestions(exp, 6) == []

    def test_escape_hatch_falls_back_to_host_path(self, tmp_path, monkeypatch):
        monkeypatch.delenv("KATIB_PBT_ONDEVICE", raising=False)
        spec = _ondevice_spec(tmp_path, settings={"on_device": "false"})
        assert not resolve_pbt_ondevice(spec)
        s = make_suggester(spec)
        exp = new_exp(spec)
        got = s.get_suggestions(exp, 2)  # host path honors count
        assert len(got) == 2
        assert COHORT_KEY_LABEL not in got[0].labels
        assert os.path.isdir(s.checkpoint_dir_for(got[0].name))

    def test_env_kill_switch_wins(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KATIB_PBT_ONDEVICE", "0")
        spec = _ondevice_spec(tmp_path)
        assert not resolve_pbt_ondevice(spec)
        monkeypatch.setenv("KATIB_PBT_ONDEVICE", "1")
        spec2 = _ondevice_spec(tmp_path, settings={"on_device": "false"})
        assert resolve_pbt_ondevice(spec2)

    def test_spec_field_overrides_setting(self, tmp_path, monkeypatch):
        monkeypatch.delenv("KATIB_PBT_ONDEVICE", raising=False)
        spec = _ondevice_spec(tmp_path, pbt_ondevice=False)
        assert not resolve_pbt_ondevice(spec)

    def test_validate_budget_covers_population(self, tmp_path, monkeypatch):
        from katib_tpu.suggest.base import SuggesterError

        monkeypatch.delenv("KATIB_PBT_ONDEVICE", raising=False)
        spec = _ondevice_spec(tmp_path)
        spec.max_trial_count = 4
        with pytest.raises(SuggesterError, match="max_trial_count"):
            PbtOnDeviceSuggester.validate(spec)


class TestOnDeviceEndToEnd:
    """Orchestrator-driven on-device PBT (real digits model, CPU)."""

    def test_lineage_settles_like_host_path(self, tmp_path):
        from katib_tpu.orchestrator.orchestrator import Orchestrator
        from katib_tpu.utils import observability as obs

        gen_before = obs.pbt_generations.get()
        spec = _ondevice_spec(tmp_path, async_orch=False)
        exp = Orchestrator(workdir=str(tmp_path / "wd")).run(spec)
        done = [t for t in exp.trials.values() if t.condition.is_completed_ok()]
        assert len(done) == 6
        names = {t.name for t in done}
        for t in done:
            # same label shape the host path stamps on next-gen members
            assert t.spec.labels[GENERATION_LABEL] == "3"
            assert t.spec.labels[PARENT_LABEL] in names
            assert t.objective_value(spec.objective) is not None
        assert obs.pbt_generations.get() - gen_before == 3

    def test_drain_resume_loses_no_member(self, tmp_path):
        """Drain after the first generation boundary; resume completes the
        remaining generations with every member's state intact."""
        from katib_tpu.models.pbt_digits import pbt_digits_trial
        from katib_tpu.runner.cohort import run_cohort
        from katib_tpu.store.base import MemoryObservationStore
        from katib_tpu.suggest.base import make_suggester as mk

        spec = _ondevice_spec(tmp_path, generations=3)
        s = mk(spec)
        exp = new_exp(spec)
        proposals = s.get_suggestions(exp, 6)
        from katib_tpu.core.types import Trial, TrialSpec

        def build_trials():
            return [
                Trial(
                    name=p.name,
                    experiment_name=spec.name,
                    spec=TrialSpec(
                        assignments=list(p.assignments),
                        labels=dict(p.labels),
                        train_fn=pbt_digits_trial,
                    ),
                    checkpoint_dir=s.checkpoint_dir_for(p.name),
                )
                for p in proposals
            ]

        store = MemoryObservationStore()
        drain = threading.Event()
        drain.set()  # drain at the FIRST boundary: exactly one generation
        results = run_cohort(
            build_trials(), store, spec.objective, drain_event=drain
        )
        assert all(
            r.condition is TrialCondition.DRAINED for r in results.values()
        )
        ckpt_steps = {}
        for p in proposals:
            from katib_tpu.utils.checkpoint import TrialCheckpointer

            steps = TrialCheckpointer(s.checkpoint_dir_for(p.name)).all_steps()
            assert steps, f"member {p.name} lost its checkpoint on drain"
            ckpt_steps[p.name] = steps
        # resume: same names, same checkpoint dirs -> the loop re-enters at
        # generation 1 and finishes
        store2 = MemoryObservationStore()
        results2 = run_cohort(build_trials(), store2, spec.objective)
        assert all(
            r.condition is TrialCondition.SUCCEEDED for r in results2.values()
        )
        for p in proposals:
            series = store2.get(p.name, "accuracy")
            reported_steps = [m.step for m in series]
            # generations 1..2 ran on resume — generation 0 was not redone
            assert reported_steps == [1, 2]

    def test_rerun_is_bit_stable(self, tmp_path):
        from katib_tpu.models.pbt_digits import pbt_digits_trial
        from katib_tpu.runner.cohort import run_cohort
        from katib_tpu.store.base import MemoryObservationStore
        from katib_tpu.core.types import Trial, TrialSpec

        def run_once(subdir):
            spec = _ondevice_spec(
                tmp_path / subdir, generations=2, name=f"bit-{subdir}"
            )
            s = make_suggester(spec)
            proposals = s.get_suggestions(new_exp(spec), 6)
            trials = [
                Trial(
                    name=f"m{i}",
                    experiment_name=spec.name,
                    spec=TrialSpec(
                        assignments=list(p.assignments),
                        labels=dict(p.labels),
                        train_fn=pbt_digits_trial,
                    ),
                    checkpoint_dir=s.checkpoint_dir_for(p.name),
                )
                for i, p in enumerate(proposals)
            ]
            store = MemoryObservationStore()
            run_cohort(trials, store, spec.objective)
            return [
                [m.value for m in store.get(f"m{i}", "accuracy")]
                for i in range(6)
            ]

        assert run_once("a") == run_once("b")
