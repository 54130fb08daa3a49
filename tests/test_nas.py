"""DARTS + ENAS tests (tiny configs; CPU-backend JAX per conftest —
the reference's CI strategy of CPU trial-image variants, SURVEY.md §4).

Slow tier: every test here compiles real (if tiny) search/train programs —
the file dominates the suite wall-clock, so it runs in the merge gate, not
the PR fast lane (op-level coverage stays fast in test_fused_ops /
test_depthwise)."""

import json

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from katib_tpu.core.types import (
    AlgorithmSpec,
    Experiment,
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
from katib_tpu.suggest import SuggesterError, SuggestionsNotReady, make_suggester
from katib_tpu.suggest.base import SearchExhausted
from tests.helpers import complete_trial

TINY_PRIMS = ("none", "skip_connection", "separable_convolution_3x3", "max_pooling_3x3")


def nas_config():
    return NasConfig(
        graph_config=GraphConfig(num_layers=4),
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
            NasOperation("skip_connection"),
        ),
    )


def nas_spec(algo="darts", settings=None):
    return ExperimentSpec(
        name=f"nas-{algo}",
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
        ),
        algorithm=AlgorithmSpec(name=algo, settings=settings or {}),
        nas_config=nas_config(),
        train_fn=lambda ctx: None,
    )


class TestDartsModel:
    def test_forward_shapes(self):
        from katib_tpu.nas.darts.model import DartsNetwork, init_alphas

        net = DartsNetwork(
            primitives=TINY_PRIMS, init_channels=8, num_layers=2, num_classes=4,
            remat=False,
        )
        alphas = init_alphas(4, len(TINY_PRIMS), jax.random.PRNGKey(0))
        x = np.zeros((2, 8, 8, 3), np.float32)
        w = net.init(jax.random.PRNGKey(1), x, alphas)
        logits = net.apply(w, x, alphas)
        assert logits.shape == (2, 4)
        assert logits.dtype == np.float32

    def test_genotype_extraction(self):
        from katib_tpu.nas.darts.model import Alphas, extract_genotype

        import jax.numpy as jnp

        k = sum(j + 2 for j in range(4))
        # make 'none' dominant everywhere: genotype must never select it
        normal = jnp.zeros((k, len(TINY_PRIMS))).at[:, 0].set(5.0)
        geno = extract_genotype(
            Alphas(normal=normal, reduce=normal), TINY_PRIMS, n_nodes=4
        )
        for node in geno.normal:
            assert len(node) == 2
            for op, edge in node:
                assert op != "none"

    def test_search_step_improves_loss(self):
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts import DartsHyper, run_darts_search

        ds = synthetic_classification(128, 64, (8, 8, 3), 4, seed=1, noise=0.3)
        out = run_darts_search(
            ds,
            primitives=TINY_PRIMS,
            num_layers=2,
            init_channels=8,
            num_epochs=2,
            batch_size=32,
            hyper=DartsHyper(unrolled=False),
            seed=0,
        )
        assert out["history"][-1]["train_loss"] < out["history"][0]["train_loss"] * 1.2
        assert len(out["genotype"].normal) == 4

    def test_genotype_trains_as_fixed_network(self):
        """Augment phase: the genotype a search discovers materializes as a
        discrete network and trains above chance — search output is usable,
        not just printable."""
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts import DartsHyper, run_darts_search, train_genotype

        ds = synthetic_classification(128, 64, (8, 8, 3), 4, seed=1, noise=0.2)
        out = run_darts_search(
            ds, primitives=TINY_PRIMS, num_layers=2, init_channels=4,
            n_nodes=2, num_epochs=1, batch_size=32,
            hyper=DartsHyper(unrolled=False), seed=0,
        )
        acc = train_genotype(
            out["genotype"], ds, init_channels=4, num_layers=2,
            lr=0.05, epochs=3, batch_size=32,
        )
        assert acc > 0.3  # 4 classes, low noise: must beat chance clearly

    def test_darts_trial_with_augment_reports_metric(self, tmp_path):
        """The orchestrated trial path: search writes genotype.json into the
        trial checkpoint dir, and augment_epochs > 0 trains the discovered
        net and reports augment_accuracy."""
        import json as _json

        from katib_tpu.nas.darts.search import darts_trial
        from katib_tpu.runner.context import TrialContext

        reports: list[dict] = []

        class Ctx:
            params = {
                "algorithm-settings": _json.dumps({
                    "n_train": "128", "n_test": "64", "num_epochs": "1",
                    "batch_size": "32", "init_channels": "4",
                    "num_nodes": "2", "unrolled": "false",
                    "augment_epochs": "1",
                }),
                "search-space": _json.dumps(list(TINY_PRIMS)),
                "num-layers": "2",
            }
            checkpoint_dir = str(tmp_path / "trial0")
            mesh = None
            _checkpointer = None

            def report(self, **kw):
                reports.append(kw)
                return True

            ensure_checkpoint_dir = TrialContext.ensure_checkpoint_dir
            checkpointer = TrialContext.checkpointer
            save_checkpoint = TrialContext.save_checkpoint
            restore_checkpoint = TrialContext.restore_checkpoint

        darts_trial(Ctx())
        geno = _json.loads((tmp_path / "trial0" / "genotype.json").read_text())
        assert geno["normal"] and geno["reduce"]
        assert any("augment_accuracy" in r for r in reports)
        # the search snapshot landed under the trial dir (preemption resume)
        assert (tmp_path / "trial0" / "search").is_dir()

    def test_darts_trial_honors_search_augment_and_paired_settings(self, tmp_path):
        """Katib-style algorithm settings flow through to the search: the
        reference's crop+flip search transforms (search_augment) and the
        paired finite-difference Hessian (paired_hessian, a bool field
        that must parse as a bool, not float-coerce)."""
        import json as _json

        from katib_tpu.nas.darts.search import darts_trial
        from katib_tpu.runner.context import TrialContext

        reports: list[dict] = []

        class Ctx:
            params = {
                "algorithm-settings": _json.dumps({
                    "dataset": "digits", "n_train": "96", "n_test": "48",
                    "num_epochs": "1", "batch_size": "16",
                    "init_channels": "4", "num_nodes": "2",
                    "search_augment": "true", "paired_hessian": "true",
                }),
                "search-space": _json.dumps(list(TINY_PRIMS)),
                "num-layers": "2",
            }
            checkpoint_dir = str(tmp_path / "trial1")
            mesh = None
            _checkpointer = None

            def report(self, **kw):
                reports.append(kw)
                return True

            ensure_checkpoint_dir = TrialContext.ensure_checkpoint_dir
            checkpointer = TrialContext.checkpointer
            save_checkpoint = TrialContext.save_checkpoint
            restore_checkpoint = TrialContext.restore_checkpoint

        # record that the augmentation actually runs inside the search
        # (imported at call time, so patching the module attr intercepts)
        import katib_tpu.models.augmentation as aug_mod

        calls = []
        real = aug_mod.random_crop_flip

        def recording(key, x, **kw):
            calls.append(x.shape)
            return real(key, x, **kw)

        orig = aug_mod.random_crop_flip
        aug_mod.random_crop_flip = recording
        try:
            darts_trial(Ctx())
        finally:
            aug_mod.random_crop_flip = orig
        geno = _json.loads((tmp_path / "trial1" / "genotype.json").read_text())
        assert geno["normal"] and geno["reduce"]
        assert reports and all(0.0 <= r["accuracy"] <= 1.0 for r in reports)
        assert calls, "search_augment setting did not reach the epoch body"

    def test_darts_trial_honors_step_loop_settings(self, tmp_path, monkeypatch):
        """stepLoopWindow (the Katib-style CR spelling) flows from
        algorithm-settings into the search: the windowed device-resident
        step loop engages with the requested fold, observable on the
        steps-per-dispatch gauge; remat=false rides the same surface."""
        import json as _json

        from katib_tpu.nas.darts.search import darts_trial
        from katib_tpu.runner.context import TrialContext
        from katib_tpu.utils import observability as obs

        monkeypatch.delenv("KATIB_STEP_LOOP", raising=False)
        monkeypatch.delenv("KATIB_STEP_LOOP_WINDOW", raising=False)

        class Ctx:
            params = {
                "algorithm-settings": _json.dumps({
                    "dataset": "digits", "n_train": "96", "n_test": "48",
                    "num_epochs": "1", "batch_size": "16",
                    "init_channels": "4", "num_nodes": "2",
                    "stepLoopWindow": "2", "remat": "false",
                }),
                "search-space": _json.dumps(list(TINY_PRIMS)),
                "num-layers": "2",
            }
            checkpoint_dir = str(tmp_path / "trial-sl")
            mesh = None
            _checkpointer = None

            def report(self, **kw):
                return True

            def should_stop(self):
                return False

            ensure_checkpoint_dir = TrialContext.ensure_checkpoint_dir
            checkpointer = TrialContext.checkpointer
            save_checkpoint = TrialContext.save_checkpoint
            restore_checkpoint = TrialContext.restore_checkpoint

        darts_trial(Ctx())
        # 48-sample w-split / batch 16 = 3 steps; window 2 -> dispatches of
        # 2 + 1 steps = 1.5 steps per dispatch, window gauge reads 2
        assert obs.step_loop_window.get(workload="darts") == 2.0
        assert obs.steps_per_dispatch.get(workload="darts") == 1.5

    def test_search_resumes_from_checkpoint(self, tmp_path):
        """A restarted search picks up at the last completed epoch (flaky
        single-chip pools: a relay drop must not restart a long search)."""
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts import DartsHyper, run_darts_search

        ds = synthetic_classification(64, 32, (8, 8, 3), 4, seed=1, noise=0.3)
        kw = dict(
            primitives=TINY_PRIMS, num_layers=2, init_channels=4, n_nodes=2,
            batch_size=32, hyper=DartsHyper(unrolled=False), seed=0,
            checkpoint_dir=str(tmp_path / "ck"),
        )
        first = run_darts_search(ds, num_epochs=1, **kw)
        assert [h["epoch"] for h in first["history"]] == [0]

        second = run_darts_search(ds, num_epochs=3, **kw)
        # epoch 0 was restored (sidecar history), 1..2 ran — the report
        # covers the FULL search and time stays monotonic across restarts
        assert [h["epoch"] for h in second["history"]] == [0, 1, 2]
        assert second["history"][0] == first["history"][0]
        elapsed = [h["elapsed_s"] for h in second["history"]]
        assert elapsed == sorted(elapsed)
        assert second["best_accuracy"] >= first["best_accuracy"]

    def test_resumed_shuffle_matches_uninterrupted_run(self, tmp_path):
        """Batch order is keyed on (seed, epoch), not on a sequential rng:
        epoch 1 of a run resumed from the epoch-0 checkpoint consumes the
        same batches — and hence produces the same metrics — as epoch 1 of
        an uninterrupted run.  (A shared rng would replay epoch 0's order
        after the restart.)  Preemption is simulated by pruning the run's
        checkpoint dir back to the epoch-1 state; num_epochs stays the
        same so the cosine-LR total_steps — and the whole program — are
        identical in both runs."""
        import json as _json
        import os
        import shutil

        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts import DartsHyper, run_darts_search

        ds = synthetic_classification(64, 32, (8, 8, 3), 4, seed=1, noise=0.3)
        kw = dict(
            primitives=TINY_PRIMS, num_layers=2, init_channels=4, n_nodes=2,
            batch_size=16, hyper=DartsHyper(unrolled=False), seed=0,
        )
        a = str(tmp_path / "a")
        straight = run_darts_search(ds, num_epochs=2, checkpoint_dir=a, **kw)

        # rewind the dir to "preempted after epoch 1": drop the step-2
        # checkpoint, rewrite the sidecar to the epoch-1 state
        b = str(tmp_path / "b")
        shutil.copytree(a, b)
        shutil.rmtree(os.path.join(b, "step_00000002"))
        row0 = straight["history"][0]
        with open(os.path.join(b, "search_meta.json"), "w") as f:
            _json.dump({
                "epochs_completed": 1,
                "best_accuracy": row0["best_accuracy"],
                "history": [row0],
                "elapsed_s": row0["elapsed_s"],
            }, f)

        resumed = run_darts_search(ds, num_epochs=2, checkpoint_dir=b, **kw)
        assert [h["epoch"] for h in resumed["history"]] == [0, 1]
        s1, r1 = straight["history"][1], resumed["history"][1]
        assert r1["train_loss"] == pytest.approx(s1["train_loss"], rel=1e-5)
        assert r1["val_accuracy"] == pytest.approx(s1["val_accuracy"], rel=1e-5)


class TestDartsService:
    def test_single_trial_contract(self):
        spec = nas_spec("darts", settings={"num_epochs": "3"})
        s = make_suggester(spec)
        exp = Experiment(spec=spec)
        proposals = s.get_suggestions(exp, 5)
        assert len(proposals) == 1  # exactly one trial, reference parity
        params = proposals[0].as_dict()
        merged = json.loads(params["algorithm-settings"])
        assert merged["num_epochs"] == "3"  # user override wins
        assert merged["w_lr"] == 0.025  # default preserved
        prims = json.loads(params["search-space"])
        assert prims == [
            "separable_convolution_3x3",
            "separable_convolution_5x5",
            "skip_connection",
        ]
        assert params["num-layers"] == "4"
        complete_trial(exp, proposals[0], 0.9)
        with pytest.raises(SearchExhausted):
            s.get_suggestions(exp, 1)

    def test_cr_can_spell_out_the_default_primitives(self):
        """``none`` is bare like ``skip_connection`` (the reference's trial
        appends it itself), so a CR reaches the flagship's eight
        DEFAULT_PRIMITIVES — what chip_smoke.py --darts sends."""
        from katib_tpu.core.types import (
            FeasibleSpace,
            NasConfig,
            NasOperation,
            ParameterSpec,
            ParameterType,
        )
        from katib_tpu.nas.darts.ops import DEFAULT_PRIMITIVES
        from katib_tpu.nas.darts.service import search_space_from_nas_config

        def op(kind, sizes=None):
            params = (
                [ParameterSpec("filter_size", ParameterType.CATEGORICAL,
                               FeasibleSpace(list=tuple(sizes)))]
                if sizes
                else []
            )
            return NasOperation(operation_type=kind, parameters=params)

        spec = nas_spec("darts")
        cfg = NasConfig(
            graph_config=spec.nas_config.graph_config,
            operations=[
                op("none"), op("max_pooling", ["3"]), op("avg_pooling", ["3"]),
                op("skip_connection"),
                op("separable_convolution", ["3", "5"]),
                op("dilated_convolution", ["3", "5"]),
            ],
        )
        assert tuple(search_space_from_nas_config(cfg)) == DEFAULT_PRIMITIVES

    def test_settings_validation(self):
        with pytest.raises(SuggesterError, match="num_epochs"):
            make_suggester(nas_spec("darts", settings={"num_epochs": "-3"}))
        with pytest.raises(SuggesterError, match="w_lr"):
            make_suggester(nas_spec("darts", settings={"w_lr": "abc"}))


class TestEnasController:
    def test_sample_shapes_and_determinism(self):
        from katib_tpu.nas.enas.controller import (
            ControllerConfig,
            init_controller,
            sample_arc,
        )

        cfg = ControllerConfig(num_layers=5, num_operations=6)
        params = init_controller(cfg, jax.random.PRNGKey(0))
        arc, stats = sample_arc(params, cfg, jax.random.PRNGKey(1))
        assert arc.ops.shape == (5,)
        assert arc.skips.shape == (5, 5)
        # lower-triangular: no skip from future layers
        sk = np.asarray(arc.skips)
        assert np.all(np.triu(sk) == 0)
        arc2, _ = sample_arc(params, cfg, jax.random.PRNGKey(1))
        assert np.array_equal(np.asarray(arc.ops), np.asarray(arc2.ops))

    def test_reinforce_learns_preference(self):
        from katib_tpu.nas.enas.controller import ControllerConfig, make_reinforce

        cfg = ControllerConfig(
            num_layers=3,
            num_operations=3,
            learning_rate=5e-3,
            entropy_weight=None,
            skip_weight=None,
            baseline_decay=0.9,
        )
        init, train_step, sample = make_reinforce(cfg)
        state = init(jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        for _ in range(200):
            key, k = jax.random.split(key)
            arc, _ = sample(state.params, k)
            reward = float(np.mean(np.asarray(arc.ops) == 1))
            state, _ = train_step(state, arc, np.float32(reward))
        counts = np.zeros(3)
        for _ in range(40):
            key, k = jax.random.split(key)
            arc, _ = sample(state.params, k)
            for o in np.asarray(arc.ops):
                counts[o] += 1
        assert counts[1] == counts.max()

    def test_arc_json_roundtrip(self):
        from katib_tpu.nas.enas.controller import (
            Arc,
            arc_from_json,
            arc_to_json,
        )
        import jax.numpy as jnp

        arc = Arc(
            ops=jnp.array([2, 0, 1], jnp.int32),
            skips=jnp.array(
                [[0, 0, 0], [1, 0, 0], [0, 1, 0]], jnp.int32
            ),
        )
        data = arc_to_json(arc)
        assert data == [[2], [0, 1], [1, 0, 1]]
        back = arc_from_json(data, 3)
        assert np.array_equal(np.asarray(back.ops), np.asarray(arc.ops))
        assert np.array_equal(np.asarray(back.skips), np.asarray(arc.skips))


class TestEnasChild:
    def test_child_builds_and_runs(self):
        from katib_tpu.nas.enas.child import child_from_arc
        from katib_tpu.nas.enas.controller import arc_from_json

        arc = arc_from_json([[0], [1, 1], [2, 0, 1], [3, 1, 1, 0]], 4)
        model = child_from_arc(arc, channels=8, num_classes=4)
        x = np.zeros((2, 16, 16, 3), np.float32)
        params = model.init(jax.random.PRNGKey(0), x)
        logits = model.apply(params, x)
        assert logits.shape == (2, 4)


class TestEnasService:
    def test_round_lifecycle(self):
        spec = nas_spec(
            "enas",
            settings={"controller_train_steps": "2", "controller_hidden_size": "16"},
        )
        s = make_suggester(spec)
        exp = Experiment(spec=spec)
        round0 = s.get_suggestions(exp, 3)
        assert len(round0) == 3
        for p in round0:
            params = p.as_dict()
            arch = json.loads(params["architecture"])
            assert len(arch) == 4
            cfgd = json.loads(params["nn_config"])
            assert cfgd["num_layers"] == 4
            assert p.labels["enas-round"] == "0"
        # round 1 blocked until round 0 completes
        from katib_tpu.core.types import TrialCondition

        t = complete_trial(exp, round0[0], 0.0, condition=TrialCondition.RUNNING)
        t.observation = None
        with pytest.raises(SuggestionsNotReady):
            s.get_suggestions(exp, 3)
        t.condition = TrialCondition.SUCCEEDED
        from katib_tpu.core.types import Metric, Observation

        t.observation = Observation(metrics=[Metric(name="accuracy", value=0.6, latest=0.6)])
        for p in round0[1:]:
            complete_trial(exp, p, 0.5)
        round1 = s.get_suggestions(exp, 2)
        assert all(p.labels["enas-round"] == "1" for p in round1)

    def test_state_dict_roundtrip(self):
        spec = nas_spec("enas", settings={"controller_hidden_size": "16"})
        s = make_suggester(spec)
        exp = Experiment(spec=spec)
        s.get_suggestions(exp, 1)
        data = s.state_dict()
        s2 = make_suggester(spec)
        s2.load_state_dict(data)
        assert s2.round == 1


class TestEnasWeightSharing:
    def test_child_inherits_pool_and_publishes_back(self, tmp_path):
        """weight_sharing: a child overlays the shared pool before training
        (same arc => starts at the previous child's final accuracy) and
        publishes its trained parameters back."""
        import json as _json

        from katib_tpu.nas.enas.trial import enas_trial

        runs: list[list[dict]] = []

        def make_ctx(trial_dir):
            reports: list[dict] = []
            runs.append(reports)

            class Ctx:
                params = {
                    "architecture": _json.dumps([[0], [1, 1]]),
                    "nn_config": _json.dumps({"num_layers": 2}),
                    "dataset": "digits",
                    # enough steps that the first child actually learns —
                    # the assertion needs accuracy daylight between a cold
                    # and a warm start
                    "num_epochs": "5",
                    "batch_size": "64",
                    "channels": "8",
                    "weight_sharing": "true",
                }
                checkpoint_dir = str(trial_dir)
                mesh = None
                _checkpointer = None

                def report(self, **kw):
                    reports.append(kw)
                    return True

            return Ctx()

        exp_dir = tmp_path / "exp"
        enas_trial(make_ctx(exp_dir / "t1"))
        assert (exp_dir / "enas-shared").is_dir()
        first_final = runs[0][-1]["accuracy"]

        enas_trial(make_ctx(exp_dir / "t2"))
        # identical arc -> full overlay -> epoch 0 is at least as good as
        # the first child's final epoch (minus a little SGD wobble)
        assert runs[1][0]["accuracy"] >= first_final - 0.05
        assert runs[1][0]["accuracy"] > runs[0][0]["accuracy"] + 0.05


class TestNativePrefetchSearch:
    def test_search_with_native_loader(self):
        """run_darts_search(native_prefetch=True) streams batches through the
        C++ loader and completes identically-shaped results."""
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts.architect import DartsHyper
        from katib_tpu.nas.darts.search import run_darts_search
        from katib_tpu.native import native_available

        if not native_available():
            pytest.skip("C++ toolchain unavailable")
        ds = synthetic_classification(96, 48, (12, 12, 3), 6, seed=0)
        r = run_darts_search(
            ds, num_layers=2, init_channels=4, n_nodes=2, num_epochs=2,
            batch_size=16, hyper=DartsHyper(unrolled=False),
            native_prefetch=True,
        )
        assert len(r["history"]) == 2
        assert {"epoch", "val_accuracy", "elapsed_s"} <= set(r["history"][0])
        assert r["genotype"].normal and r["genotype"].reduce

    def test_loader_failure_falls_back_to_python(self):
        """A loader that can't start (batch > records) must degrade to the
        Python stream with a warning, not fail the search."""
        import warnings

        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts.architect import DartsHyper
        from katib_tpu.nas.darts.search import run_darts_search
        from katib_tpu.native import native_available

        if not native_available():
            pytest.skip("C++ toolchain unavailable")
        ds = synthetic_classification(24, 16, (8, 8, 3), 4, seed=0)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            r = run_darts_search(
                ds, num_layers=2, init_channels=4, n_nodes=2, num_epochs=1,
                batch_size=16,  # > 12 records per half -> ktl_open rejects
                hyper=DartsHyper(unrolled=False), native_prefetch=True,
            )
        assert any("native prefetch unavailable" in str(w.message) for w in caught)
        assert r["genotype"] is not None


class TestDeviceDataSearch:
    def test_scan_epoch_matches_streamed_path(self):
        """device_data=True (HBM-resident splits, one lax.scan dispatch per
        epoch) must reproduce the streamed path's trajectory exactly: same
        (seed, epoch) permutation draws => same batch composition => same
        history. Guards the docstring claim that the fast path changes the
        transport, not the math."""
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts.architect import DartsHyper
        from katib_tpu.nas.darts.search import run_darts_search

        ds = synthetic_classification(96, 48, (12, 12, 3), 6, seed=0)
        kw = dict(
            num_layers=2, init_channels=4, n_nodes=2, num_epochs=2,
            batch_size=16, hyper=DartsHyper(unrolled=False), seed=3,
        )
        streamed = run_darts_search(ds, device_data=False, **kw)
        scanned = run_darts_search(ds, device_data=True, **kw)
        for a, b in zip(streamed["history"], scanned["history"]):
            assert a["val_accuracy"] == pytest.approx(b["val_accuracy"], abs=1e-5)
            assert a["train_loss"] == pytest.approx(b["train_loss"], rel=1e-4)
        assert streamed["genotype"].normal == scanned["genotype"].normal
        assert streamed["genotype"].reduce == scanned["genotype"].reduce

    def test_eager_escape_hatch_matches_step_loop(self, monkeypatch):
        """KATIB_STEP_LOOP=0 (eager stepping: one dispatch per step of the
        separately jitted single-step program with an on-device gather)
        must reproduce the default windowed step loop's trajectory: the
        escape hatch exists so a pool whose terminal-side compile of the
        window-sized scan program stalls can still run the flagship off
        the cheap single-step compile — it must change the dispatch
        granularity, not the math."""
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts.architect import DartsHyper
        from katib_tpu.nas.darts.search import run_darts_search
        from katib_tpu.utils import observability as obs

        ds = synthetic_classification(96, 48, (12, 12, 3), 6, seed=0)
        kw = dict(
            num_layers=2, init_channels=4, n_nodes=2, num_epochs=2,
            batch_size=16, hyper=DartsHyper(unrolled=True), seed=3,
            # augmentation ON so the eager path's per-step aug_step +
            # fold_in(aug_key, state.step) keying is compared against the
            # scan body's in-jit fold — the claim that the mode changes
            # dispatch granularity, not math, includes the augment branch
            search_augment=True,
        )
        monkeypatch.delenv("KATIB_STEP_LOOP", raising=False)
        looped = run_darts_search(ds, device_data=True, **kw)
        # the default path IS the step loop: 3 steps/epoch, one dispatch
        assert obs.steps_per_dispatch.get(workload="darts") == 3.0
        monkeypatch.setenv("KATIB_STEP_LOOP", "0")
        stepped = run_darts_search(ds, device_data=True, **kw)
        assert obs.steps_per_dispatch.get(workload="darts") == 1.0
        assert obs.step_loop_window.get(workload="darts") == 0.0
        for a, b in zip(looped["history"], stepped["history"]):
            assert a["val_accuracy"] == pytest.approx(b["val_accuracy"], abs=1e-5)
            assert a["train_loss"] == pytest.approx(b["train_loss"], rel=1e-4)
        assert looped["genotype"].normal == stepped["genotype"].normal
        assert looped["genotype"].reduce == stepped["genotype"].reduce

    def test_explicit_step_loop_that_cannot_engage_raises(self, monkeypatch):
        """An EXPLICITLY requested step loop that cannot engage must raise
        StepLoopUnavailable with the reasons, not warn and run the slow
        path (a silent fallback once burned a TPU window on the wrong
        program shape); the same condition under the DEFAULT quietly runs
        the eager path."""
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts.architect import DartsHyper
        from katib_tpu.nas.darts.search import (
            StepLoopUnavailable,
            run_darts_search,
        )

        ds = synthetic_classification(96, 48, (12, 12, 3), 6, seed=0)
        kw = dict(
            num_layers=2, init_channels=4, n_nodes=2, num_epochs=1,
            batch_size=16, hyper=DartsHyper(unrolled=False), seed=3,
        )
        monkeypatch.setenv("KATIB_STEP_LOOP", "1")
        with pytest.raises(StepLoopUnavailable, match="KATIB_DEVICE_DATA=0"):
            monkeypatch.setenv("KATIB_DEVICE_DATA", "0")
            run_darts_search(ds, **kw)
        monkeypatch.delenv("KATIB_DEVICE_DATA")
        # split smaller than one batch: explicit -> raise ...
        small = synthetic_classification(24, 16, (8, 8, 3), 4, seed=0)
        with pytest.raises(StepLoopUnavailable, match="smaller than one batch"):
            run_darts_search(small, **{**kw, "batch_size": 16, "num_layers": 2})
        # ... default -> quiet eager fallback (test below covers it too)
        monkeypatch.delenv("KATIB_STEP_LOOP")
        r = run_darts_search(small, **{**kw, "batch_size": 16})
        assert r["genotype"] is not None

    def test_split_smaller_than_batch_falls_back(self):
        """A split smaller than one batch has zero full batches; the scan
        path must stand down (not crash on a short permutation reshape)."""
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts.architect import DartsHyper
        from katib_tpu.nas.darts.search import run_darts_search

        ds = synthetic_classification(24, 16, (8, 8, 3), 4, seed=0)
        r = run_darts_search(
            ds, num_layers=2, init_channels=4, n_nodes=2, num_epochs=1,
            batch_size=16, hyper=DartsHyper(unrolled=False), device_data=True,
        )
        assert r["genotype"] is not None

    def test_train_classifier_scan_matches_streamed(self):
        """The shared supervised loop (MNIST trials, DARTS augment, ENAS
        children) gets the same device-resident scan path; trajectories
        must match the streamed path exactly."""
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.models.mnist import MLP, train_classifier

        ds = synthetic_classification(128, 64, (6, 6, 1), 4, seed=1)
        hist_a, hist_b = [], []
        kw = dict(lr=0.1, epochs=3, batch_size=32, seed=7)
        a = train_classifier(
            MLP(units=16), ds,
            report=lambda **m: hist_a.append(m), device_data=False, **kw,
        )
        b = train_classifier(
            MLP(units=16), ds,
            report=lambda **m: hist_b.append(m), device_data=True, **kw,
        )
        assert a == pytest.approx(b, abs=1e-5)
        for ma, mb in zip(hist_a, hist_b):
            assert ma["accuracy"] == pytest.approx(mb["accuracy"], abs=1e-5)
            assert ma["loss"] == pytest.approx(mb["loss"], rel=1e-4)

    def test_hp_sweep_compiles_once(self):
        """Different (lr, momentum) assignments must share one traced step:
        hyperparameters are runtime state (inject_hyperparams), not trace
        constants — the difference between N compiles and 1 for an N-trial
        sweep on a chip where a compile costs minutes."""
        from katib_tpu.models import mnist as M
        from katib_tpu.models.data import synthetic_classification

        ds = synthetic_classification(128, 64, (6, 6, 1), 4, seed=1)
        M._STEP_CACHE.clear()
        accs = [
            M.train_classifier(
                M.MLP(units=16), ds, lr=lr, momentum=0.9, epochs=3,
                batch_size=32, optimizer="momentum", seed=7,
            )
            for lr in (0.1, 0.0001)
        ]
        # the hyperparameters really flowed in: wildly different lr must
        # produce different trajectories (placeholder-0.0 would make them
        # identical and learn nothing)
        assert accs[0] != accs[1]
        # the sane-lr arm learned (4-class chance is 0.25; the injected
        # optimizer is bit-identical to the plain one — asserted elsewhere)
        assert accs[0] > 0.4
        assert len(M._STEP_CACHE) == 1  # both trials hit one cache entry
        _tx, step, _ev, scan_epoch, _aug = next(iter(M._STEP_CACHE.values()))
        traced = scan_epoch._cache_size() + step._cache_size()
        assert traced == 1, f"expected exactly one trace total, got {traced}"

    def test_remat_policy_matches_no_remat(self):
        """Rematerialisation must never change the math: a dots-policy
        remat search reproduces the no-remat trajectory exactly."""
        from katib_tpu.models.data import synthetic_classification
        from katib_tpu.nas.darts.architect import DartsHyper
        from katib_tpu.nas.darts.search import run_darts_search

        ds = synthetic_classification(96, 48, (12, 12, 3), 6, seed=0)
        kw = dict(
            num_layers=2, init_channels=4, n_nodes=2, num_epochs=1,
            batch_size=16, hyper=DartsHyper(unrolled=True), seed=3,
        )
        plain = run_darts_search(ds, remat=False, **kw)
        dots = run_darts_search(ds, remat=True, remat_policy="dots", **kw)
        assert plain["history"][0]["val_accuracy"] == pytest.approx(
            dots["history"][0]["val_accuracy"], abs=1e-4
        )
        # recompute legally reorders float ops, so compare the learned
        # alphas numerically (1 epoch leaves them near their 1e-3 init —
        # exact genotype argmax over near-ties would be flaky)
        for a, b in zip(plain["alphas"], dots["alphas"]):
            assert float(abs(np.asarray(a) - np.asarray(b)).max()) < 5e-3

    def test_unknown_remat_policy_rejected(self):
        import jax
        import jax.numpy as jnp

        from katib_tpu.nas.darts.model import DartsNetwork, init_alphas

        net = DartsNetwork(num_layers=2, init_channels=4, n_nodes=2,
                           remat_policy="bogus")
        alphas = init_alphas(2, 8, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="unknown remat_policy"):
            net.init(jax.random.PRNGKey(1), jnp.zeros((1, 8, 8, 3)), alphas)
