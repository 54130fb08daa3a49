"""SPMD trial-parallel cohorts: the vmap'd member axis sharded over the
mesh's reserved ``trial`` axis.

Acceptance properties (ISSUE: perf_opt / trial-parallel cohorts):
- an 8-member cohort sharded over the 8-virtual-device CPU mesh produces
  per-member states and metric rows that match the single-device vmap
  cohort to float32 ULP precision (per-member compute is independent and
  the partitioner inserts no cross-member collectives, but the per-device
  K/D-member program may vectorize its reductions differently from the
  K-member one — the dry-run gate's tolerance, __graft_entry__.py),
  and the stacked state's sharding actually spans the trial axis,
- K=5 on 8 devices pads with inert ghost members whose metric rows are
  dropped before the ObservationStore,
- the sharded cohort still compiles exactly ONE program,
- the trial axis counts as a non-data axis for the grouped-conv
  safe-gradient selection, and serial paths drop a trial-axis-only mesh,
- the orchestrator derives the cohort width from the trial-axis size and
  rejects trial-axis meshes for black-box experiments.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.core.types import (
    COHORT_KEY_LABEL,
    ObjectiveSpec,
    ObjectiveType,
    ParameterAssignment,
    TrialAssignmentSet,
    TrialCondition,
)
from katib_tpu.orchestrator.orchestrator import Orchestrator
from katib_tpu.parallel.mesh import (
    TRIAL_AXIS,
    make_mesh,
    needs_safe_conv,
    padded_cohort_size,
    serial_mesh,
    shard_members,
    trial_axis_size,
)
from katib_tpu.parallel.train import (
    cohort_trace_counter,
    make_cohort_eval_step,
    make_cohort_train_step,
    stack_pytrees,
)
from katib_tpu.runner.cohort import CohortContext, attach_cohort_fn, run_cohort
from katib_tpu.store.base import MemoryObservationStore
from tests.helpers import make_spec
from tests.test_cohort import (
    OBJECTIVE,
    _make_trial,
    _toy_batch,
    _toy_loss,
    _toy_state,
    _toy_tx,
)

OBJECTIVE_ACC = ObjectiveSpec(
    type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
)


# sharded vs single-device: ULP-tight, the tolerance of the dry-run gate
# (__graft_entry__.dryrun_multichip) — a wrong partitioning is orders of
# magnitude off
GATE_TOLERANCE = dict(rtol=1e-6, atol=1e-7)


def _trial_mesh(n=8):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip("needs the 8-device virtual mesh")
    return make_mesh({TRIAL_AXIS: n}, devices=devs[:n])


class TestShardedEquivalence:
    def test_sharded_matches_single_device_bitwise(self):
        """K=8 over a {trial: 8} mesh == single-device vmap, to an ULP."""
        mesh = _trial_mesh()
        dim, steps = 4, 10
        lrs = [0.01 * (i + 1) for i in range(8)]
        batch = _toy_batch(dim)

        ref_tx = _toy_tx()
        ref_step = make_cohort_train_step(_toy_loss, ref_tx, donate=False)
        ref_states = stack_pytrees([_toy_state(ref_tx, lr, dim) for lr in lrs])
        for _ in range(steps):
            ref_states, ref_metrics = ref_step(ref_states, batch)

        sh_tx = _toy_tx()
        sh_step = make_cohort_train_step(_toy_loss, sh_tx, donate=False, mesh=mesh)
        sh_states = shard_members(
            stack_pytrees([_toy_state(sh_tx, lr, dim) for lr in lrs]), mesh
        )
        # the input placement really spans the trial axis...
        assert sh_states.params["w"].sharding.spec[0] == TRIAL_AXIS
        for _ in range(steps):
            sh_states, sh_metrics = sh_step(sh_states, batch)
        # ...and the step's out_shardings keep it there
        spec = sh_states.params["w"].sharding.spec
        assert len(spec) >= 1 and spec[0] == TRIAL_AXIS, spec
        assert len(sh_states.params["w"].sharding.device_set) == 8

        for leaf_ref, leaf_sh in zip(
            jax.tree_util.tree_leaves(ref_states),
            jax.tree_util.tree_leaves(sh_states),
        ):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(leaf_sh)),
                np.asarray(jax.device_get(leaf_ref)),
                **GATE_TOLERANCE,
            )
        np.testing.assert_allclose(
            np.asarray(jax.device_get(sh_metrics["loss"])),
            np.asarray(jax.device_get(ref_metrics["loss"])),
            **GATE_TOLERANCE,
        )

    def test_sharded_eval_matches_single_device(self):
        mesh = _trial_mesh()
        dim = 4
        tx = _toy_tx()
        states = stack_pytrees(
            [_toy_state(tx, 0.01, dim, seed=i) for i in range(8)]
        )
        x, y = _toy_batch(dim)

        def metric_fn(params, batch):
            return {"loss": _toy_loss(params, batch)}

        ref = make_cohort_eval_step(metric_fn)(states.params, (x, y))
        sh_params = shard_members(states.params, mesh)
        sh = make_cohort_eval_step(metric_fn, mesh=mesh)(sh_params, (x, y))
        assert sh["loss"].sharding.spec[0] == TRIAL_AXIS
        np.testing.assert_allclose(
            np.asarray(jax.device_get(sh["loss"])),
            np.asarray(jax.device_get(ref["loss"])),
            **GATE_TOLERANCE,
        )

    def test_sharded_single_trace(self):
        """The sharded K=8 cohort still compiles exactly ONE program."""
        mesh = _trial_mesh()
        dim = 23  # unique shape: no other test shares this executable
        tx = _toy_tx()
        step = make_cohort_train_step(_toy_loss, tx, donate=False, mesh=mesh)
        states = shard_members(
            stack_pytrees([_toy_state(tx, 0.01 * (i + 1), dim) for i in range(8)]),
            mesh,
        )
        batch = _toy_batch(dim)
        before = cohort_trace_counter.count
        for _ in range(6):
            states, _ = step(states, batch)
        assert cohort_trace_counter.count - before == 1

    def test_nan_member_freeze_survives_sharding(self):
        """The per-member non-finite freeze works across device boundaries."""
        mesh = _trial_mesh()
        dim = 4
        lrs = [0.01, 0.02, float("inf"), 0.03, 0.04, 0.05, 0.06, 0.07]
        tx = _toy_tx()
        step = make_cohort_train_step(_toy_loss, tx, donate=False, mesh=mesh)
        states = shard_members(
            stack_pytrees([_toy_state(tx, lr, dim) for lr in lrs]), mesh
        )
        batch = _toy_batch(dim)
        for _ in range(5):
            states, metrics = step(states, batch)
        loss = np.asarray(jax.device_get(metrics["loss"]))
        assert not np.isfinite(loss[2])
        healthy = [i for i in range(8) if i != 2]
        assert np.isfinite(loss[healthy]).all()


class TestGhostPadding:
    def _ctx(self, k, mesh):
        trials = [_make_trial(f"g{i}", lr=0.01 * (i + 1)) for i in range(k)]
        store = MemoryObservationStore()
        return CohortContext(trials, store, OBJECTIVE, mesh=mesh), store, trials

    def test_padded_size_and_stacked(self):
        mesh = _trial_mesh()
        ctx, _, _ = self._ctx(5, mesh)
        assert ctx.trial_devices == 8
        assert ctx.padded_size == 8
        lrs = np.asarray(ctx.stacked("lr"))
        assert lrs.shape == (8,)
        np.testing.assert_allclose(lrs[:5], [0.01, 0.02, 0.03, 0.04, 0.05])
        # ghost rows ride member 0's hyperparameters: inert but finite
        np.testing.assert_allclose(lrs[5:], [0.01] * 3)

    def test_report_drops_ghost_rows(self):
        mesh = _trial_mesh()
        ctx, store, trials = self._ctx(5, mesh)
        ctx.report(step=0, loss=list(np.arange(8.0)))
        for i, t in enumerate(trials):
            obs_i = store.observation_for(t.name, OBJECTIVE)
            assert obs_i is not None
            assert float(obs_i.metrics[0].value) == float(i)
        # ghost rows never became trials, so nothing else reached the store
        assert store.observation_for("g5", OBJECTIVE) is None

    def test_padded_cohort_size_helper(self):
        mesh = _trial_mesh()
        assert padded_cohort_size(5, mesh) == 8
        assert padded_cohort_size(8, mesh) == 8
        assert padded_cohort_size(9, mesh) == 16
        assert padded_cohort_size(5, None) == 5

    def test_no_mesh_context_is_identity(self):
        ctx, _, _ = self._ctx(5, None)
        assert ctx.trial_devices == 1
        assert ctx.padded_size == 5
        assert ctx.cohort_mesh is None
        tree = {"a": jnp.ones((5, 2))}
        assert ctx.place_members(tree) is tree


class TestMeshHelpers:
    def test_trial_axis_counts_for_safe_conv(self):
        """The trial axis is a non-data axis: grouped-conv filter gradients
        must use the partitioner-safe formulation on it."""
        mesh = _trial_mesh()
        assert needs_safe_conv(mesh) is True
        assert trial_axis_size(mesh) == 8

    def test_serial_mesh_drops_trial_only(self):
        mesh = _trial_mesh()
        assert serial_mesh(mesh) is None
        assert serial_mesh(None) is None
        # a mesh that also carries tensor axes is kept
        devs = jax.devices()[:8]
        mixed = make_mesh({"data": 4, TRIAL_AXIS: 2}, devices=devs)
        assert serial_mesh(mixed) is mixed


class TestOrchestratorTrialMesh:
    def test_width_derived_from_trial_axis(self, tmp_path):
        mesh = _trial_mesh()
        orch = Orchestrator(workdir=str(tmp_path))
        train_fn = attach_cohort_fn(lambda ctx: None, lambda cctx: None)
        # no cohort_width, no cohort_key: the trial mesh alone must group
        spec = make_spec(train_fn=train_fn)
        props = [
            TrialAssignmentSet(assignments=[ParameterAssignment("x", float(i))])
            for i in range(10)
        ]
        groups = orch._group_proposals(spec, props, mesh)
        assert sorted(len(g) for g in groups) == [2, 8]
        for g in groups:
            for p in g:
                assert p.labels.get(COHORT_KEY_LABEL) == "trial-mesh"

    def test_explicit_width_wins_when_larger(self, tmp_path):
        mesh = _trial_mesh()
        orch = Orchestrator(workdir=str(tmp_path))
        train_fn = attach_cohort_fn(lambda ctx: None, lambda cctx: None)
        spec = make_spec(train_fn=train_fn, cohort_width=16, cohort_key="wide")
        props = [
            TrialAssignmentSet(assignments=[ParameterAssignment("x", float(i))])
            for i in range(16)
        ]
        groups = orch._group_proposals(spec, props, mesh)
        assert sorted(len(g) for g in groups) == [16]

    def test_validate_mesh_rejects_blackbox(self, tmp_path):
        mesh = _trial_mesh()
        orch = Orchestrator(workdir=str(tmp_path))
        spec = make_spec(train_fn=None, command=["echo", "hi"])
        with pytest.raises(ValueError, match="trial axis"):
            orch._validate_mesh(spec, mesh)
        # white-box specs pass, and data-only meshes are always fine
        orch._validate_mesh(make_spec(), mesh)
        orch._validate_mesh(spec, make_mesh({"data": 1}, devices=jax.devices()[:1]))


class TestMnistShardedCohort:
    STRUCT = dict(
        units=14, num_layers=1, epochs=1, batch_size=64,
        n_train=256, n_test=128, optimizer="momentum",
    )

    def _trial(self, name, lr):
        from katib_tpu.models.mnist import mnist_trial

        return _make_trial(
            name, spec_kw={"train_fn": mnist_trial}, lr=lr, **self.STRUCT
        )

    def test_mnist_cohort_k5_on_trial_mesh(self):
        """End-to-end: a K=5 MNIST cohort on the {trial: 8} mesh pads with
        ghosts, trains one program, settles 5 real members, and records the
        device span on the gauge."""
        mesh = _trial_mesh()
        from katib_tpu.utils import observability as obs

        lrs = [0.02, 0.04, 0.06, 0.08, 0.1]
        store = MemoryObservationStore()
        trials = [self._trial(f"sm{i}", lr) for i, lr in enumerate(lrs)]
        results = run_cohort(trials, store, OBJECTIVE_ACC, mesh=mesh)
        assert all(
            r.condition is TrialCondition.SUCCEEDED for r in results.values()
        ), {n: r.message for n, r in results.items()}
        for t in trials:
            o = store.observation_for(t.name, OBJECTIVE_ACC)
            assert o is not None
            acc = float([m for m in o.metrics if m.name == "accuracy"][0].value)
            assert 0.0 <= acc <= 1.0
        assert obs.cohort_devices.get() == 8.0

    def test_mnist_sharded_matches_single_device(self):
        """Same seeds, same batch schedule: the sharded MNIST cohort's
        per-member metric rows match the single-device vmap cohort."""
        mesh = _trial_mesh()
        lrs = [0.02, 0.05, 0.08, 0.11, 0.03, 0.06, 0.09, 0.12]
        ref_store = MemoryObservationStore()
        ref = run_cohort(
            [self._trial(f"rf{i}", lr) for i, lr in enumerate(lrs)],
            ref_store, OBJECTIVE_ACC,
        )
        sh_store = MemoryObservationStore()
        sh = run_cohort(
            [self._trial(f"sh{i}", lr) for i, lr in enumerate(lrs)],
            sh_store, OBJECTIVE_ACC, mesh=mesh,
        )
        assert all(r.condition is TrialCondition.SUCCEEDED for r in ref.values())
        assert all(
            r.condition is TrialCondition.SUCCEEDED for r in sh.values()
        ), {n: r.message for n, r in sh.items()}
        for i in range(len(lrs)):
            r = ref_store.observation_for(f"rf{i}", OBJECTIVE_ACC)
            s = sh_store.observation_for(f"sh{i}", OBJECTIVE_ACC)
            rv = float([m for m in r.metrics if m.name == "accuracy"][0].value)
            sv = float([m for m in s.metrics if m.name == "accuracy"][0].value)
            assert rv == sv, (i, rv, sv)
