"""Tunable MNIST models + the standard white-box trial function.

Parity target: the reference's ``pytorch-mnist`` trial image
(``examples/v1beta1/trial-images/pytorch-mnist/mnist.py``) — an MLP/CNN with
tunable lr/momentum that prints accuracy lines for the sidecar.  Here the
trainer is a JAX function on a device mesh reporting metrics through the
trial context; hyperparameters arrive typed.

Tunable parameters understood by ``mnist_trial``: lr, momentum, units,
num_layers, batch_size, epochs, optimizer(sgd|adam|momentum), arch(mlp|cnn).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from katib_tpu import costmodel
from katib_tpu.models.data import Dataset, batches, load_mnist
from katib_tpu.parallel.mesh import shard_batch
from katib_tpu.parallel.train import (
    TrainState,
    accuracy,
    cross_entropy_loss,
    make_cohort_eval_step,
    make_cohort_train_step,
    make_eval_step,
    make_train_step,
    stack_pytrees,
)


class MLP(nn.Module):
    units: int = 64
    num_layers: int = 2
    num_classes: int = 10
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1)).astype(self.dtype)
        for _ in range(self.num_layers):
            x = nn.Dense(self.units, dtype=self.dtype)(x)
            x = nn.relu(x)
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


class SmallCNN(nn.Module):
    channels: int = 32
    num_classes: int = 10
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        x = nn.Conv(self.channels, (3, 3), dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(self.channels * 2, (3, 3), dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.channels * 4, dtype=self.dtype)(x)
        x = nn.relu(x)
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


def make_optimizer(name: str, lr: float, momentum: float = 0.9):
    if name == "adam":
        return optax.adam(lr)
    if name == "momentum":
        return optax.sgd(lr, momentum=momentum)
    return optax.sgd(lr)


def _family_optimizer(name: str) -> optax.GradientTransformation:
    """Optimizer with lr/momentum as RUNTIME state (inject_hyperparams).

    Baking hyperparameters into the trace as Python floats means every
    trial of an HP sweep compiles its own executable — on a TPU where the
    full compile is minutes, a 100-trial sweep would spend hours in XLA
    for identical programs.  Injected hyperparameters live in
    ``opt_state.hyperparams``, so one compiled step serves every
    (lr, momentum) assignment; the placeholder 0.0 values are overwritten
    per trial by ``_set_hyperparams``.
    """
    if name == "adam":
        return optax.inject_hyperparams(optax.adam)(learning_rate=0.0)
    if name == "momentum":
        return optax.inject_hyperparams(optax.sgd)(learning_rate=0.0, momentum=0.0)
    return optax.inject_hyperparams(optax.sgd)(learning_rate=0.0)


def _set_hyperparams(opt_state, lr: float, momentum: float):
    """Write the trial's actual hyperparameters into an inject_hyperparams
    state (only keys the family declares are set)."""
    hp = dict(opt_state.hyperparams)
    hp["learning_rate"] = jnp.asarray(lr, jnp.float32)
    if "momentum" in hp:
        hp["momentum"] = jnp.asarray(momentum, jnp.float32)
    return opt_state._replace(hyperparams=hp)


# (model, optimizer family, mesh) -> (tx, step, evaluate, scan_epoch):
# concurrent trials of an HP sweep share ONE set of jit objects, so the
# executable compiles once per architecture instead of once per trial.
# flax Modules hash by field values; unhashable configs (e.g. a genotype
# carrying lists) fall back to uncached per-call builds.  LRU-bounded:
# an ENAS search trains hundreds of DISTINCT child architectures through
# this loop, and an unbounded map would pin every compiled executable for
# the life of the process.
import threading  # noqa: E402  (module-scope cache)
from collections import OrderedDict  # noqa: E402

_STEP_CACHE: OrderedDict = OrderedDict()
_STEP_CACHE_MAX = 32
_STEP_CACHE_LOCK = threading.Lock()


def _build_steps(model: nn.Module, optimizer: str, mesh, augment_fn=None):
    def loss_fn(params, batch):
        x, y = batch
        return cross_entropy_loss(model.apply(params, x), y)

    def metric_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return {
            "accuracy": accuracy(logits, y),
            "loss": cross_entropy_loss(logits, y),
        }

    tx = _family_optimizer(optimizer)
    step = make_train_step(loss_fn, tx, mesh)
    evaluate = make_eval_step(metric_fn, mesh)

    # train-time augmentation runs INSIDE the scan body (device-side, one
    # fold of the step counter per batch) so the host->device path the
    # device_data scan removed never comes back for augmented runs
    def _epoch(state, x, y, ix, akey):
        def body(s, i):
            xb = x[i]
            if augment_fn is not None:
                xb = augment_fn(jax.random.fold_in(akey, s.step), xb)
            s, m = step(s, (xb, y[i]))
            return s, m["loss"]

        return jax.lax.scan(body, state, ix)

    scan_epoch = jax.jit(_epoch, donate_argnums=(0,))
    # jitted per-batch augment for the streamed path, built (and cached)
    # alongside the steps so concurrent trials share one trace
    aug_step = (
        jax.jit(lambda k, xb: augment_fn(k, xb)) if augment_fn is not None else None
    )
    return tx, step, evaluate, scan_epoch, aug_step


def _model_dtype(model) -> str:
    """Compute-dtype key for the MFU denominator (flax modules here cast
    to their ``dtype`` field internally; f32 inputs still run bf16 math)."""
    return "bf16" if getattr(model, "dtype", None) == jnp.bfloat16 else "f32"


def _mesh_key(mesh):
    """Stable identity for a mesh: id() can be recycled after GC, handing a
    new mesh another mesh's cached steps (stale shardings)."""
    if mesh is None:
        return None
    return (
        tuple(getattr(d, "id", repr(d)) for d in mesh.devices.flat),
        tuple(mesh.axis_names),
        mesh.devices.shape,
    )


def _steps_for(model: nn.Module, optimizer: str, mesh, augment_fn=None):
    try:
        # augment_fn keys by identity: pass a module-level function (e.g.
        # augmentation.cifar_train_augment), not a fresh lambda per call,
        # or every trial recompiles
        key = (hash(model), model, optimizer, _mesh_key(mesh), augment_fn)
    except TypeError:
        return _build_steps(model, optimizer, mesh, augment_fn)
    with _STEP_CACHE_LOCK:
        built = _STEP_CACHE.get(key)
    if built is None:
        # build OUTSIDE the lock (tracing is slow); a concurrent duplicate
        # build is harmless — setdefault keeps exactly one
        fresh = _build_steps(model, optimizer, mesh, augment_fn)
        with _STEP_CACHE_LOCK:
            built = _STEP_CACHE.setdefault(key, fresh)
    with _STEP_CACHE_LOCK:
        if key in _STEP_CACHE:
            _STEP_CACHE.move_to_end(key)
        while len(_STEP_CACHE) > _STEP_CACHE_MAX:
            _STEP_CACHE.popitem(last=False)
    return built


def train_classifier(
    model: nn.Module,
    dataset: Dataset,
    *,
    lr: float,
    epochs: int,
    batch_size: int,
    optimizer: str = "momentum",
    momentum: float = 0.9,
    mesh=None,
    seed: int = 0,
    report=None,
    eval_batch: int = 1024,
    init_transform=None,
    on_finish=None,
    device_data: bool | None = None,
    augment_fn=None,
) -> float:
    """Train and return final test accuracy; calls ``report(epoch, acc, loss)``
    per epoch when given (the trial metrics hook).

    ``init_transform(params) -> params`` warm-starts the freshly initialized
    parameters (ENAS weight sharing); ``on_finish(params)`` receives the
    final parameters (publishing back to a shared pool).

    ``device_data`` (default on for single-device runs, ``KATIB_DEVICE_DATA``
    overrides): train split lives in device memory for the whole run and
    each epoch is ONE jitted ``lax.scan`` with on-device batch gather from
    permutation indices — same transport-only optimization, same
    batch-composition guarantee as ``nas/darts/search.py``.

    ``augment_fn(key, x) -> x``: jittable train-time batch augmentation
    (e.g. ``models.augmentation.cifar_train_augment``), applied inside the
    epoch scan (device-side) or per streamed batch; keyed off the run
    seed + global step, so augmented runs stay reproducible.  Pass a
    module-level function — identity keys the jit-step cache."""
    rng = np.random.default_rng(seed)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, *dataset.input_shape), jnp.float32)
    )
    if init_transform is not None:
        # warm starts (e.g. ENAS weight sharing overlays the shared pool)
        params = init_transform(params)
    tx, step, evaluate, cached_scan_epoch, aug_step = _steps_for(
        model, optimizer, mesh, augment_fn
    )
    # augmentation randomness: independent of the shuffle stream, folded
    # with the GLOBAL step in both execution paths (scan folds
    # TrainState.step in-body; the streamed loop mirrors it with a running
    # counter), so the same seed draws the same augmentations regardless
    # of device_data mode
    aug_key = jax.random.PRNGKey(seed + 0x5EED)
    state = TrainState.create(params, tx)
    # lr/momentum are runtime values inside opt_state (compile-once sweeps)
    state = state._replace(
        opt_state=_set_hyperparams(state.opt_state, lr, momentum)
    )
    if mesh is not None:
        from katib_tpu.parallel.mesh import replicate

        state = replicate(state, mesh)

    if device_data is None:
        import os

        from katib_tpu.utils.booleans import parse_bool

        env = os.environ.get("KATIB_DEVICE_DATA")
        device_data = mesh is None if env is None else parse_bool(env)
    scan_steps = len(dataset.x_train) // batch_size
    scan_epoch = None
    if device_data and mesh is None and scan_steps >= 1:
        # split lives in HBM across the run; arrays are explicit arguments
        # (closure-captured constants would be re-embedded per trace), and
        # the jitted epoch comes from the shared cache so concurrent sweep
        # trials reuse one executable
        xd = jax.device_put(dataset.x_train)
        yd = jax.device_put(dataset.y_train)
        scan_epoch = cached_scan_epoch

    # eval prefix is constant across epochs — build (and place) it once;
    # under a mesh it truncates to a multiple of the data-axis size
    # (shard_batch's divisibility contract — 397 test rows on an 8-way
    # axis would otherwise crash after the training epochs already ran)
    ne = min(eval_batch, len(dataset.x_test))
    xe = dataset.x_test[:ne]
    ye = dataset.y_test[:ne]
    if mesh is not None:
        from katib_tpu.parallel.mesh import DATA_AXIS, local_mesh_size

        d = local_mesh_size(mesh, DATA_AXIS)
        if ne >= d:
            xe, ye = xe[: (ne // d) * d], ye[: (ne // d) * d]
        elif ne > 0:  # tiny split: tile up to one row per device
            reps = -(-d // ne)
            xe = np.tile(xe, (reps,) + (1,) * (xe.ndim - 1))[:d]
            ye = np.tile(ye, reps)[:d]
        # ne == 0 shards fine (0 % d == 0) and evals to NaN
        ebatch = shard_batch((xe, ye), mesh)
    else:
        ebatch = jax.device_put((xe, ye))

    test_acc = 0.0
    global_step = 0  # mirrors TrainState.step for the streamed aug keying
    for epoch in range(epochs):
        if scan_epoch is not None:
            # same rng draw as batches() below: one permutation per epoch
            # from the same sequential generator
            idx = rng.permutation(len(dataset.x_train))[: scan_steps * batch_size]
            idx_d = jnp.asarray(idx.reshape(scan_steps, batch_size), jnp.int32)
            state, losses = scan_epoch(state, xd, yd, idx_d, aug_key)
            n = scan_steps
            train_loss = float(jnp.sum(losses))
            if epoch == 0:
                # one report covers ONE dispatch of this epoch program
                # (steps = the folded scan length); observed after the
                # first dispatch so warm/cold classification timing stays
                # untouched.  Memoized on the step-cache key: concurrent
                # sweep trials sharing the executable trace it once.
                costmodel.observe_program(
                    ("mnist.scan", model, optimizer, _mesh_key(mesh),
                     augment_fn, batch_size, scan_steps),
                    scan_epoch,
                    (state, xd, yd, idx_d, aug_key),
                    program="train_classifier.scan_epoch",
                    steps=scan_steps,
                    per_report=1,
                    dtype=_model_dtype(model),
                )
        else:
            # device futures, one transfer per epoch — per-step float()
            # would host-sync every step and serialize async dispatch (see
            # nas/darts/search.py)
            step_losses = []
            for xb, yb in batches(dataset.x_train, dataset.y_train, batch_size, rng):
                batch = (xb, yb) if mesh is None else shard_batch((xb, yb), mesh)
                if aug_step is not None:
                    # augment AFTER sharding (elementwise + per-sample
                    # gathers partition cleanly along the batch axis — no
                    # default-device round-trip), keyed off the same
                    # global step the scan path folds
                    batch = (
                        aug_step(
                            jax.random.fold_in(aug_key, global_step), batch[0]
                        ),
                        batch[1],
                    )
                state, metrics = step(state, batch)
                global_step += 1
                step_losses.append(metrics["loss"])
            n = len(step_losses)
            train_loss = float(np.sum(jax.device_get(step_losses))) if n else 0.0
            if epoch == 0 and n:
                # streamed path: one report covers n single-step dispatches
                costmodel.observe_program(
                    ("mnist.step", model, optimizer, _mesh_key(mesh),
                     augment_fn, batch_size),
                    step,
                    (state, batch),
                    program="train_classifier.step",
                    steps=1,
                    per_report=n,
                    dtype=_model_dtype(model),
                )
        em = evaluate(state.params, ebatch)
        test_acc = float(em["accuracy"])
        if report is not None:
            cont = report(
                epoch=epoch,
                accuracy=test_acc,
                loss=train_loss / max(n, 1),
            )
            if cont is False:
                break
    if on_finish is not None:
        on_finish(jax.device_get(state.params))
    return test_acc


# -- the white-box trial function (workload parity with pytorch-mnist) -------

_DATASET_CACHE: dict[tuple, Dataset] = {}


def _cached_mnist(n_train: int, n_test: int) -> Dataset:
    key = (n_train, n_test)
    if key not in _DATASET_CACHE:
        _DATASET_CACHE[key] = load_mnist(n_train, n_test)
    return _DATASET_CACHE[key]


def _build_cohort_steps(model: nn.Module, optimizer: str, mesh=None):
    def loss_fn(params, batch):
        x, y = batch
        return cross_entropy_loss(model.apply(params, x), y)

    def metric_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return {
            "accuracy": accuracy(logits, y),
            "loss": cross_entropy_loss(logits, y),
        }

    tx = _family_optimizer(optimizer)
    step = make_cohort_train_step(loss_fn, tx, mesh=mesh)
    evaluate = make_cohort_eval_step(metric_fn, mesh=mesh)
    return tx, step, evaluate


def _cohort_steps_for(model: nn.Module, optimizer: str, mesh=None):
    """Cohort twin of ``_steps_for``: same LRU, ``"cohort"``-tagged keys so
    serial and cohort executables for one architecture coexist (the mesh is
    part of the key — a trial-sharded executable must never serve a
    single-device cohort or vice versa)."""
    try:
        key = ("cohort", hash(model), model, optimizer, _mesh_key(mesh))
    except TypeError:
        return _build_cohort_steps(model, optimizer, mesh)
    with _STEP_CACHE_LOCK:
        built = _STEP_CACHE.get(key)
    if built is None:
        fresh = _build_cohort_steps(model, optimizer, mesh)
        with _STEP_CACHE_LOCK:
            built = _STEP_CACHE.setdefault(key, fresh)
    with _STEP_CACHE_LOCK:
        if key in _STEP_CACHE:
            _STEP_CACHE.move_to_end(key)
        while len(_STEP_CACHE) > _STEP_CACHE_MAX:
            _STEP_CACHE.popitem(last=False)
    return built


def mnist_cohort_trial(cctx) -> None:
    """Cohort twin of ``mnist_trial``: K members differing only in lr/momentum
    train as ONE vmapped program with stacked ``[K, ...]`` states.

    Structural knobs (arch/units/batch size/…) go through ``cctx.shared`` —
    they change the compiled program, so disagreeing members belong in
    different cohorts.  lr/momentum ride as ``[K]`` rows inside
    ``opt_state.hyperparams`` (the inject_hyperparams seam ``_set_hyperparams``
    uses serially), so the executable is identical to the serial one modulo
    the leading vmap axis.

    Batch schedule mirrors ``train_classifier(seed=0)`` exactly — one
    ``default_rng(0)`` permutation per epoch, truncated to whole batches —
    so per-member results match a serial run of the same assignment.

    On a mesh with a ``trial`` axis the stacked member dimension is padded
    to ``cctx.padded_size`` (ghost rows ride member 0's hyperparameters)
    and device-put onto the trial-sharded layout; the shared train/eval
    splits are replicated.  ``cctx.report`` drops the ghost rows, so the
    observation path is identical to the single-device cohort."""
    arch = str(cctx.shared("arch", "mlp"))
    if arch == "cnn":
        model = SmallCNN(channels=int(cctx.shared("channels", 32)))
    else:
        model = MLP(
            units=int(cctx.shared("units", 64)),
            num_layers=int(cctx.shared("num_layers", 2)),
        )
    dataset = _cached_mnist(
        int(cctx.shared("n_train", 4096)), int(cctx.shared("n_test", 1024))
    )
    epochs = int(cctx.shared("epochs", 3))
    batch_size = int(cctx.shared("batch_size", 256))
    optimizer = str(cctx.shared("optimizer", "momentum"))
    lrs = cctx.stacked("lr", default=0.05, dtype=jnp.float32)
    moms = cctx.stacked("momentum", default=0.9, dtype=jnp.float32)

    k = cctx.padded_size  # == len(cctx) without a trial axis
    seed = 0  # train_classifier's default — keeps cohort == serial
    rng = np.random.default_rng(seed)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, *dataset.input_shape), jnp.float32)
    )
    tx, step, evaluate = _cohort_steps_for(model, optimizer, cctx.cohort_mesh)
    base = TrainState.create(params, tx)
    state = stack_pytrees([base] * k)
    # per-member hyperparameters as [K] runtime operands (stacked() pads
    # ghost rows with member 0's values)
    hp = dict(state.opt_state.hyperparams)
    hp["learning_rate"] = lrs
    if "momentum" in hp:
        hp["momentum"] = moms
    state = state._replace(opt_state=state.opt_state._replace(hyperparams=hp))
    state = cctx.place_members(state)

    xd, yd = cctx.place_shared((dataset.x_train, dataset.y_train))
    scan_steps = len(dataset.x_train) // batch_size
    ne = min(1024, len(dataset.x_test))
    ebatch = cctx.place_shared((dataset.x_test[:ne], dataset.y_test[:ne]))

    for epoch in range(epochs):
        idx = rng.permutation(len(dataset.x_train))[: scan_steps * batch_size]
        losses = []
        for s in range(scan_steps):
            b = jnp.asarray(idx[s * batch_size : (s + 1) * batch_size], jnp.int32)
            # shared batch, mapped states: in_axes=(0, None) inside the step
            state, metrics = step(state, (xd[b], yd[b]))
            losses.append(metrics["loss"])  # [K], device future
        if epoch == 0 and scan_steps >= 1:
            # whole-cohort program cost ([K]-batched step); one report
            # covers scan_steps dispatches of it
            costmodel.observe_program(
                ("mnist.cohort", model, optimizer,
                 _mesh_key(cctx.cohort_mesh), k, batch_size),
                step,
                (state, (xd[b], yd[b])),
                program="mnist_cohort_trial.step",
                steps=1,
                per_report=scan_steps,
                dtype=_model_dtype(model),
            )
        train_loss = (
            jnp.sum(jnp.stack(losses), axis=0) if losses else jnp.zeros((k,))
        )
        em = evaluate(state.params, ebatch)
        cont = cctx.report(
            step=epoch,
            accuracy=em["accuracy"],
            loss=train_loss / max(scan_steps, 1),
        )
        if not cont:
            break


def mnist_trial(ctx) -> None:
    """White-box trial: tunable MNIST classifier reporting accuracy/loss."""
    p = ctx.params
    arch = str(p.get("arch", "mlp"))
    if arch == "cnn":
        model = SmallCNN(channels=int(p.get("channels", 32)))
    else:
        model = MLP(units=int(p.get("units", 64)), num_layers=int(p.get("num_layers", 2)))
    dataset = _cached_mnist(int(p.get("n_train", 4096)), int(p.get("n_test", 1024)))

    def report(epoch, accuracy, loss):
        return ctx.report(step=epoch, accuracy=accuracy, loss=loss)

    train_classifier(
        model,
        dataset,
        lr=float(p.get("lr", 0.05)),
        momentum=float(p.get("momentum", 0.9)),
        epochs=int(p.get("epochs", 3)),
        batch_size=int(p.get("batch_size", 256)),
        optimizer=str(p.get("optimizer", "momentum")),
        mesh=ctx.mesh,
        report=report,
    )


def mnist_prewarm(shared: dict, k: int, mesh=None) -> None:
    """Compile-only twin of ``mnist_trial``/``mnist_cohort_trial`` (see
    ``compile.prewarm.attach_prewarm_fn``): builds the exact jitted step
    objects the real run will pull from ``_STEP_CACHE`` and runs them once
    on dummy operands of the right shapes/dtypes, so the trial's first step
    hits the in-process jit cache (and, with ``init_compile_cache`` wired,
    the persistent XLA cache) instead of tracing + compiling.

    Dataset-free by design — prewarm must not trigger dataset loads; MNIST
    shapes are static (28, 28, 1) and the loaders produce float32/int32,
    so zeros of the right aval compile the identical program.  Mirrors the
    real paths' branching: ``k > 1`` warms the vmapped cohort step (trial
    sharding when the mesh carries a trial axis), ``k == 1`` warms either
    the device-data epoch scan or the streamed per-batch step, matching
    ``train_classifier``'s own mode selection."""
    p = dict(shared)
    arch = str(p.get("arch", "mlp"))
    if arch == "cnn":
        model = SmallCNN(channels=int(p.get("channels", 32)))
    else:
        model = MLP(
            units=int(p.get("units", 64)), num_layers=int(p.get("num_layers", 2))
        )
    n_train = int(p.get("n_train", 4096))
    n_test = int(p.get("n_test", 1024))
    batch_size = int(p.get("batch_size", 256))
    optimizer = str(p.get("optimizer", "momentum"))
    shape = (28, 28, 1)  # load_mnist's static input_shape
    k = int(k)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *shape), jnp.float32)
    )
    xb = jnp.zeros((batch_size, *shape), jnp.float32)
    yb = jnp.zeros((batch_size,), jnp.int32)
    ne = min(1024, n_test)

    if k > 1:
        from katib_tpu.parallel.mesh import replicate, shard_members, trial_axis_size

        # cohort_mesh semantics: no trial axis -> single-device vmap
        cmesh = mesh if (mesh is not None and trial_axis_size(mesh) > 1) else None
        tx, step, evaluate = _cohort_steps_for(model, optimizer, cmesh)
        base = TrainState.create(params, tx)
        state = stack_pytrees([base] * k)
        # hyperparameter VALUES are runtime rows — any finite placeholder
        # compiles the same program the real assignments will run
        hp = dict(state.opt_state.hyperparams)
        hp["learning_rate"] = jnp.full((k,), 0.05, jnp.float32)
        if "momentum" in hp:
            hp["momentum"] = jnp.full((k,), 0.9, jnp.float32)
        state = state._replace(opt_state=state.opt_state._replace(hyperparams=hp))
        xe = jnp.zeros((ne, *shape), jnp.float32)
        ye = jnp.zeros((ne,), jnp.int32)
        if cmesh is not None:
            state = shard_members(state, cmesh)
            batch = replicate((xb, yb), cmesh)
            ebatch = replicate((xe, ye), cmesh)
        else:
            batch = (xb, yb)
            ebatch = (xe, ye)
        state, _ = step(state, batch)
        # same memo label as mnist_cohort_trial: the prewarm twin and the
        # real cohort share one executable, so they share one cost record
        # (the ambient slot feeds PrewarmWorker's registry cost merge)
        costmodel.observe_program(
            ("mnist.cohort", model, optimizer, _mesh_key(cmesh), k, batch_size),
            step,
            (state, batch),
            program="mnist_cohort_trial.step",
            steps=1,
            per_report=max(1, n_train // batch_size),
            dtype=_model_dtype(model),
        )
        em = evaluate(state.params, ebatch)
    else:
        import os

        from katib_tpu.utils.booleans import parse_bool

        tx, step, evaluate, scan_epoch, _aug = _steps_for(model, optimizer, mesh)
        state = TrainState.create(params, tx)
        state = state._replace(
            opt_state=_set_hyperparams(state.opt_state, 0.05, 0.9)
        )
        if mesh is not None:
            from katib_tpu.parallel.mesh import replicate

            state = replicate(state, mesh)
        env = os.environ.get("KATIB_DEVICE_DATA")
        device_data = mesh is None if env is None else parse_bool(env)
        scan_steps = n_train // batch_size
        if device_data and mesh is None and scan_steps >= 1:
            xz = jnp.zeros((n_train, *shape), jnp.float32)
            yz = jnp.zeros((n_train,), jnp.int32)
            iz = jnp.zeros((scan_steps, batch_size), jnp.int32)
            kz = jax.random.PRNGKey(0)
            state, _ = scan_epoch(state, xz, yz, iz, kz)
            costmodel.observe_program(
                ("mnist.scan", model, optimizer, _mesh_key(mesh),
                 None, batch_size, scan_steps),
                scan_epoch,
                (state, xz, yz, iz, kz),
                program="train_classifier.scan_epoch",
                steps=scan_steps,
                per_report=1,
                dtype=_model_dtype(model),
            )
        else:
            batch = (xb, yb) if mesh is None else shard_batch((xb, yb), mesh)
            state, _ = step(state, batch)
            costmodel.observe_program(
                ("mnist.step", model, optimizer, _mesh_key(mesh),
                 None, batch_size),
                step,
                (state, batch),
                program="train_classifier.step",
                steps=1,
                per_report=max(1, scan_steps),
                dtype=_model_dtype(model),
            )
        # eval prefix: same truncate/tile placement as train_classifier
        xe = np.zeros((ne, *shape), np.float32)
        ye = np.zeros((ne,), np.int32)
        if mesh is not None:
            from katib_tpu.parallel.mesh import DATA_AXIS, local_mesh_size

            d = local_mesh_size(mesh, DATA_AXIS)
            if ne >= d:
                xe, ye = xe[: (ne // d) * d], ye[: (ne // d) * d]
            elif ne > 0:
                reps = -(-d // ne)
                xe = np.tile(xe, (reps,) + (1,) * (xe.ndim - 1))[:d]
                ye = np.tile(ye, reps)[:d]
            ebatch = shard_batch((xe, ye), mesh)
        else:
            ebatch = jax.device_put((xe, ye))
        em = evaluate(state.params, ebatch)
    em["accuracy"].block_until_ready()


# opt-in: the orchestrator batches compatible mnist_trial proposals through
# the vmapped twin when the experiment declares a cohort (runner/cohort.py),
# and the prewarm worker compiles upcoming groups' programs in the
# background through the compile-only twin (compile/prewarm.py)
from katib_tpu.compile.prewarm import attach_prewarm_fn  # noqa: E402
from katib_tpu.runner.cohort import attach_cohort_fn  # noqa: E402

attach_cohort_fn(mnist_trial, mnist_cohort_trial)
attach_prewarm_fn(mnist_trial, mnist_prewarm)
