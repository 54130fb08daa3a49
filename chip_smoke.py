#!/usr/bin/env python3
"""Standing proof that katib-tpu's main path starts on the chip.

Drives suggester -> orchestrator -> cohort/trial runner -> checkpoint ->
journal through the calls ``katib-tpu run`` makes (``KatibConfig`` ->
``experiment_spec_from_dict`` -> ``init_compile_cache`` ->
``Orchestrator.run``), in ONE process: a chip belongs to one process at a
time, so nothing here starts a child that needs it.

    python chip_smoke.py            # sweep phase: MNIST cohort sweep, one chip
    python chip_smoke.py --darts    # DARTS phase only: the flagship supernet
                                    # (cold compile: ~20 min and > 40 GiB of host
                                    # memory; see CHANGES.md, PR 22)
    python chip_smoke.py --chips 4  # trial-sharded cohort vs one device only
    python chip_smoke.py --allow-cpu [--darts | --chips 4]   # tiny rehearsal

Without a TPU the script exits non-zero before any phase.  ``--allow-cpu``
rehearses the control flow at a tiny size and never prints the result line.
Every phase that fails raises, so the run ends non-zero; the last line of a
passing run is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it.  Work directories live under ``chiprun_out/chip_smoke/``; the
compile cache is wherever ``init_compile_cache`` resolves it
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""

from __future__ import annotations

import argparse
import collections
import json
import logging
import math
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
SEED = 0
# the dry-run gate's tolerance for sharded-vs-single-device cohorts
# (__graft_entry__.dryrun_multichip)
GATE_RTOL, GATE_ATOL = 1e-6, 1e-7


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, sort_keys=True, default=str), flush=True)


def check(cond: bool, message: str) -> None:
    if not cond:
        raise AssertionError(message)


class CompileCounts(logging.Handler):
    """jax's own compile telemetry, by program name: every compile request
    the persistent cache answered (a hit) and every one it did not (a
    compilation).  Read from the two log records of ``jax._src.compiler``,
    which carry the module's name as their first argument."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.hits: collections.Counter = collections.Counter()
        self.compiled: collections.Counter = collections.Counter()
        logger = logging.getLogger("jax._src.compiler")
        logger.setLevel(logging.DEBUG)
        logger.propagate = False  # the records are counted here, not printed
        logger.addHandler(self)

    def emit(self, record: logging.LogRecord) -> None:
        if not isinstance(record.msg, str) or not record.args:
            return
        if record.msg.startswith("Persistent compilation cache hit"):
            self.hits[str(record.args[0])] += 1
        elif record.msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.compiled[str(record.args[0])] += 1

    def snapshot(self) -> tuple[collections.Counter, collections.Counter]:
        return collections.Counter(self.hits), collections.Counter(self.compiled)

    def since(self, snap) -> dict:
        hits, compiled = self.hits - snap[0], self.compiled - snap[1]
        return {
            "persistent_cache_hits": sum(hits.values()),
            "compilations": sum(compiled.values()),
            "compiled_programs": dict(sorted(compiled.items())),
        }


def _obs_totals() -> dict:
    """The program's own counters this script reads, summed over labels."""
    from katib_tpu.utils import observability as obs

    counters = {
        "cohorts": obs.cohorts_executed,
        "fallbacks": obs.cohort_fallbacks,
        "registry_warm": obs.compile_cache_hits,
        "registry_cold": obs.compile_cache_misses,
    }
    return {
        name: float(sum(v for _labels, v in metric.samples()))
        for name, metric in counters.items()
    }


def fresh_workdir(name: str) -> str:
    """This script's own work directory for one phase, emptied: a journal
    left by an earlier run must not be replayed into this one."""
    path = os.path.join(OUT, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def run_experiment(doc: dict, workdir: str, mesh_axes: dict | None = None):
    """The calls ``katib-tpu run`` makes (cli.cmd_run), device preflight on."""
    from katib_tpu.core.config import KatibConfig
    from katib_tpu.runner.trial_runner import init_compile_cache
    from katib_tpu.sdk.yaml_spec import experiment_spec_from_dict

    cfg = KatibConfig.load(None)
    cfg.init.workdir = workdir
    if mesh_axes:
        cfg.init.mesh_axes = dict(mesh_axes)
    spec = experiment_spec_from_dict(doc)
    cache = init_compile_cache(spec.compile_cache)
    orch = cfg.make_orchestrator()
    orch.preflight = True
    exp = orch.run(spec)
    # the orchestrator asks its prewarm worker to stop but never joins it:
    # let a compile in flight finish, so that its count lands in this phase
    # and the interpreter does not tear JAX down under it
    for t in threading.enumerate():
        if t.name == "katib-prewarm":
            t.join(timeout=300)
    return orch, exp, cache


def settled_ok(phase: str, orch, exp, workdir: str, n_trials: int) -> dict:
    """What every phase must hold: all trials Succeeded, an optimal trial,
    finite metrics, and a journal that replays clean.  Returns
    ``{trial_name: {metric: [values by step]}}``."""
    from katib_tpu.orchestrator.journal import replay_journal

    conditions = sorted(t.condition.value for t in exp.trials.values())
    say(phase, experiment=exp.condition.value, trials=conditions, message=exp.message)
    check(
        exp.condition.is_terminal() and exp.condition.value != "Failed",
        f"experiment {exp.condition.value}: {exp.message}",
    )
    check(
        conditions == ["Succeeded"] * n_trials,
        f"expected {n_trials} Succeeded trials, got {conditions}",
    )
    check(exp.optimal is not None, "no optimal trial")
    say(
        phase,
        optimal=exp.optimal.trial_name,
        objective=exp.optimal.objective_value,
        store=type(orch.store).__name__,
    )
    series: dict[str, dict[str, list[float]]] = {}
    for name in exp.trials:
        logs = sorted(orch.store.get(name), key=lambda m: (m.metric_name, m.step))
        per: dict[str, list[float]] = {}
        for m in logs:
            per.setdefault(m.metric_name, []).append(float(m.value))
        check("loss" in per and "accuracy" in per, f"{name}: no loss/accuracy reported")
        check(
            all(math.isfinite(v) for vs in per.values() for v in vs),
            f"{name}: non-finite metric {per}",
        )
        series[name] = per
    state, stats = replay_journal(workdir, exp.name)
    check(state is not None, "the work directory holds no journal")
    replayed = sorted(t.get("condition") for t in state["trials"].values())
    say(
        phase,
        journal_snapshot_seq=stats.snapshot_seq,
        journal_last_seq=stats.last_seq,
        journal_records_after_snapshot=stats.applied,
        journal_bad=stats.bad_records,
        journal_torn_bytes=stats.torn_bytes,
        journal_trials=len(replayed),
    )
    check(stats.bad_records == 0 and stats.torn_bytes == 0, "journal has bad or torn records")
    check(replayed == ["Succeeded"] * n_trials, f"journal replays to {replayed}")
    return series


def spans(workdir: str, exp_name: str, span_name: str) -> list[dict]:
    from katib_tpu.utils import tracing

    recs = tracing.read_journal(tracing.trace_path(workdir, exp_name))
    return [r for r in recs if r["name"] == span_name]


# -- sweep phase --------------------------------------------------------------


def sweep_doc(name: str, tiny: bool, width: int, trials: int) -> dict:
    """An experiment of the shape of examples/hp-tuning/cohort-prewarm.yaml:
    mnist_trial at the model's own width (units 64) on the dataset's full
    rows (60000/10000; synthetic MNIST-shaped data made from a fixed seed,
    models/data.py), random search, vmapped cohorts, prewarm on."""
    n_train, n_test = (1024, 256) if tiny else (60000, 10000)

    def pinned(pname: str, value: int) -> dict:
        return {
            "name": pname,
            "parameterType": "int",
            "feasibleSpace": {"min": str(value), "max": str(value)},
        }

    return {
        "apiVersion": "kubeflow.org/v1beta1",
        "kind": "Experiment",
        "metadata": {"name": name},
        "spec": {
            "objective": {
                "type": "maximize",
                "objectiveMetricName": "accuracy",
                "additionalMetricNames": ["loss"],
            },
            "algorithm": {
                "algorithmName": "random",
                "algorithmSettings": [{"name": "random_state", "value": str(SEED)}],
            },
            "parallelTrialCount": width,
            "maxTrialCount": trials,
            "cohortWidth": width,
            "cohortKey": "mnist-mlp64",
            "cohortBuckets": True,
            "prewarm": True,
            "parameters": [
                {
                    "name": "lr",
                    "parameterType": "double",
                    "feasibleSpace": {"min": "0.01", "max": "0.1"},
                },
                {
                    "name": "momentum",
                    "parameterType": "double",
                    "feasibleSpace": {"min": "0.5", "max": "0.95"},
                },
                pinned("units", 64),
                pinned("n_train", n_train),
                pinned("n_test", n_test),
            ],
            "trialTemplate": {"trainFn": "katib_tpu.models.mnist.mnist_trial"},
        },
    }


class MemberPlacement:
    """Records where ``CohortContext.place_members`` put each cohort's
    stacked ``[K, ...]`` state — the script's own probe, the program has no
    option for it."""

    def __init__(self) -> None:
        from katib_tpu.runner.cohort import CohortContext

        self.cohorts: list[dict] = []
        inner = CohortContext.place_members
        records = self.cohorts

        def place_members(ctx, tree):
            import jax

            placed = inner(ctx, tree)
            leaves = jax.tree_util.tree_leaves(placed)
            lead = max(leaves, key=lambda a: a.size)
            records.append(
                {
                    "k": int(lead.shape[0]),
                    "platforms": sorted({d.platform for a in leaves for d in a.devices()}),
                    "devices": sorted({d.id for a in leaves for d in a.devices()}),
                    # one row per device that really holds a shard
                    "members_per_device": {
                        s.device.id: int(s.data.shape[0]) for s in lead.addressable_shards
                    },
                }
            )
            return placed

        CohortContext.place_members = place_members


def run_sweep(phase, name, workdir, tiny, width, trials, counts, placement, mesh_axes=None):
    from katib_tpu.utils import observability as obs

    before = _obs_totals()
    c0 = counts.snapshot()
    n_placed = len(placement.cohorts)
    t0 = time.perf_counter()
    orch, exp, cache = run_experiment(sweep_doc(name, tiny, width, trials), workdir, mesh_axes)
    wall = time.perf_counter() - t0
    series = settled_ok(phase, orch, exp, workdir, trials)

    after = _obs_totals()
    delta = {k: after[k] - before[k] for k in after}
    say(
        phase,
        cache_dir=cache,
        wall_seconds=round(wall, 3),
        **counts.since(c0),
        registry_warm_first_steps=delta["registry_warm"],
        registry_cold_first_steps=delta["registry_cold"],
    )
    n_cohorts = trials // width
    check(delta["cohorts"] == n_cohorts, f"{delta['cohorts']} cohorts ran, expected {n_cohorts}")
    check(delta["fallbacks"] == 0, "a cohort fell back to serial trials")

    placed = placement.cohorts[n_placed:]
    say(phase, member_states=placed)
    check(len(placed) == n_cohorts, f"{len(placed)} cohort states placed, expected {n_cohorts}")

    cohort_spans = sorted(spans(workdir, exp.name, "cohort"), key=lambda r: r["ts"])
    durs = [r["dur"] for r in cohort_spans]
    first_steps = [
        {**labels, "seconds": round(v, 3)}
        for labels, v in obs.trial_first_step_seconds.samples()
        if labels.get("phase") == "first_report"
    ]
    say(
        phase,
        first_cohort_seconds_compile_and_train=durs[0] if durs else None,
        later_cohort_seconds=durs[1:],
        compile_and_first_step=first_steps,
    )
    check(len(durs) == n_cohorts, f"{len(durs)} cohort spans in the trace, expected {n_cohorts}")
    return exp, series, placed


def sweep_phase(args, counts, placement, platform: str) -> None:
    _exp, _series, placed = run_sweep(
        "sweep", "chip-smoke-sweep", fresh_workdir("sweep"), args.allow_cpu,
        width=4, trials=8, counts=counts, placement=placement,
    )
    for rec in placed:
        check(
            rec["platforms"] == [platform] and rec["k"] == 4,
            f"stacked member state not a K=4 array on {platform}: {rec}",
        )


# -- four chips ---------------------------------------------------------------


def chips4_phase(args, counts, placement, platform: str) -> None:
    """The one path users run across chips: the sweep's cohort at K=8 with
    the member dimension sharded over a {trial: 4} mesh, against the same
    cohort on one device."""
    import numpy as np

    runs = {}
    for tag, mesh_axes in (("sharded", {"trial": 4}), ("single", None)):
        exp, series, placed = run_sweep(
            f"chips4-{tag}", "chip-smoke-chips4", fresh_workdir(f"chips4-{tag}"),
            args.allow_cpu, width=8, trials=8, counts=counts, placement=placement,
            mesh_axes=mesh_axes,
        )
        by_assignment = {
            tuple(sorted((a.name, a.value) for a in t.spec.assignments)): series[t.name]
            for t in exp.trials.values()
        }
        runs[tag] = (by_assignment, placed[0])

    sharded = runs["sharded"][1]
    check(
        sharded["platforms"] == [platform]
        and len(sharded["members_per_device"]) == 4
        and set(sharded["members_per_device"].values()) == {2},
        f"K=8 is not 2 members on each of 4 devices: {sharded}",
    )
    single = runs["single"][1]
    check(
        len(single["devices"]) == 1 and single["k"] == 8,
        f"the comparison cohort is not K=8 on one device: {single}",
    )
    a, b = runs["sharded"][0], runs["single"][0]
    check(set(a) == set(b), "the two runs drew different assignments")
    worst = 0.0
    for key in a:
        for metric in ("loss", "accuracy"):
            got, want = np.asarray(a[key][metric]), np.asarray(b[key][metric])
            check(got.shape == want.shape, f"{metric} series differ in length")
            worst = max(worst, float(np.max(np.abs(got - want))))
            np.testing.assert_allclose(
                got, want, rtol=GATE_RTOL, atol=GATE_ATOL,
                err_msg=f"trial-sharded cohort differs from one device in {metric}",
            )
    say("chips4", members=8, devices=4, max_abs_difference=worst, rtol=GATE_RTOL, atol=GATE_ATOL)


# -- DARTS phase --------------------------------------------------------------


def darts_doc(tiny: bool) -> dict:
    """The flagship at the reference's width: 8 layers, 16 channels, 4
    nodes, the eight DEFAULT_PRIMITIVES, batch 64, second order, on the
    default device-resident scan path with a 4-step window.  Data is
    synthetic, CIFAR-shaped, made from a fixed seed (models/data.py)."""
    layers, channels, nodes, batch, n_train = (
        (2, 4, 2, 8, 64) if tiny else (8, 16, 4, 64, 1024)
    )
    settings = {
        "num_epochs": 1,
        "batch_size": batch,
        "init_channels": channels,
        "num_nodes": nodes,
        "n_train": n_train,
        "n_test": 256 if tiny else 1024,
        "stepLoopWindow": 4,
    }

    def conv(kind: str, sizes: list[str]) -> dict:
        return {
            "operationType": kind,
            "parameters": [
                {
                    "name": "filter_size",
                    "parameterType": "categorical",
                    "feasibleSpace": {"list": sizes},
                }
            ],
        }

    return {
        "apiVersion": "kubeflow.org/v1beta1",
        "kind": "Experiment",
        "metadata": {"name": "chip-smoke-darts"},
        "spec": {
            "objective": {
                "type": "maximize",
                "objectiveMetricName": "accuracy",
                "additionalMetricNames": ["loss"],
            },
            "algorithm": {
                "algorithmName": "darts",
                "algorithmSettings": [
                    {"name": k, "value": str(v)} for k, v in settings.items()
                ],
            },
            "parallelTrialCount": 1,
            "maxTrialCount": 1,
            "nasConfig": {
                "graphConfig": {"numLayers": layers},
                # spelled out in DEFAULT_PRIMITIVES' order
                "operations": [
                    {"operationType": "none"},
                    conv("max_pooling", ["3"]),
                    conv("avg_pooling", ["3"]),
                    {"operationType": "skip_connection"},
                    conv("separable_convolution", ["3", "5"]),
                    conv("dilated_convolution", ["3", "5"]),
                ],
            },
            "trialTemplate": {"trainFn": "katib_tpu.nas.darts.search.darts_trial"},
        },
    }


def darts_phase(args, counts, platform: str) -> None:
    import jax
    import jax.numpy as jnp

    from katib_tpu.nas.darts.ops import DEFAULT_PRIMITIVES
    from katib_tpu.ops import mixed_op
    from katib_tpu.utils import observability as obs

    check(
        "KATIB_PALLAS_MIXED_OP" not in os.environ,
        "KATIB_PALLAS_MIXED_OP is set; this phase runs the default dispatch",
    )
    # which implementation the traced supernet calls: counted at the two
    # functions mixed_op_sum chooses between (the script's probe)
    calls = {"pallas": 0, "lax": 0}
    inner_pallas, inner_lax = mixed_op._pallas_mixed_op, mixed_op._lax_reference

    def count_pallas(weights, stacked, interpret):
        calls["pallas"] += 1
        return inner_pallas(weights, stacked, interpret)

    def count_lax(weights, stacked):
        calls["lax"] += 1
        return inner_lax(weights, stacked)

    mixed_op._pallas_mixed_op, mixed_op._lax_reference = count_pallas, count_lax

    workdir = fresh_workdir("darts")
    doc = darts_doc(args.allow_cpu)
    c0 = counts.snapshot()
    t0 = time.perf_counter()
    orch, exp, cache = run_experiment(doc, workdir)
    wall = time.perf_counter() - t0
    settled_ok("darts", orch, exp, workdir, 1)

    (trial,) = exp.trials.values()
    space = json.loads(trial.params()["search-space"])
    check(tuple(space) == DEFAULT_PRIMITIVES, f"search space {space} is not DEFAULT_PRIMITIVES")
    genotype_path = os.path.join(trial.checkpoint_dir, "genotype.json")
    check(os.path.isfile(genotype_path), f"{genotype_path} was not written")
    with open(genotype_path) as f:
        genotype = json.load(f)
    check(bool(genotype["normal"]) and bool(genotype["reduce"]), "empty genotype")

    spd = obs.steps_per_dispatch.get(workload="darts")
    window = obs.step_loop_window.get(workload="darts")
    first = {
        labels["phase"]: v
        for labels, v in obs.trial_first_step_seconds.samples()
        if labels.get("workload") == "darts-scan"
    }
    (epoch,) = spans(workdir, exp.name, "darts.epoch")
    steps = int(epoch["args"]["steps"])
    say(
        "darts",
        data="synthetic CIFAR-shaped, seeded (models/data.py)",
        settings=json.loads(trial.params()["algorithm-settings"]),
        num_layers=trial.params()["num-layers"],
        primitives=space,
        genotype=genotype_path,
        cache_dir=cache,
        wall_seconds=round(wall, 3),
        **counts.since(c0),
    )
    say(
        "darts",
        katib_steps_per_dispatch=spd,
        step_loop_window=window,
        steps=steps,
        # first dispatch blocks on trace + compile; the loss fetch then
        # blocks on the execution of every step of the epoch
        trace_and_compile_seconds=first.get("compile"),
        execute_seconds_all_steps=first.get("execute"),
        step_seconds=(first["execute"] / steps) if "execute" in first else None,
        epoch_seconds=epoch["dur"],
        epoch=epoch["args"],
    )
    check(spd == 4.0, f"katib_steps_per_dispatch is {spd}, expected 4")
    check("compile" in first and "execute" in first, "no first-step split was recorded")

    # the kernel in the compiled program: the traced supernet went through
    # _pallas_mixed_op and never through the lax reference, and one MixedOp
    # of the first cell lowers to a tpu_custom_call on this backend
    say("darts", mixed_op_calls_while_tracing=calls, backend=jax.default_backend())
    if platform == "tpu":
        check(calls["pallas"] > 0 and calls["lax"] == 0, f"supernet traced the lax path: {calls}")
        w = jax.ShapeDtypeStruct((len(DEFAULT_PRIMITIVES),), jnp.float32)
        x = jax.ShapeDtypeStruct((len(DEFAULT_PRIMITIVES), 64, 32, 32, 16), jnp.bfloat16)
        text = jax.jit(mixed_op.mixed_op_sum).lower(w, x).as_text()
        n = text.count("tpu_custom_call")
        say("darts", mixed_op_lowered_tpu_custom_calls=n)
        check(n > 0, "one MixedOp lowered without a tpu_custom_call")
    else:
        say("darts", note="rehearsal on the CPU: the lax reference is the expected branch")


# -- entry --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--darts", action="store_true", help="run the DARTS phase only")
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: the trial-sharded cohort against one device, and no other phase",
    )
    ap.add_argument(
        "--allow-cpu", action="store_true",
        help="rehearse at a tiny size without a TPU; never prints the result line",
    )
    args = ap.parse_args(argv)
    if args.darts and args.chips != 1:
        ap.error("--darts and --chips 4 are separate runs")
    if args.allow_cpu and args.chips > 1 and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={args.chips}"
        ).strip()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(
            f"chip_smoke: JAX found no accelerator (platform {dev.platform!r}); "
            "this check needs a TPU (rehearse with --allow-cpu)",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, JAX reports {len(devices)}", file=sys.stderr)
        return 2

    from importlib import metadata

    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = ""
    say(
        "start",
        platform=dev.platform,
        kind=dev.device_kind,
        count=len(devices),
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=libtpu,
        JAX_COMPILATION_CACHE_DIR=os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        rehearsal=args.allow_cpu,
    )
    counts = CompileCounts()
    if args.darts:
        darts_phase(args, counts, dev.platform)
    elif args.chips == 4:
        chips4_phase(args, counts, MemberPlacement(), dev.platform)
    else:
        sweep_phase(args, counts, MemberPlacement(), dev.platform)

    if args.allow_cpu:
        say("done", note="rehearsal passed; no result line without a TPU")
        return 0
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
