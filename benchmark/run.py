#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, in ONE
process (a chip belongs to one process): warms up the cell's programs (set-up),
drives ``Orchestrator.run`` for ``--seconds`` seconds as ``katib-tpu run``
would, compares a finished trial's reported series with the family's plain
reference, and prints one JSON line.  Without a TPU it exits 2 and prints no
result; ``--allow-cpu`` rehearses the control flow at the sizes of a cell under
``benchmark/tests/`` and never prints the result line.

Everything that belongs to one cell is data found by name: the configuration
(``configs/<config>.json``), its family's code (``families/<family>.py``), the
traffic mix (``traffic/<mix>.json``), the limits of ``correct``
(``limits/<cell>.json``) and one reader a per-layer metric
(``layer_metrics/<metric>.py``).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "benchmark_out")
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]

import peaks  # noqa: E402
import trace_reduce  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_PROCESS:8.2f}s] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, from data
# ---------------------------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything found by its names."""

    def __init__(self, workload: str, bench_path: str | None = None):
        # another BENCHMARK.json (the tests' tiny cell) brings its own data files
        data = os.path.dirname(os.path.abspath(bench_path)) if bench_path else HERE
        base = data if bench_path else ROOT
        self.bench = load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
        rows = [w for w in self.bench["workloads"] if w["name"] == workload]
        if not rows:
            raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
        self.row = rows[0]
        self.name = workload
        self.chips = int(self.row["chips"])
        (cfg_row,) = [c for c in self.bench["configs"] if c["name"] == self.row["config"]]
        self.config = load_json(os.path.join(base, cfg_row["file"]))
        self.traffic = load_json(os.path.join(data, "traffic", f"{self.row['traffic']}.json"))
        self.family = load_module("families", self.config["family"])
        self.sizes = {k: self.config[k] for k in self.family.SIZE_KEYS}
        self.limits = load_json(os.path.join(data, "limits", f"{workload}.json"))["limits"]

    def metrics(self, group: str) -> list[dict]:
        """The cell's rows of ``end_to_end`` or ``per_layer``."""
        return [
            m for m in self.bench[group] if "workloads" not in m or self.name in m["workloads"]
        ]


# ---------------------------------------------------------------------------
# what the harness takes from the program: compile log, spans, store, journal
# ---------------------------------------------------------------------------


class CompileCounts(logging.Handler):
    """jax's own compile telemetry, by program name: every compile request the
    persistent cache answered (a load) and every one it did not (a
    compilation).  Copied from chip_smoke.py."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.hits: collections.Counter = collections.Counter()
        self.compiled: collections.Counter = collections.Counter()
        logger = logging.getLogger("jax._src.compiler")
        logger.setLevel(logging.DEBUG)
        logger.propagate = False
        logger.addHandler(self)

    def emit(self, record: logging.LogRecord) -> None:
        if not isinstance(record.msg, str) or not record.args:
            return
        if record.msg.startswith("Persistent compilation cache hit"):
            self.hits[str(record.args[0])] += 1
        elif record.msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.compiled[str(record.args[0])] += 1

    def snapshot(self):
        return collections.Counter(self.hits), collections.Counter(self.compiled)

    def since(self, snap) -> dict:
        hits, compiled = self.hits - snap[0], self.compiled - snap[1]
        return {
            "loads": sum(hits.values()),
            "compilations": sum(compiled.values()),
            "compiled_programs": dict(sorted(compiled.items())),
            "loaded_programs": dict(sorted(hits.items())),
        }


def make_orchestrator(doc: dict, workdir: str):
    """The calls ``katib-tpu run`` makes (cli.cmd_run), device preflight on."""
    from katib_tpu.core.config import KatibConfig
    from katib_tpu.runner.trial_runner import init_compile_cache
    from katib_tpu.sdk.yaml_spec import experiment_spec_from_dict

    cfg = KatibConfig.load(None)
    cfg.init.workdir = workdir
    spec = experiment_spec_from_dict(doc)
    init_compile_cache(spec.compile_cache)
    orch = cfg.make_orchestrator()
    orch.preflight = True
    return orch, spec


def read_spans(workdir: str, exp_name: str) -> list[dict]:
    """The experiment's trace journal, every span on the host's wall clock:
    ``t0``/``t1`` in ``time.time()`` seconds (the journal keeps an elapsed
    offset and the wall time of the same instant)."""
    path = os.path.join(workdir, exp_name, "trace.jsonl")
    out = []
    try:
        with open(path, errors="replace") as f:
            lines = f.readlines()
    except OSError:
        return out
    anchor = None
    recs = []
    for line in lines:
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "name" in rec and "ts" in rec and "wall" in rec:
            recs.append(rec)
            # wall is rounded to the millisecond: the earliest difference is
            # the tracer's anchor to within that
            a = float(rec["wall"]) - float(rec["ts"])
            anchor = a if anchor is None else min(anchor, a)
    for rec in recs:
        t0 = anchor + float(rec["ts"])
        out.append(
            {"name": rec["name"], "t0": t0, "t1": t0 + float(rec["dur"]), "args": rec.get("args", {})}
        )
    return out


def trial_series(orch, trial_name: str) -> dict:
    """``{metric: {step: value}}`` of one trial, from the observation store."""
    per: dict[str, dict[int, float]] = {}
    for m in orch.store.get(trial_name):
        per.setdefault(m.metric_name, {})[int(m.step)] = float(m.value)
    return per


def assignment_of(trial) -> dict:
    return {a.name: a.value for a in trial.spec.assignments}


def journal_faults(workdir: str, exp_name: str, succeeded: set[str]) -> dict:
    """The journal must replay clean and to the same settlements."""
    from katib_tpu.orchestrator.journal import replay_journal

    state, stats = replay_journal(workdir, exp_name)
    if state is None:
        return {"journal_bad_records": math.inf, "journal_unsettled": math.inf}
    replayed = {n for n, t in state["trials"].items() if t.get("condition") == "Succeeded"}
    return {
        "journal_bad_records": float(stats.bad_records + stats.torn_bytes),
        "journal_unsettled": float(len(succeeded ^ replayed)),
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_experiment(cell: Cell, name: str, workdir: str, seed: int, **doc_args):
    doc = cell.family.experiment_doc(name, cell.sizes, cell.traffic, seed, **doc_args)
    orch, spec = make_orchestrator(doc, workdir)
    exp = orch.run(spec)
    return orch, exp


def warm_up(cell: Cell, workdir: str, seed: int, counts: CompileCounts) -> dict:
    """One trial at the cell's own sizes with the first learning rate.  If
    that compiled anything the cache is cold: one trial at each other rate
    too, so that the window finds every program in the cache."""
    values = cell.family.lr_values(cell.traffic)
    snap = counts.snapshot()
    first = None
    done = []
    for i, lr in enumerate(values):
        orch, exp = run_experiment(
            cell, f"warm-{i}", workdir, seed, lr_values=[lr], max_trials=1
        )
        conditions = [t.condition.value for t in exp.trials.values()]
        if conditions != ["Succeeded"]:
            raise RuntimeError(f"warm-up trial at lr {lr} ended {conditions}: {exp.message}")
        done.append(lr)
        if i == 0:
            (trial,) = exp.trials.values()
            first = {"assignment": assignment_of(trial), "series": trial_series(orch, trial.name)}
            if counts.since(snap)["compilations"] == 0:
                break
    c = counts.since(snap)
    log(f"warm-up: {len(done)} trial(s), {c['compilations']} compilations, {c['loads']} cache loads")
    return {"trials": len(done), "first": first, **c}


class Profiler:
    """jax.profiler around one slice of the window, started and stopped from
    the main thread when the journal shows a trial's end."""

    def __init__(self, directory: str):
        self.directory = directory
        self.t1 = None
        self.anchor_wall_ns = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.anchor_wall_ns = time.time_ns()
        with jax.profiler.TraceAnnotation("benchmark.anchor"):
            pass

    def stop(self) -> None:
        import jax

        self.t1 = time.time()
        jax.profiler.stop_trace()

    def path(self) -> str | None:
        found = []
        for base, _dirs, files in os.walk(self.directory):
            found += [os.path.join(base, f) for f in files if f.endswith(".xplane.pb")]
        return max(found, key=os.path.getmtime) if found else None


def count_trial_spans(workdir: str, exp_name: str) -> int:
    try:
        with open(os.path.join(workdir, exp_name, "trace.jsonl"), errors="replace") as f:
            return sum(1 for line in f if '"name": "trial"' in line)
    except OSError:
        return 0


def window(cell: Cell, workdir: str, seed: int, seconds: float, profiler: Profiler | None):
    """Start the experiment on a thread (``Orchestrator.run`` blocks), let it
    run for ``seconds``, stop it from here."""
    name = "window"
    doc = cell.family.experiment_doc(name, cell.sizes, cell.traffic, seed)
    orch, spec = make_orchestrator(doc, workdir)
    box: dict = {}

    def target():
        try:
            box["exp"] = orch.run(spec)
        except BaseException as e:  # reported by the caller
            box["error"] = e

    thread = threading.Thread(target=target, name="benchmark-window", daemon=True)
    if profiler is not None:
        # the traced slice: the window's first trial, whole, from the window's
        # start (experiment start, suggest, host work, every step, harvest).
        # Starting the profiler takes seconds, so it starts before the window.
        profiler.start()
    t0_wall, t0 = time.time(), time.perf_counter()
    thread.start()
    deadline = t0 + seconds
    while thread.is_alive() and time.perf_counter() < deadline:
        if profiler is not None and profiler.t1 is None and count_trial_spans(workdir, name) >= 1:
            profiler.stop()
        nap = 0.02 if profiler is not None and profiler.t1 is None else 0.25
        time.sleep(max(0.0, min(nap, deadline - time.perf_counter())))
    if profiler is not None and profiler.t1 is None:
        profiler.stop()
    t1_wall = time.time()
    orch.stop()
    thread.join(timeout=180)
    if thread.is_alive():
        raise RuntimeError("the experiment did not stop within 180 s of Orchestrator.stop()")
    if "error" in box:
        raise box["error"]
    return orch, box["exp"], name, t0_wall, t1_wall


def device_report(devices) -> dict:
    """The device as JAX reports it.  The TPU's allocator keeps live buffers
    (``peak_bytes_in_use``) and the scratch it reserves for a running program
    (``peak_bytes_reserved``: a train step's temporaries) in two counters; a
    train step holds both at once, so the peak is their sum."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(
            peak, int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))
        )
    dev = devices[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def limit_of(limit, assignment: dict) -> float | None:
    """A limit is one number, or one number for each value of a parameter of
    the trial that was compared: ``{"by": "lr", "0.0001": ..., "0.003": null}``.
    ``null``: the number has no limit at that value and is not compared
    (PERF.md says why); it is printed under ``not_compared``."""
    if isinstance(limit, dict):
        limit = limit[repr(float(assignment[limit["by"]]))]
    return None if limit is None else float(limit)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, keep_trace: str | None = None) -> dict:
    """Everything of a run after the look for a chip.  Returns the result
    line as a dict; raises where the run cannot be measured."""
    import jax

    devices = jax.devices()[: cell.chips]
    counts = CompileCounts()
    workdir = os.path.join(OUT, cell.name, "work")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)

    warm = warm_up(cell, workdir, seed, counts)
    profiler = Profiler(os.path.join(OUT, cell.name, "profile")) if trace else None

    snap = counts.snapshot()
    t_window = time.perf_counter()
    setup_s = t_window - T_PROCESS
    log(f"window: {seconds} s, set-up took {setup_s:.2f} s")
    orch, exp, exp_name, t0_wall, t1_wall = window(cell, workdir, seed, seconds, profiler)
    compile_log = counts.since(snap)
    device = device_report(devices)

    spans = read_spans(workdir, exp_name)
    trial_spans = sorted((s for s in spans if s["name"] == "trial"), key=lambda s: s["t1"])
    in_window = [s for s in trial_spans if s["t1"] <= t1_wall]
    done = [s for s in in_window if s["args"].get("condition") == "Succeeded"]
    failed = [s for s in in_window if s["args"].get("condition") != "Succeeded"]
    log(
        f"window closed: {len(done)} trials Succeeded, {len(failed)} failed, "
        f"{compile_log['compilations']} compilations, {compile_log['loads']} cache loads"
    )
    if not done:
        raise RuntimeError(
            f"no trial completed inside the window of {seconds} s "
            f"(experiment {exp.condition.value}: {exp.message})"
        )
    last_end = done[-1]["t1"]
    done_names = [s["args"]["trial"] for s in done]
    succeeded = {n for n, t in exp.trials.items() if t.condition.value == "Succeeded"}

    # -- correct: against the plain reference, one finished trial of the window
    # drawn from the seed, and the warm-up trial (the same entry and programs
    # at the first learning rate, whose every number has a limit)
    sampled = random.Random(seed).choice(done_names)
    all_series = {name: trial_series(orch, name) for name in done_names}
    judged = [
        ("", assignment_of(exp.trials[sampled]), all_series[sampled]),
        ("warmup_", warm["first"]["assignment"], warm["first"]["series"]),
    ]
    assignment = judged[0][1]
    exact = journal_faults(workdir, exp_name, succeeded)
    exact["nonfinite_reports"] = float(
        sum(
            not math.isfinite(v)
            for series in all_series.values()
            for per in series.values()
            for v in per.values()
        )
    )
    del orch, exp
    jax.clear_caches()
    compared = {k: {"value": v, "limit": float(cell.limits[k])} for k, v in exact.items()}
    not_compared = {}
    t_ref = time.perf_counter()
    references: dict = {}
    for prefix, asg, series in judged:
        lr = float(asg["lr"])
        if lr not in references:
            references[lr] = cell.family.reference_series(cell.sizes, cell.traffic, seed, lr)
        for k, v in cell.family.compare(series, references[lr]).items():
            limit = limit_of(cell.limits[k], asg)
            row = {"value": v, "limit": limit}
            (compared if limit is not None else not_compared)[prefix + k] = row
    reference_s = time.perf_counter() - t_ref
    log(f"reference: trial {sampled} lr {assignment['lr']} and the warm-up trial in {reference_s:.2f} s")
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in compared.values())

    result = {
        "correct": bool(correct),
        "attempted": len(done) + len(failed),
        "failed": len(failed),
        "metrics": {},
        "device": device,
    }
    if not trace:
        values = {
            "trials_per_hour": len(done) * 3600.0 / (last_end - t0_wall),
            "setup_s": setup_s,
        }
        for m in cell.metrics("end_to_end"):
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        path = profiler.path()
        if path is None:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        if keep_trace:
            os.makedirs(os.path.dirname(os.path.abspath(keep_trace)), exist_ok=True)
            shutil.copyfile(path, keep_trace)
        t_read = time.perf_counter()
        tr = trace_reduce.load_xplane(path)
        sl = trace_reduce.Slice(
            tr, t0_wall, profiler.t1, profiler.anchor_wall_ns, spans, cell.family.STEP_MODULE
        )
        log(f"trace: {os.path.getsize(path)} bytes read in {time.perf_counter() - t_read:.2f} s")
        result["device"]["busy_s"] = sl.busy_s
        result["device"]["window_s"] = sl.window_s
        result["breakdown"] = sl.breakdown()
        shutil.rmtree(profiler.directory, ignore_errors=True)
        # what a per-layer metric's reader is given
        context = {
            "cell": cell,
            "t0": t0_wall,
            "last_end": last_end,
            "spans": spans,
            "done": done,
            "compile_log": compile_log,
            "device": device,
            "peaks": peaks.peaks_of(device["kind"]),
            "slice": sl,
        }
        for m in cell.metrics("per_layer"):
            value = load_module("layer_metrics", m["name"]).read(context)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["reference_s"] = reference_s
    result["sampled"] = {"trial": sampled, "lr": assignment["lr"], "trials": len(done)}
    result["not_compared"] = not_compared
    result["checks"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument(
        "--allow-cpu", action="store_true",
        help="rehearse without a TPU; never prints the result line",
    )
    ap.add_argument("--benchmark", default=None, help="another BENCHMARK.json (tests)")
    ap.add_argument("--keep-trace", default=None, help="copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)

    cell = Cell(args.workload, args.benchmark)
    seconds = args.seconds if args.seconds is not None else cell.bench["run_seconds"]

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(
            f"run.py: JAX found no accelerator (platform {dev.platform!r}); "
            "the benchmark needs a TPU (rehearse with --allow-cpu)",
            file=sys.stderr,
        )
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: cell {cell.name} needs {cell.chips} chips, JAX reports {len(devices)}", file=sys.stderr)
        return 2
    if dev.platform == "tpu":
        peaks.peaks_of(dev.device_kind)  # a chip without a row of peaks is an error

    result = run_cell(cell, args.seed, seconds, bool(args.trace), keep_trace=args.keep_trace)
    for name, c in result["not_compared"].items():
        print(f"not compared {name} {c['value']!r}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    if args.allow_cpu and dev.platform != "tpu":
        log("rehearsal passed; no result line without a TPU")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
