#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (``limits/<cell>.json``).

    python3 benchmark/calibrate.py --workload <cell> --seeds 11,12,... --control-seeds 11,12,13

Not part of a benchmark run.  For each seed and each learning rate of the
cell's traffic: one trial through the orchestrator at the cell's own sizes (the
program's reading: its gaps to the plain reference) and, on the control seeds,
the reference put in the program's place in the precisions below the
configuration's (``fp8``; ``bf16`` as a second witness of the program's own
precision) and with the faults a cell can have planted in it (``half_batch``,
``state_unchanged``).  One JSON line a reading, on standard output and in
``chiprun_out/calibrate_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--benchmark", default=None)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="fp8,bf16,half_batch,state_unchanged")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload, args.benchmark)
    import jax

    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("calibrate.py: no TPU", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    out_dir = os.path.join(run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"calibrate_{cell.name}.jsonl"), "a")

    def emit(**row):
        row["platform"] = jax.devices()[0].platform
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    family = cell.family
    workdir = os.path.join(run.OUT, cell.name, "calibrate")
    for seed in seeds:
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        program = {}
        for i, lr in enumerate(family.lr_values(cell.traffic)):
            t0 = time.perf_counter()
            orch, exp = run.run_experiment(cell, f"cal-{i}", workdir, seed, lr_values=[lr], max_trials=1)
            (trial,) = exp.trials.values()
            if trial.condition.value != "Succeeded":
                raise RuntimeError(f"seed {seed} lr {lr}: trial ended {trial.condition.value}: {exp.message}")
            program[lr] = run.trial_series(orch, trial.name)
            run.log(f"seed {seed} lr {lr}: trial in {time.perf_counter() - t0:.1f} s")
            del orch, exp
        jax.clear_caches()
        for lr, series in program.items():
            t0 = time.perf_counter()
            reference = family.reference_series(cell.sizes, cell.traffic, seed, lr)
            emit(
                kind="program", cell=cell.name, seed=seed, lr=lr, reference_s=time.perf_counter() - t0,
                reference=reference, series={m: {s: series[m].get(s) for s in family.COMPARE_STEPS} for m in family.METRICS},
                **family.compare(series, reference),
            )
            if seed not in control_seeds:
                continue
            for control in [c for c in args.controls.split(",") if c]:
                kw = {"precision": control} if control in ("fp8", "bf16") else {"fault": control}
                got = family.reference_series(cell.sizes, cell.traffic, seed, lr, **kw)
                emit(kind=control, cell=cell.name, seed=seed, lr=lr, series=got, **family.compare(got, reference))
    return 0


if __name__ == "__main__":
    sys.exit(main())
