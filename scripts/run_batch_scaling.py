"""Batch-scaling study for the flagship bilevel step on the TPU.

The honest batch-64 measurement (artifacts/flagship/bench_tpu.json,
~535 ms/step, 0.56% MFU) is small-op/tile-padding-bound, so throughput
should scale sub-linearly-in-time with batch — this harness measures how
far.  Each configuration runs through ``bench.py`` itself, one process per
point (same fetch-forced timing), so a scaling point is produced by exactly
the code the bench runs.

Safety: every configuration must carry a committed deviceless-AOT block
proving ``hbm_fits_v5e`` before this script will submit it to the chip.
Missing AOT memo => the config is SKIPPED with a note, never attempted.

Artifacts: ``artifacts/flagship/batch_scaling.json``.
Env knobs: SCALING_CONFIGS (comma list like ``64:none,128:dots``; extra
``:``-separated variant fields select program variants — ``ph`` adds the
paired-Hessian step (fit-proof looked up under the matching ``_pairhess``
tag), ``w<N>`` runs the bench child's fused step loop with an N-step scan
window (``BENCH_STEP_LOOP_WINDOW``), e.g. ``128:dots:ph:w8``),
BENCH_STEPS per point (default 5).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import REPO, artifacts_root, write_artifact  # noqa: E402

RESULT_PREFIX = '{"metric"'


def parse_configs(raw: str) -> list[tuple[int, str | None, bool, int | None]]:
    out: list[tuple[int, str | None, bool, int | None]] = []
    for part in raw.split(","):
        fields = [f.strip() for f in part.strip().split(":")]
        batch = int(fields[0])
        policy = fields[1] if len(fields) > 1 and fields[1] not in ("", "none") else None
        pairhess = False
        window: int | None = None
        # fail fast on anything unrecognized: a typo'd variant that silently
        # parsed as the non-variant would burn a fit-proof-gated chip point
        # on the wrong program and only surface after the window ends
        for f in fields[2:]:
            if f == "ph":
                pairhess = True
            elif len(f) > 1 and f[0] == "w" and f[1:].isdigit() and int(f[1:]) >= 1:
                window = int(f[1:])
            else:
                raise ValueError(
                    f"unknown variant field {f!r} in {part!r} "
                    "(only 'ph' and 'w<N>')"
                )
        out.append((batch, policy, pairhess, window))
    return out


def aot_block_for(batch: int, policy: str | None, pairhess: bool = False) -> dict | None:
    """The committed deviceless-AOT evidence for this config, or None."""
    if policy is None and batch == 64 and not pairhess:
        name = "aot_v5e.json"
    else:
        tag = f"b{batch}" + ("_remat" if policy is not None else "")
        if policy:
            tag += f"_{policy}"
        if pairhess:
            tag += "_pairhess"
        name = f"aot_v5e_{tag}.json"
    try:
        with open(os.path.join(artifacts_root(), "flagship", name)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _flush(points: list[dict]) -> dict:
    """Rewrite batch_scaling.json with the points measured SO FAR.  Called
    after every point: a caller that kills this script at a time limit
    would otherwise lose every already-measured chip point with it."""
    result = {
        "what": (
            "flagship second-order bilevel step throughput vs batch size; "
            "each point measured by bench.py's fetch-forced child on the "
            "chip, submitted only with committed AOT HBM-fit proof"
        ),
        "points": points,
    }
    write_artifact("flagship", "batch_scaling.json", result)
    return result


def main() -> int:
    configs = parse_configs(os.environ.get("SCALING_CONFIGS", "64:none,128:dots"))
    steps = os.environ.get("BENCH_STEPS", "5")
    points: list[dict] = []
    for batch, policy, pairhess, window in configs:
        # the scan window chunks dispatches of the SAME per-step program —
        # donated carry, no extra live activations — so the fit-proof is
        # keyed on (batch, policy, pairhess) only
        aot = aot_block_for(batch, policy, pairhess)
        if aot is None or not aot.get("hbm_fits_v5e"):
            points.append(
                {
                    "batch": batch,
                    "remat_policy": policy,
                    "paired_hessian": pairhess,
                    "skipped": True,
                    "reason": (
                        "no committed AOT fit-proof — run the deviceless "
                        "AOT first"
                        if aot is None
                        else f"AOT says {aot['hbm_gib']} GiB > v5e HBM"
                    ),
                }
            )
            _flush(points)
            continue
        env = dict(os.environ)
        env.update(BENCH_BATCH=str(batch), BENCH_STEPS=steps)
        if policy is not None:
            env.update(BENCH_REMAT="1", BENCH_REMAT_POLICY=policy)
        else:
            env.pop("BENCH_REMAT", None)
            env.pop("BENCH_REMAT_POLICY", None)
        if pairhess:
            env["BENCH_PAIRED_HESSIAN"] = "1"
        else:
            env.pop("BENCH_PAIRED_HESSIAN", None)
        if window is not None:
            env["BENCH_STEP_LOOP_WINDOW"] = str(window)
        else:
            env.pop("BENCH_STEP_LOOP_WINDOW", None)
        print(
            f"scaling: batch={batch} policy={policy} pairhess={pairhess}"
            f" window={window} ...",
            flush=True,
        )
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(REPO, "bench.py")],
                capture_output=True,
                text=True,
                env=env,
                timeout=float(os.environ.get("SCALING_POINT_TIMEOUT", "3000")),
            )
        except subprocess.TimeoutExpired:
            # one hung point must not lose the points already measured
            points.append(
                {
                    "batch": batch,
                    "remat_policy": policy,
                    "paired_hessian": pairhess,
                    "failed": True,
                    "timeout": True,
                }
            )
            _flush(points)
            continue
        rec: dict | None = None
        for line in (proc.stdout or "").splitlines():
            if line.startswith(RESULT_PREFIX):
                rec = json.loads(line)
        if rec is None:
            points.append(
                {
                    "batch": batch,
                    "remat_policy": policy,
                    "paired_hessian": pairhess,
                    "failed": True,
                    "stderr_tail": (proc.stderr or "")[-500:],
                }
            )
            _flush(points)
            continue
        point = {
            "batch": batch,
            "remat_policy": policy,
            "paired_hessian": pairhess,
            "images_per_sec": rec["value"],
            "step_secs": rec["step_secs"],
            "mfu": rec["mfu"],
            "platform": rec["platform"],
            "aot_hbm_gib": aot["hbm_gib"],
            "steps_per_dispatch": rec.get("steps_per_dispatch", 1),
        }
        fused = rec.get("fused_loop")
        if fused is not None:
            point["fused_loop"] = {
                "images_per_sec": fused["value"],
                "step_secs": fused["step_secs"],
                "steps_per_dispatch": fused["steps_per_dispatch"],
                "mfu": fused["mfu"],
            }
        points.append(point)
        _flush(points)
        print(f"scaling:   -> {rec['value']} img/s ({rec['step_secs']}s/step)", flush=True)
        if fused is not None:
            print(
                f"scaling:   -> fused x{fused['steps_per_dispatch']}: "
                f"{fused['value']} img/s ({fused['step_secs']}s/step)",
                flush=True,
            )

    result = _flush(points)
    print(json.dumps(result["points"]), flush=True)
    ok = any("images_per_sec" in p for p in points)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
