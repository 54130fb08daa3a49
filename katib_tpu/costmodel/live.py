"""Live roofline telemetry — the ambient cost slot and the MFU gauges.

The runner's heartbeat seams (``runner/trial_runner.py`` ``_beat``,
``runner/cohort.py`` ``_beat``, the DARTS epoch block) know *when* work
happened but never hold the jitted objects; model code holds the jitted
objects but doesn't own the clocks.  The bridge is the same ambient
per-thread pattern ``utils/tracing.py`` uses for tracers:

- model code observes its program once per trial
  (:func:`observe_program` — memoized, one extra trace, no compile) and
  the record lands in this thread's slot;
- the heartbeat reads :func:`active_cost`, divides by the measured
  report interval, and publishes :func:`publish_dispatch`'s gauges —
  ``katib_dispatch_mfu``, ``katib_arithmetic_intensity``,
  ``katib_roofline_headroom`` — plus span attrs for the trial/cohort/
  darts.epoch spans.

``per_report`` is the model's declaration of granularity: how many
dispatches of the observed program one ``ctx.report`` interval covers
(1 for a scan-epoch program reporting per epoch; the per-epoch batch
count for a streamed per-batch step).  Everything is best-effort — a
failed observation leaves the slot empty and the heartbeat publishes
nothing.
"""

from __future__ import annotations

import threading
from typing import Any

from katib_tpu.analysis import make_lock
from katib_tpu.costmodel.peaks import DevicePeaks, peaks_for
from katib_tpu.costmodel.record import CostRecord, extract_cost
from katib_tpu.utils import observability as obs

# label -> CostRecord | None (None pins a failed extraction so a sweep
# doesn't re-trace a program that cannot be costed, once per trial)
_MEMO: dict[Any, CostRecord | None] = {}
_MEMO_MAX = 128
_MEMO_LOCK = make_lock("costmodel.memo")

_tls = threading.local()


def observe_program(
    label: Any,
    fn: Any,
    args: tuple,
    *,
    program: str = "?",
    steps: int = 1,
    per_report: int = 1,
    dtype: str = "bf16",
) -> CostRecord | None:
    """Extract (memoized by ``label``) the cost of jitted ``fn`` at
    ``args`` and arm this thread's active-cost slot with it.

    ``label`` should be process-stable for one compiled program (e.g.
    the model/optimizer/mesh tuple the jit-step caches key by) so
    concurrent sweep trials sharing one executable trace it once.
    ``None`` or unhashable labels skip the memo (per-run programs like a
    DARTS search's window fn).  Never raises.
    """
    try:
        try:
            hashable = label is not None and (hash(label) or True)
        except TypeError:
            hashable = False
        rec = None
        hit = False
        if hashable:
            with _MEMO_LOCK:
                if label in _MEMO:
                    rec, hit = _MEMO[label], True
        if not hit:
            rec = extract_cost(fn, args, program=program, steps=steps, dtype=dtype)
            if hashable:
                with _MEMO_LOCK:
                    _MEMO[label] = rec
                    while len(_MEMO) > _MEMO_MAX:
                        _MEMO.pop(next(iter(_MEMO)))
        if rec is not None:
            set_active_cost(rec, per_report=per_report)
        return rec
    except Exception:
        return None


def set_active_cost(rec: CostRecord, per_report: int = 1) -> None:
    """Arm the calling thread's slot directly (models with their own
    cost accounting, tests)."""
    _tls.cost = rec
    _tls.per_report = max(1, int(per_report))


def active_cost() -> tuple[CostRecord, int] | None:
    """This thread's (record, per_report), or None when nothing observed."""
    rec = getattr(_tls, "cost", None)
    if rec is None:
        return None
    return rec, getattr(_tls, "per_report", 1)


def clear_active() -> None:
    """Disarm the slot (trial start: executor threads are reused, and a
    stale record from the previous trial must not leak into this one)."""
    _tls.cost = None
    _tls.per_report = 1
    _tls.attrs = {}


def span_attrs() -> dict:
    """Cost attrs of this thread's most recent publication — stamped on
    trial/cohort spans by whoever owns the span."""
    return dict(getattr(_tls, "attrs", {}) or {})


# backwards-friendly alias used by the package __init__
take_span_attrs = span_attrs


def publish_dispatch(
    rec: CostRecord,
    step_secs: float,
    *,
    workload: str,
    peaks: DevicePeaks | None = None,
) -> dict:
    """Publish the roofline gauges for one measured per-step time and
    return the span attrs (also retained for :func:`span_attrs`).

    - ``katib_dispatch_mfu`` — measured flops/s over peak flops
    - ``katib_arithmetic_intensity`` — flops per byte accessed
    - ``katib_roofline_headroom`` — measured step time over the binding
      roofline floor (1.0 = running at the roofline; 10 = 10x off it)
    """
    try:
        if step_secs <= 0 or not rec.flops:
            return {}
        pk = peaks or peaks_for()
        roof = rec.roofline(pk)
        mfu = rec.mfu(step_secs, pk)
        floor = roof["floor_step_secs"]
        headroom = step_secs / floor if floor else 0.0
        obs.dispatch_mfu.set(
            mfu, workload=workload, device_kind=pk.device_kind, dtype=rec.dtype
        )
        obs.arithmetic_intensity.set(
            roof["arithmetic_intensity"], workload=workload
        )
        obs.roofline_headroom.set(
            headroom, workload=workload, bound=roof["bound"]
        )
        attrs = {
            "mfu": round(mfu, 6),
            "arithmetic_intensity": round(roof["arithmetic_intensity"], 2),
            "roofline": roof["bound"],
            "roofline_headroom": round(headroom, 1),
        }
        _tls.attrs = attrs
        return dict(attrs)
    except Exception:
        return {}  # gauges are telemetry, never a trial failure
