"""Roofline cost model — per-program XLA cost records and live MFU telemetry.

``bench.py`` has always known how expensive the flagship program is
(``compiled.cost_analysis()``), but that accounting was trapped in the
benchmark: ordinary trials, cohorts, and the dashboard ran blind to
utilization.  This package makes the roofline a first-class observability
layer:

- :mod:`peaks` — per-device-kind peak flops / HBM bandwidth table (the
  MFU denominator); a device the table does not hold raises, and the
  live gauges then publish nothing.
- :mod:`record` — :class:`CostRecord`: flops, bytes accessed, peak HBM,
  arithmetic intensity, roofline floors and memory/compute-bound
  classification for one compiled program; extraction helpers for
  ``Lowered`` / ``Compiled`` objects and live jitted functions.
- :mod:`live` — the ambient per-thread cost slot: model code that owns
  the jitted objects observes its program once
  (:func:`live.observe_program`); the runner/cohort heartbeat seams read
  the slot and publish ``katib_dispatch_mfu`` /
  ``katib_arithmetic_intensity`` / ``katib_roofline_headroom`` against
  measured step time (:func:`live.publish_dispatch`).
- :mod:`aot` — the deviceless TPU-topology AOT compile path shared with
  ``bench.py`` (cost analysis for a described chip — works on CPU
  hosts).
- :mod:`profiler` — on-demand ``jax.profiler`` capture with an
  in-process registry of trace directories (``/api/status`` and the
  ``katib-tpu profile --list`` verb read it).

Cost records persist at the ``CompileSignature`` seam: the shape
registry (``katib_tpu/compile/registry.py``) merges each program's cost
into its signature row in ``shape_registry.jsonl``, so ``katib-tpu
cost`` can print the roofline table of a sweep that ran yesterday.
Everything here is best-effort telemetry — an extraction failure must
never fail a trial.
"""

from __future__ import annotations

from katib_tpu.costmodel.live import (
    active_cost,
    clear_active,
    observe_program,
    publish_dispatch,
    set_active_cost,
    span_attrs,
)
from katib_tpu.costmodel.peaks import (
    DevicePeaks,
    UnknownDeviceKind,
    detect_device_kind,
    normalize_device_kind,
    peaks_for,
)
from katib_tpu.costmodel.record import (
    CostRecord,
    cost_of_compiled,
    cost_of_lowered,
    extract_cost,
)

__all__ = [
    "CostRecord",
    "DevicePeaks",
    "UnknownDeviceKind",
    "active_cost",
    "clear_active",
    "cost_of_compiled",
    "cost_of_lowered",
    "detect_device_kind",
    "extract_cost",
    "normalize_device_kind",
    "observe_program",
    "peaks_for",
    "publish_dispatch",
    "set_active_cost",
    "span_attrs",
]
