#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) and the program's spans to numbers.

The reduction every PR uses, so that no PR that claims a gain can change it:

- ``load_xplane``: the device planes' lines and the benchmark's anchor event,
  as plain lists (``jax.profiler.ProfileData`` reads the file);
- ``Slice``: one traced slice of the window on the host's wall clock: the
  seconds in which an operation ran on the device (union of the ``XLA Ops``
  intervals), the device operations that took most time, the idle gaps by what
  the host was doing, the events of one jitted module and of one kernel.

``python benchmark/trace_reduce.py --selfcheck`` reduces the small recorded
trace kept in ``testdata/`` and compares with the numbers recorded beside it.
``python benchmark/trace_reduce.py --trim <xplane.pb> <out.json>`` makes such a
recording.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANCHOR = "benchmark.anchor"
DEVICE_PREFIX = "/device:TPU:"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def load_xplane(path: str) -> dict:
    """``{"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "anchor_ns": start of the anchor event or None}``.  On a TPU an event of
    the ``XLA Ops`` line is named by its whole HLO text, which is where a
    Pallas kernel shows (``custom_call_target="tpu_custom_call"``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    anchor = None
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PREFIX) and plane.name[len(DEVICE_PREFIX):].isdigit()
        if is_device:
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                rows = lines.setdefault(line.name, [])
                for ev in line.events:
                    rows.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
        elif anchor is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = float(ev.start_ns)
                        break
                if anchor is not None:
                    break
    return {"devices": devices, "anchor_ns": anchor}


def short_name(hlo: str) -> str:
    """``opcode output-shape`` of an operation named by its HLO text, with the
    target of a custom call: operations of one kind and shape read as one row
    (``fusion f32[8,1023,50257]``, ``custom-call:tpu_custom_call
    bf16[8,12,1024,64]``)."""
    lhs, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    shape = rest.lstrip("(").split("{")[0].split(" ")[0]
    m = re.search(r"[\s)]([a-z][\w\-]*)\(", rest)
    opcode = m.group(1) if m else lhs.lstrip("%").rstrip("0123456789.")
    t = re.search(r'custom_call_target="([^"]+)"', rest)
    if t:
        opcode += ":" + t.group(1)
    return f"{opcode} {shape}"[:80]


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of ``(t0, t1)`` intervals and the merged
    intervals themselves."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


class Slice:
    """One traced slice on the wall clock (seconds, ``time.time()``)."""

    def __init__(
        self, trace: dict, t0: float, t1: float, anchor_wall_ns: float, spans: list[dict],
        step_module: str = "jit_step_fn",
    ):
        self.t0, self.t1 = t0, t1
        self.step_module = step_module
        self._train_bounds: dict = {}
        self.window_s = t1 - t0
        self.spans = spans
        if trace["anchor_ns"] is None:
            raise RuntimeError("the trace holds no benchmark.anchor event: cannot place it on the host's clock")
        offset = anchor_wall_ns - trace["anchor_ns"]
        self.devices: dict = {}
        for plane, lines in trace["devices"].items():
            self.devices[plane] = {
                line: [
                    (name, (start + offset) * 1e-9, (start + dur + offset) * 1e-9)
                    for name, start, dur in rows
                ]
                for line, rows in lines.items()
            }
        used = {p: lines for p, lines in self.devices.items() if lines.get(OPS_LINE)}
        if not used:
            raise RuntimeError("no operation ran on a device inside the traced slice")
        self.used = used
        busy = []
        self.busy_intervals: dict = {}
        for plane, lines in used.items():
            seconds, merged = union_seconds(
                [(max(a, t0), min(b, t1)) for _n, a, b in lines[OPS_LINE] if b > t0 and a < t1]
            )
            busy.append(seconds)
            self.busy_intervals[plane] = merged
        self.busy_s = sum(busy) / len(busy)

    # -- events ---------------------------------------------------------------

    def ops(self) -> list[tuple]:
        return [ev for lines in self.used.values() for ev in lines[OPS_LINE]]

    def module_events(self, module: str) -> list[tuple]:
        """Executions of one jitted program (``jit_step_fn``) on the device."""
        return [
            ev
            for lines in self.used.values()
            for ev in lines.get(MODULES_LINE, [])
            if ev[0].split("(")[0] == module
        ]

    def kernel_events(self, mark: str) -> list[tuple]:
        """Device operations whose HLO text holds ``mark`` (a Pallas kernel:
        ``custom_call_target="tpu_custom_call"``)."""
        return [ev for ev in self.ops() if mark in ev[0]]

    # -- the breakdown the ledger keeps -----------------------------------------

    def host_state(self, t: float) -> str:
        """What the host was doing at ``t``, from the program's spans."""
        inside = lambda name: [s for s in self.spans if s["name"] == name and s["t0"] <= t <= s["t1"]]  # noqa: E731
        train = inside("train_fn")
        if train:
            key = train[0]["t0"]
            if key not in self._train_bounds:
                steps = [
                    ev for ev in self.module_events(self.step_module)
                    if train[0]["t0"] <= ev[1] <= train[0]["t1"]
                ]
                self._train_bounds[key] = (
                    min((ev[1] for ev in steps), default=None), max((ev[2] for ev in steps), default=None)
                )
            first, last = self._train_bounds[key]
            if first is None or t < first:
                return "train_fn before its first step (init, trace, lower, cache load)"
            if t > last:
                return "train_fn after its last step"
            return "train_fn between steps (dispatch, eval fetch, report)"
        if inside("trial"):
            return "trial outside train_fn (runner set-up and harvest)"
        if inside("suggest"):
            return "suggest"
        return "orchestrator between trials (schedule, settle, journal)"

    def breakdown(self) -> dict:
        by_name: dict[str, float] = {}
        for name, a, b in self.ops():
            if b > self.t0 and a < self.t1:
                key = short_name(name)
                by_name[key] = by_name.get(key, 0.0) + (min(b, self.t1) - max(a, self.t0))
        n = len(self.used)
        device_ops = sorted(((k, v / n) for k, v in by_name.items()), key=lambda kv: -kv[1])[:10]
        gaps: dict[str, float] = {}
        plane = sorted(self.busy_intervals)[0]
        cursor = self.t0
        for a, b in self.busy_intervals[plane] + [(self.t1, self.t1)]:
            if a > cursor:
                state = self.host_state(0.5 * (a + cursor))
                gaps[state] = gaps.get(state, 0.0) + (a - cursor)
            cursor = max(cursor, b)
        idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
        return {
            "device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps],
        }


# ---------------------------------------------------------------------------
# the recorded trace and its check
# ---------------------------------------------------------------------------

RECORDED = os.path.join(HERE, "testdata", "trace_gpt2s_steps.json.gz")


def summary(sl: "Slice") -> dict:
    steps = sl.module_events(sl.step_module)
    kernels = sl.kernel_events(KERNEL_MARK)
    return {
        "window_s": sl.window_s,
        "busy_s": sl.busy_s,
        "n_steps": len(steps),
        "step_s": sum(b - a for _n, a, b in steps),
        "kernel_events": len(kernels),
        "kernel_s": sum(b - a for _n, a, b in kernels),
        "top_op": sl.breakdown()["device_ops"][0][0],
    }


def trim(xplane: str, out: str, keep_steps: int = 3) -> None:
    """Keep the first ``keep_steps`` executions of ``jit_step_fn`` of the first
    device plane with their operations, and record what the reduction gives."""
    tr = load_xplane(xplane)
    plane = sorted(p for p, lines in tr["devices"].items() if lines.get(OPS_LINE))[0]
    lines = tr["devices"][plane]
    steps = [ev for ev in lines[MODULES_LINE] if ev[0].split("(")[0] == "jit_step_fn"][:keep_steps]
    lo, hi = steps[0][1], steps[-1][1] + steps[-1][2]
    lo -= 1000.0  # a microsecond of nothing before the first step
    kept = {
        line: [ev for ev in rows if ev[1] >= lo and ev[1] + ev[2] <= hi] for line, rows in lines.items()
    }
    small = {"devices": {plane: kept}, "anchor_ns": lo}
    t0 = 1000.0
    sl = Slice(small, t0, t0 + (hi - lo) * 1e-9, t0 * 1e9, [])
    expected = summary(sl)
    with gzip.open(out, "wt") as f:
        json.dump({"trace": small, "t0": t0, "expected": expected}, f)
    print(json.dumps(expected, indent=1))


def selfcheck() -> int:
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    tr, t0, want = rec["trace"], rec["t0"], rec["expected"]
    sl = Slice(tr, t0, t0 + want["window_s"], t0 * 1e9, [])
    got = summary(sl)
    bad = []
    for k, w in want.items():
        g = got[k]
        same = g == w if not isinstance(w, float) else abs(g - w) <= 1e-9 * max(1.0, abs(w))
        if not same:
            bad.append(f"{k}: reduced {g!r}, recorded {w!r}")
    # properties that hold whatever was recorded
    total, merged = union_seconds([(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)])
    if total != 4.0 or merged != [(0.0, 3.0), (5.0, 6.0)]:
        bad.append(f"union_seconds gave {total} {merged}")
    if not (0.0 < got["busy_s"] <= got["window_s"]):
        bad.append("busy seconds outside (0, window]")
    if got["kernel_s"] > got["busy_s"] or got["step_s"] > got["window_s"] * 1.000001:
        bad.append("a part is longer than the whole")
    for line in bad:
        print("selfcheck FAILED:", line)
    if not bad:
        print(f"selfcheck ok: {json.dumps(got)}")
    return 1 if bad else 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["--selfcheck"]:
        sys.exit(selfcheck())
    if sys.argv[1:2] == ["--trim"]:
        trim(sys.argv[2], sys.argv[3])
        sys.exit(0)
    print(__doc__)
    sys.exit(2)
