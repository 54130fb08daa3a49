"""Span tracing — wall-clock attribution for every pipeline stage.

The counters in ``utils.observability`` say *what happened*; this module says
*where the time went*.  Podracer-style TPU systems attribute every wall-clock
second to a pipeline stage before optimizing it — suggestion latency, trial
queueing, XLA compile, per-step training — so the orchestrator opens one
:class:`Tracer` per experiment and every layer (orchestrator, suggesters,
trial runner, NAS loops) records spans into it:

- ``Tracer.span(name, **attrs)`` — context manager measuring ``perf_counter``
  intervals; each finished span is one JSONL line in
  ``<workdir>/<experiment>/trace.jsonl`` (the trace journal).
- the journal is append-only and restart-safe: a resumed experiment
  continues from the previous max elapsed offset (the same monotonic-base
  pattern ``darts/search.py`` uses for ``elapsed_s``), so a single export
  covers the experiment's whole life across process restarts.
- spans carry experiment/trial IDs in ``args`` so one export reconstructs
  the full lifecycle of e.g. a 32-trial Hyperband sweep.
- every record has an ``id`` and, below a root, the ``parent`` that was open
  on the same thread when it started; a child that names no ``trial`` takes
  its parent's, so one trial's records share its name.
- an open span accumulates numbers (``Span.add``): the jax listeners below
  add what jax says it traced, lowered, loaded and compiled to every span
  open on the compiling thread, and journal the long ones as ``jit.*`` spans.
- while jax is imported an open span is also a
  ``jax.profiler.TraceAnnotation`` that carries the record's ``id`` (and its
  trial's name): a profiler capture shows the program's spans on the host
  plane, on the clock of the device operations, and each event names its
  journal line.  This module never imports jax itself (the simulator runs
  without it).

Layers below the orchestrator don't hold a Tracer reference; they use the
ambient per-thread tracer (``activate``/``use_tracer`` set it, the
module-level :func:`span` / :func:`record_span` pick it up and no-op when
none is active — instrumented code stays runnable standalone).

Export: ``to_chrome_trace`` converts journal records to Chrome-trace JSON
(the ``traceEvents`` array Perfetto and ``chrome://tracing`` load directly);
``summarize`` aggregates latency distributions per span name.  CLI verbs
``katib-tpu trace export`` / ``trace summary`` wrap both.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Iterator

TRACE_FILE = "trace.jsonl"

TRACE_ENV = "KATIB_TRACE"


def enabled() -> bool:
    """Span-tracing kill switch: ``KATIB_TRACE=0`` (or ``false``/``off``)
    suppresses the per-experiment trace journal.  Tracing is best-effort by
    contract, and at sweep scale (tens of thousands of short trials — e.g.
    the virtual-time simulator) the per-span write+flush is pure overhead."""
    return os.environ.get(TRACE_ENV, "1").strip().lower() not in (
        "0",
        "false",
        "off",
    )


def trace_path(workdir: str, experiment_name: str) -> str:
    return os.path.join(workdir, experiment_name, TRACE_FILE)


class Span:
    """Handle yielded by ``span(...)``: collects attributes to attach when
    the span closes (``sp.set(condition="Succeeded")``) and numbers counted
    while it is open (``sp.add("jit_programs", 1)``)."""

    __slots__ = ("name", "attrs", "id", "up", "tracer", "counters")

    def __init__(
        self,
        name: str,
        attrs: dict[str, Any],
        span_id: int = 0,
        up: "Span | None" = None,
        tracer: "Tracer | None" = None,
    ):
        self.name = name
        self.attrs = attrs
        self.id = span_id
        # the span this one was opened inside, on the same thread
        self.up = up
        self.tracer = tracer
        self.counters: dict[str, float] = {}

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)

    def add(self, key: str, x: float) -> None:
        """Add ``x`` to counter ``key`` of this span and of every span it was
        opened inside: a span's counters mean "inside me".  Written into
        ``args`` when the span closes."""
        sp: Span | None = self
        while sp is not None:
            sp.counters[key] = sp.counters.get(key, 0) + x
            sp = sp.up


class _NullSpan(Span):
    """Returned when no tracer is active; absorbs ``set`` and ``add`` calls."""

    def __init__(self) -> None:
        super().__init__("", {})

    def set(self, **attrs: Any) -> None:
        pass

    def add(self, key: str, x: float) -> None:
        pass


_NULL_SPAN = _NullSpan()

# per thread: the ambient tracer (``tracer``), the spans open on the thread
# innermost last (``stack``), and what the jax listeners keep between two
# events of one thread (``traced``, ``cache``)
_active = threading.local()


def _inherit(attrs: dict[str, Any], up: Span | None) -> dict[str, Any]:
    """A record that names no ``trial`` (or ``experiment``) takes that of the
    span it lies in, so one trial's records share its name."""
    if up is not None:
        for key in ("trial", "experiment"):
            if key in up.attrs:
                attrs.setdefault(key, up.attrs[key])
    return attrs


def _journal_resume_point(path: str) -> tuple[float, int]:
    """Max ``ts + dur`` and max ``id`` over an existing journal — the
    monotonic elapsed base and the id counter a resumed experiment continues
    from (0.0 and 0 for a fresh journal)."""
    base, last_id = 0.0, 0
    try:
        with open(path, errors="replace") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail write from a crash mid-append
                if isinstance(rec, dict):
                    try:
                        end = float(rec.get("ts", 0.0)) + float(rec.get("dur", 0.0))
                        last_id = max(last_id, int(rec.get("id", 0)))
                    except (TypeError, ValueError):
                        continue
                    base = max(base, end)
    except OSError:
        return 0.0, 0
    return base, last_id


class Tracer:
    """Thread-safe span recorder appending to one experiment's trace journal.

    Every write is one line + flush so the journal survives a crash with at
    most the in-flight span lost; recording is best-effort (a full disk must
    never fail the experiment)."""

    def __init__(self, path: str, experiment: str | None = None):
        self.path = path
        self.experiment = experiment
        self._lock = threading.Lock()
        base, last_id = _journal_resume_point(path)
        self._ids = itertools.count(last_id + 1)
        # the two clocks read back to back: ``ts`` runs on perf_counter and
        # ``wall`` is the same instant on time.time(), to the microsecond
        wall_ns, perf_ns = time.time_ns(), time.perf_counter_ns()
        # elapsed base continues across restarts so ts stays monotonic over
        # the experiment's whole life (darts/search.py elapsed_s pattern)
        self._t0 = perf_ns * 1e-9 - base
        # wall-clock anchor for ts→epoch conversion in exported traces
        self._wall_anchor = wall_ns * 1e-9 - base
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._fh = open(path, "a")
        self._closed = False
        _hook_jax()

    def elapsed(self) -> float:
        """Seconds since experiment start (monotonic across restarts)."""
        return time.perf_counter() - self._t0

    def _enclosing(self) -> Span | None:
        """The innermost span of this tracer open on the calling thread."""
        stack = getattr(_active, "stack", None)
        if stack and stack[-1].tracer is self:
            return stack[-1]
        return None

    def record(self, name: str, start_s: float, dur_s: float, **attrs: Any) -> None:
        """Append one finished span (``start_s`` in journal-elapsed seconds),
        a child of the innermost span open on the calling thread."""
        up = self._enclosing()
        self._write(name, start_s, dur_s, _inherit(attrs, up), next(self._ids), up)

    def _write(
        self,
        name: str,
        start_s: float,
        dur_s: float,
        attrs: dict[str, Any],
        span_id: int,
        up: Span | None,
    ) -> None:
        rec: dict[str, Any] = {"name": name, "id": span_id}
        if up is not None:
            rec["parent"] = up.id
        rec.update(
            ts=round(start_s, 6),
            dur=round(max(dur_s, 0.0), 6),
            wall=round(self._wall_anchor + start_s, 6),
            pid=os.getpid(),
            tid=threading.get_ident(),
        )
        if self.experiment is not None:
            attrs.setdefault("experiment", self.experiment)
        if attrs:
            rec["args"] = attrs
        try:
            line = json.dumps(rec, default=str)
        except (TypeError, ValueError):
            return
        with self._lock:
            if self._closed:
                return
            try:
                self._fh.write(line + "\n")
                self._fh.flush()
            except (OSError, ValueError):
                pass  # tracing is best-effort; never fail the experiment

    @contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Span]:
        up = self._enclosing()
        sp = Span(name, _inherit(attrs, up), next(self._ids), up, self)
        stack = _active.__dict__.setdefault("stack", [])
        stack.append(sp)
        jax = _hook_jax()
        annotation = nullcontext()
        if jax is not None:
            # the same interval on the profiler's clock, in any capture; ``id``
            # (and the trial) join the host-plane event to this record.  jax
            # encodes the keywords into the event only while a capture runs.
            ident = {"trial": str(sp.attrs["trial"])} if "trial" in sp.attrs else {}
            annotation = jax.profiler.TraceAnnotation(name, id=sp.id, **ident)
        start = self.elapsed()
        try:
            with annotation:
                yield sp
        except BaseException as e:
            sp.attrs.setdefault("error", type(e).__name__)
            raise
        finally:
            dur = self.elapsed() - start
            stack.remove(sp)
            if not stack:
                _active.__dict__.pop("traced", None)
            for key, x in sp.counters.items():
                sp.attrs[key] = round(x, 6) if isinstance(x, float) else x
            self._write(name, start, dur, sp.attrs, sp.id, up)

    def close(self) -> None:
        with self._lock:
            self._closed = True
            try:
                self._fh.close()
            except OSError:
                pass


# -- ambient per-thread tracer ------------------------------------------------


def current_tracer() -> Tracer | None:
    return getattr(_active, "tracer", None)


def activate(tracer: Tracer | None) -> Tracer | None:
    """Set the calling thread's ambient tracer; returns the previous one
    (pass it back to :func:`deactivate` to restore)."""
    prev = current_tracer()
    _active.tracer = tracer
    return prev


def deactivate(prev: Tracer | None) -> None:
    _active.tracer = prev


@contextmanager
def use_tracer(tracer: Tracer | None) -> Iterator[Tracer | None]:
    prev = activate(tracer)
    try:
        yield tracer
    finally:
        deactivate(prev)


@contextmanager
def span(name: str, **attrs: Any) -> Iterator[Span]:
    """Span on the ambient tracer; no-op (null span) when none is active."""
    tracer = current_tracer()
    if tracer is None:
        yield _NULL_SPAN
        return
    with tracer.span(name, **attrs) as sp:
        yield sp


def current_span() -> Span:
    """The innermost span of the ambient tracer open on the calling thread,
    for code that counts into whatever span it runs inside (``Span.add``);
    the null span where there is none."""
    tracer = current_tracer()
    return (tracer._enclosing() if tracer is not None else None) or _NULL_SPAN


def record_span(name: str, dur_s: float, **attrs: Any) -> None:
    """Record a span that ended *now* with the given duration — for code
    that measures intervals itself (e.g. time between epoch callbacks)."""
    tracer = current_tracer()
    if tracer is not None:
        end = tracer.elapsed()
        tracer.record(name, end - dur_s, dur_s, **attrs)


# -- jax's compile events, as counters on the open spans ------------------------

# an event this long is also journaled as a span, by program name: the step
# and eval programs of a trial are, the eager one-op programs are counted only
JIT_SPAN_MIN_S = 0.05

# the totals a ``train_fn`` span carries (trial_runner seeds them with 0)
JIT_COUNTERS = (
    "jit_trace_s",
    "jit_lower_s",
    "jit_backend_s",
    "jit_programs",
    "cache_hits",
    "cache_misses",
)

# event -> (span name, counter).  The backend event wraps jax's
# compile_or_get_cached: on a warm persistent cache it is lookup plus
# executable load.
_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jit.trace", "jit_trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("jit.lower", "jit_lower_s"),
    "/jax/core/compile/backend_compile_duration": ("jit.backend", "jit_backend_s"),
}
# event -> (``cache`` of the program's ``jit.backend`` span, counter)
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": ("hit", "cache_hits"),
    "/jax/compilation_cache/cache_misses": ("miss", "cache_misses"),
}

_listeners_lock = threading.Lock()
_listeners_registered = False


def _hook_jax():
    """The ``jax`` module once something else has imported it, else ``None``
    (this module never imports it).  The first sight of it registers the two
    listeners, once a process."""
    global _listeners_registered
    jax = sys.modules.get("jax")
    if not hasattr(jax, "profiler") or not hasattr(jax, "monitoring"):
        return None  # not imported, or still being imported
    if not _listeners_registered:
        with _listeners_lock:
            if not _listeners_registered:
                jax.monitoring.register_event_time_span_listener(_on_jax_time_span)
                jax.monitoring.register_event_listener(_on_jax_event)
                _listeners_registered = True
    return jax


def _uncounted_trace_seconds(start: float, end: float) -> float:
    """Seconds of a trace event not yet counted on this thread.  A jitted
    function called while another is traced reports first and lies inside
    the outer one's interval, so the thread's tracing time is the union of
    the intervals, not their sum.  Kept while a span is open on the thread."""
    counted = _active.__dict__.setdefault("traced", [])
    inside = 0.0
    while counted and counted[-1][0] >= start:
        a, b = counted.pop()
        inside += b - a
    counted.append((start, end))
    return max(end - start - inside, 0.0)


def _on_jax_time_span(event: str, start_time: float, end_time: float, **kwargs: Any) -> None:
    """jax.monitoring time-span listener; runs on the thread that compiles,
    which is the trial's, so the ambient tracer is the trial's."""
    tracer = current_tracer()
    if tracer is None or event not in _JIT_EVENTS:
        return
    name, counter = _JIT_EVENTS[event]
    dur = max(end_time - start_time, 0.0)
    attrs = {"program": kwargs.get("fun_name")}
    if name == "jit.backend":
        cache = _active.__dict__.pop("cache", None)
        if cache is not None:
            attrs["cache"] = cache
    inner = tracer._enclosing()
    if inner is not None:
        if name == "jit.trace":
            inner.add(counter, _uncounted_trace_seconds(start_time, end_time))
        else:
            inner.add(counter, dur)
        if name == "jit.lower":
            inner.add("jit_programs", 1)  # one per program really built
    if dur >= JIT_SPAN_MIN_S:
        # the event's ends are time.time() values
        tracer.record(name, start_time - tracer._wall_anchor, dur, **attrs)


def _on_jax_event(event: str, **kwargs: Any) -> None:
    """jax.monitoring event listener: persistent-cache hits and misses.  Both
    fire inside the backend event of the same program, which reads ``cache``."""
    tracer = current_tracer()
    if tracer is None or event not in _CACHE_EVENTS:
        return
    _active.cache, counter = _CACHE_EVENTS[event]
    inner = tracer._enclosing()
    if inner is not None:
        inner.add(counter, 1)


# -- journal readers / exporters ---------------------------------------------


def read_journal(path: str) -> list[dict]:
    """Parse a trace journal, skipping torn/corrupt lines (crash mid-append)."""
    records: list[dict] = []
    try:
        with open(path, errors="replace") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "name" in rec and "ts" in rec:
                    records.append(rec)
    except OSError:
        return []
    return records


def to_chrome_trace(records: list[dict]) -> dict:
    """Journal records → Chrome-trace JSON object format (complete events),
    loadable by Perfetto / ``chrome://tracing`` as-is.  Timestamps are µs of
    journal-elapsed time, so restarts stay on one monotonic axis."""

    def _num(rec: dict, key: str) -> float:
        try:
            return float(rec.get(key, 0.0))
        except (TypeError, ValueError):
            return 0.0

    events: list[dict] = []
    pids: set = set()
    for rec in records:
        pid = rec.get("pid", 0)
        pids.add(pid)
        args = dict(rec.get("args", {}))
        args.update({k: rec[k] for k in ("id", "parent") if k in rec})
        events.append(
            {
                "name": str(rec.get("name", "?")),
                "cat": "katib",
                "ph": "X",
                "ts": round(_num(rec, "ts") * 1e6, 3),
                "dur": round(_num(rec, "dur") * 1e6, 3),
                "pid": pid,
                "tid": rec.get("tid", 0),
                "args": args,
            }
        )
    # process metadata rows label each restart's process in the viewer
    for pid in sorted(pids, key=str):
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": f"katib-tpu pid {pid}"},
            }
        )
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _covered(intervals: list[tuple[float, float]], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    total, cursor = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, cursor), min(b, hi)
        if b > a:
            total += b - a
            cursor = b
    return total


def summarize(records: list[dict]) -> list[dict]:
    """Latency distribution per span name: count, total/self/mean/p50/p95/max
    seconds — ordered by total descending (where the wall-clock went).
    ``self_s`` is a span's duration minus what its children (records whose
    ``parent`` is its ``id``) cover: the time no span below it accounts for."""
    by_name: dict[str, list[float]] = {}
    timed: list[tuple[dict, float, float]] = []
    children: dict[Any, list[tuple[float, float]]] = {}
    for rec in records:
        try:
            ts, dur = float(rec.get("ts", 0.0)), float(rec.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        by_name.setdefault(str(rec.get("name", "?")), []).append(dur)
        timed.append((rec, ts, dur))
        if "parent" in rec:
            children.setdefault(rec["parent"], []).append((ts, ts + dur))
    self_by_name: dict[str, float] = {}
    for rec, ts, dur in timed:
        below = _covered(children.get(rec.get("id"), []), ts, ts + dur)
        name = str(rec.get("name", "?"))
        self_by_name[name] = self_by_name.get(name, 0.0) + dur - below
    out = []
    for name, durs in by_name.items():
        durs.sort()
        total = sum(durs)
        out.append(
            {
                "name": name,
                "count": len(durs),
                "total_s": round(total, 6),
                "self_s": round(self_by_name[name], 6),
                "mean_s": round(total / len(durs), 6),
                "p50_s": round(_percentile(durs, 0.50), 6),
                "p95_s": round(_percentile(durs, 0.95), 6),
                "max_s": round(durs[-1], 6),
            }
        )
    out.sort(key=lambda r: r["total_s"], reverse=True)
    return out


def export_chrome_trace(journal_path: str, out_path: str) -> int:
    """Read a journal, write Chrome-trace JSON to ``out_path``; returns the
    number of span events exported (0 when the journal is missing/empty)."""
    records = read_journal(journal_path)
    if not records:
        return 0
    doc = to_chrome_trace(records)
    parent = os.path.dirname(out_path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, out_path)
    return len(records)
