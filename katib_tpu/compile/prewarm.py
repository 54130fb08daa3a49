"""Background compile prewarm worker: strictly best-effort, never on the
critical path.

While the current cohort trains, the orchestrator already knows the next
groups' trial twins, structural parameters, bucketed widths, and mesh —
everything a compile needs except the data.  The worker drains those
signatures on a daemon thread and calls each train function's *prewarm
twin*, which builds the exact jitted step functions the real cohort will
use (through the same module-level step caches) and runs them once on
dummy operands of the right shapes.  That populates the in-process jit
cache — and, with ``init_compile_cache`` wired, the persistent XLA cache —
so the cohort's first step deserializes instead of recompiling.

A train function opts in like the cohort protocol::

    def my_trial(ctx): ...
    def my_prewarm(shared, k, mesh=None): ...   # compile, don't train
    attach_prewarm_fn(my_trial, my_prewarm)

``prewarm(shared, k, mesh)`` receives the member-agreed structural
parameters, the padded/bucketed cohort width, and the mesh; it must be
side-effect free beyond compilation (no dataset downloads, no metric
reports).

Failure contract: the worker can be killed, starved, or blow up
mid-compile and nothing downstream notices — every exception is logged
and swallowed, ``stop()`` bounds its wait, and the thread is a daemon so
process exit never blocks on it.  Duplicate submissions dedupe against
the shape registry, so a queued signature compiles exactly once.
"""

from __future__ import annotations

import logging
import queue
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

from katib_tpu.analysis import guarded_by, make_lock
from katib_tpu.compile.registry import (
    REGISTRY,
    CompileSignature,
    ShapeRegistry,
    _program_name,
    _shapes_of,
    mesh_signature,
)
from katib_tpu.utils import observability as obs

_log = logging.getLogger(__name__)

_PREWARM_ATTR = "__prewarm_fn__"


def attach_prewarm_fn(train_fn: Callable, prewarm_fn: Callable) -> Callable:
    """Declare ``prewarm_fn(shared, k, mesh)`` as the compile-only twin of
    ``train_fn``; returns ``train_fn`` (decorator-style one-liner)."""
    setattr(train_fn, _PREWARM_ATTR, prewarm_fn)
    return train_fn


def prewarm_fn_of(train_fn: Callable | None) -> Callable | None:
    if train_fn is None:
        return None
    return getattr(train_fn, _PREWARM_ATTR, None)


@dataclass
class PrewarmRequest:
    """One upcoming program: who compiles it and with what shapes."""

    train_fn: Callable
    shared: Mapping[str, Any] = field(default_factory=dict)
    k: int = 1
    mesh: Any = None
    # the cohort twin (if any) names the program, matching the signature
    # run_cohort classifies against
    program_fn: Callable | None = None

    def signature(self) -> CompileSignature:
        return CompileSignature(
            program=_program_name(self.program_fn or self.train_fn),
            shapes=_shapes_of(
                {n: v for n, v in self.shared.items() if not isinstance(v, float)}
            ),
            k=int(self.k),
            mesh=mesh_signature(self.mesh),
        )


class PrewarmWorker:
    """Daemon-thread compile worker over a bounded queue of requests."""

    # the worker thread bumps the counters; the CLI/tests read them after
    # drain() — both sides go through _lock, like the thread handle itself
    _GUARDS = guarded_by(_lock=("_thread", "compiled", "failed"))

    def __init__(self, registry: ShapeRegistry = REGISTRY, max_queue: int = 64):
        self._registry = registry
        self._queue: queue.Queue = queue.Queue(maxsize=max_queue)
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._lock = make_lock("prewarm.worker")
        self.compiled = 0  # successful prewarm compiles (tests/CLI)
        self.failed = 0

    def submit(self, request: PrewarmRequest) -> bool:
        """Enqueue a request; returns False (without queuing) when the
        train_fn never opted in, the signature is already registered, or
        the queue is full — submission never blocks the caller."""
        if prewarm_fn_of(request.train_fn) is None:
            return False
        if self._registry.seen(request.signature()):
            return False
        try:
            self._queue.put_nowait(request)
        except queue.Full:
            return False  # backpressure: drop, the trial compiles live
        self._ensure_thread()
        return True

    def _ensure_thread(self) -> None:
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._run, name="katib-prewarm", daemon=True
                )
                self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                req = self._queue.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                self._compile(req)
            except Exception:
                with self._lock:  # LCK001: counter read from the caller thread
                    self.failed += 1
                _log.warning(
                    "prewarm compile failed for %s (best-effort, trial will "
                    "compile live)",
                    _program_name(req.train_fn),
                    exc_info=True,
                )
            finally:
                self._queue.task_done()

    def _compile(self, req: PrewarmRequest) -> None:
        sig = req.signature()
        if self._registry.seen(sig):
            return  # raced with a trial (or a duplicate submit): already warm
        fn = prewarm_fn_of(req.train_fn)
        if fn is None:
            return
        import time

        from katib_tpu import costmodel

        costmodel.clear_active()  # worker thread is reused across requests
        started = time.perf_counter()
        fn(dict(req.shared), int(req.k), req.mesh)
        elapsed = time.perf_counter() - started
        if self._registry.record(sig, source="prewarm", compile_seconds=elapsed):
            with self._lock:  # LCK001: counter read from the caller thread
                self.compiled += 1
            obs.prewarm_compiles.inc(program=sig.program)
        # twins observe their program cost into the ambient slot
        # (costmodel.observe_program) — persist it next to the signature so
        # `katib-tpu cost` can print the roofline table without a run
        active = costmodel.active_cost()
        if active is not None:
            try:
                self._registry.record_cost(sig, active[0].as_dict())
            except Exception:
                pass  # cost is telemetry; the prewarm itself succeeded

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait (bounded) for the queue to empty — CLI verb / tests only;
        the orchestrator never blocks on the worker."""
        import time

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._queue.unfinished_tasks == 0:
                return True
            time.sleep(0.02)
        return False

    def stop(self, timeout: float = 1.0) -> None:
        """Ask the worker to wind down; bounded, never raises.  A compile
        in flight keeps running on the daemon thread and is abandoned at
        process exit — by design, nothing waits on it."""
        self._stop.set()
        with self._lock:  # LCK001: _ensure_thread writes _thread under _lock
            t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)
