"""Compile-signature registry: what has been compiled, and was it warm?

A *compile signature* is the coarse identity of a jitted trial program:
which train function, which structural hyperparameters (the ones baked
into the trace — model widths, batch sizes, optimizer family), the padded
cohort width K, the mesh layout, and whether the carried state is donated.
Two executions with the same signature trace the same program, so the
second one should hit the in-process jit cache or the persistent XLA
compilation cache (``init_compile_cache``) instead of recompiling.

The registry records every signature compiled (by trials, by the prewarm
worker, by the CLI ``prewarm`` verb) and classifies each trial's first
step warm/cold against it, exporting
``katib_compile_cache_hits_total`` / ``katib_compile_cache_misses_total``
and the warm-vs-cold ``katib_first_step_compile_seconds`` histogram so a
cache regression shows up as the miss counter climbing.

When the persistent compilation cache is wired, signatures also persist
to ``<cache_dir>/shape_registry.jsonl`` — a prewarm subprocess (or an
earlier run of the same sweep) warms classification for later processes
sharing the cache directory.  Everything here is best-effort telemetry:
an unreadable registry file, an unhashable value, or a full disk never
fails a trial.

Classification heuristics (documented, deliberate):

- float-valued parameters are excluded from the signature — the model
  fns in this repo carry lr/momentum as runtime operands
  (``optax.inject_hyperparams``), so floats don't change the program;
- cohort signatures use only the parameters every member agrees on
  (per-member varying values are runtime rows by construction);
- over-keying (a shared float that *doesn't* change the program) errs
  toward classifying cold — conservative, never falsely warm.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Mapping, Sequence

from katib_tpu.analysis import guarded_by, make_lock
from katib_tpu.utils import observability as obs

_REGISTRY_FILENAME = "shape_registry.jsonl"


def _program_name(fn: Callable | None) -> str:
    if fn is None:
        return "<none>"
    return getattr(fn, "__qualname__", getattr(fn, "__name__", repr(fn)))


def mesh_signature(mesh: Any) -> str:
    """Stable cross-process mesh identity: axis layout + platform (device
    ids are process-local and recycle; the compiled program depends on the
    shape of the mesh, not which physical chips back it)."""
    if mesh is None:
        return ""
    try:
        axes = ",".join(f"{n}={s}" for n, s in mesh.shape.items())
        platform = next(iter(mesh.devices.flat)).platform
        return f"{axes}:{platform}"
    except Exception:
        return repr(mesh)


def _structural(value: Any) -> bool:
    """True for values baked into the trace (ints, strs, bools); floats ride
    as runtime operands through inject_hyperparams and are excluded."""
    return isinstance(value, (int, str, bool)) and not isinstance(value, float)


@dataclass(frozen=True)
class CompileSignature:
    """Coarse identity of one compiled trial program."""

    program: str
    shapes: tuple[tuple[str, str], ...] = ()
    k: int = 1
    mesh: str = ""
    donation: bool = True

    def key(self) -> str:
        return json.dumps(
            {
                "program": self.program,
                "shapes": list(self.shapes),
                "k": self.k,
                "mesh": self.mesh,
                "donation": self.donation,
            },
            sort_keys=True,
        )


def shared_structural(param_dicts: Sequence[Mapping[str, Any]]) -> dict[str, Any]:
    """Structural parameters every member agrees on — the signature's shape
    component.  Per-member varying values (lr, momentum, seeds) drop out
    here exactly because they vary: they are runtime rows, not trace
    constants."""
    if not param_dicts:
        return {}
    out: dict[str, Any] = {}
    first = param_dicts[0]
    for name, value in first.items():
        if not _structural(value):
            continue
        if all(p.get(name) == value for p in param_dicts[1:]):
            out[name] = value
    return out


def _shapes_of(shared: Mapping[str, Any]) -> tuple[tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in shared.items()))


def cohort_signature(
    cohort_fn: Callable | None,
    trials: Sequence[Any],
    k: int,
    mesh: Any = None,
) -> CompileSignature:
    """Signature of a cohort execution: the cohort twin's program, the
    member-agreed structural parameters, and the padded/bucketed width
    ``k`` the stacked state will actually carry."""
    params = [t.params() for t in trials]
    return CompileSignature(
        program=_program_name(cohort_fn),
        shapes=_shapes_of(shared_structural(params)),
        k=int(k),
        mesh=mesh_signature(mesh),
    )


def trial_signature(train_fn: Callable | None, trial: Any, mesh: Any = None) -> CompileSignature:
    """Signature of a singleton white-box trial (k=1)."""
    params = trial.params()
    shared = {n: v for n, v in params.items() if _structural(v)}
    return CompileSignature(
        program=_program_name(train_fn),
        shapes=_shapes_of(shared),
        k=1,
        mesh=mesh_signature(mesh),
    )


def _cache_dir() -> str | None:
    """The persistent-compile-cache dir in force, or None — whatever
    ``init_compile_cache`` resolved (imported lazily: the runner imports
    this package)."""
    from katib_tpu.runner.trial_runner import compile_cache_dir

    return compile_cache_dir()


class ShapeRegistry:
    """Thread-safe compiled-signature set with optional JSONL persistence.

    Reached from the caller thread (trial runner first steps), the async
    harvest thread (settlement-time classification), and the prewarm
    worker — every access to the signature map, the loaded-dir marker,
    and the torn-tail truncation offset goes through ``_lock``, including
    the JSONL append (``_append`` orders truncate-then-append against
    concurrent recorders).
    """

    _GUARDS = guarded_by(_lock=("_seen", "_loaded_dir", "_truncate_to"))

    def __init__(self) -> None:
        self._lock = make_lock("compile.registry")
        self._seen: dict[str, dict] = {}
        self._loaded_dir: str | None = None
        # byte length of the valid prefix when the registry file ends in a
        # torn/corrupt line (crash mid-append); the next _append truncates
        # to here first so the file heals instead of growing garbage
        self._truncate_to: int | None = None

    # -- persistence (best-effort) ----------------------------------------

    def _path(self) -> str | None:
        d = _cache_dir()
        return os.path.join(d, _REGISTRY_FILENAME) if d else None

    def _maybe_load(self) -> None:  # lint: holds(_lock)
        """Lazily fold the cache dir's registry file into memory, once per
        directory (a later init_compile_cache of a different dir reloads)."""
        d = _cache_dir()
        if d is None or d == self._loaded_dir:
            return
        self._loaded_dir = d
        self._truncate_to = None
        path = os.path.join(d, _REGISTRY_FILENAME)
        try:
            with open(path, "rb") as f:
                offset = 0
                valid_end = 0
                torn = 0
                dupes = 0
                for raw in f:
                    offset += len(raw)
                    line = raw.decode("utf-8", errors="replace").strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        # same torn-tail rule as the experiment journal:
                        # tolerate the bad line, remember where the valid
                        # prefix ends so the next append truncates it away
                        torn += 1
                        continue
                    torn = 0
                    valid_end = offset
                    key = rec.get("key") if isinstance(rec, dict) else None
                    if key:
                        cur = self._seen.setdefault(key, rec)
                        if cur is not rec:
                            dupes += 1
                            if isinstance(rec.get("cost"), dict):
                                # first record wins for identity fields,
                                # but a later cost-bearing line
                                # (record_cost re-appends the row) carries
                                # the freshest XLA analysis
                                cur["cost"] = rec["cost"]
                if torn:
                    import warnings

                    warnings.warn(
                        f"shape registry {path} ends in {torn} torn/corrupt "
                        f"line(s) ({offset - valid_end} bytes) — skipped; "
                        "will truncate on next append",
                        RuntimeWarning,
                        stacklevel=2,
                    )
                    self._truncate_to = valid_end
                if dupes:
                    # record_cost re-appends its row on every cost change,
                    # so a long-lived cache dir accretes duplicate lines
                    # without bound: compact to one merged row per key.
                    # The durable rewrite (tmp + fsync + rename, same
                    # recipe as the journal) also heals any torn tail.
                    self._compact(path)
        except OSError:
            pass

    def _compact(self, path: str) -> None:  # lint: holds(_lock)
        """Durably rewrite the registry file as one merged row per
        signature (the in-memory view).  A concurrent reader sees the old
        file or the compacted one, never a partial rewrite."""
        try:
            from katib_tpu.utils.fsio import atomic_replace

            body = "".join(
                json.dumps(rec) + "\n" for rec in self._seen.values()
            )
            atomic_replace(path, body.encode("utf-8"), prefix=".compact-")
            self._truncate_to = None
        except OSError:
            pass  # compaction is housekeeping, never a failure

    def _append(self, rec: dict) -> None:  # lint: holds(_lock)
        path = self._path()
        if path is None:
            return
        try:
            if self._truncate_to is not None:
                # heal the torn tail _maybe_load found before appending
                # after it (appending after garbage would orphan every
                # later record for pre-fix readers)
                with open(path, "rb+") as f:
                    f.truncate(self._truncate_to)
                self._truncate_to = None
            with open(path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        except OSError:
            pass  # registry persistence is telemetry, never a failure

    # -- the registry proper ----------------------------------------------

    def seen(self, sig: CompileSignature) -> bool:
        with self._lock:
            self._maybe_load()
            return sig.key() in self._seen

    def record(
        self,
        sig: CompileSignature,
        source: str = "trial",
        compile_seconds: float | None = None,
    ) -> bool:
        """Record a compiled signature; returns True when it was new."""
        key = sig.key()
        rec = {
            "key": key,
            "program": sig.program,
            "k": sig.k,
            "mesh": sig.mesh,
            "shapes": dict(sig.shapes),
            "donation": sig.donation,
            "source": source,
        }
        if compile_seconds is not None:
            rec["compile_seconds"] = round(float(compile_seconds), 4)
        with self._lock:
            self._maybe_load()
            fresh = key not in self._seen
            if fresh:
                self._seen[key] = rec
                # LCK001 fix: _append reads/clears _truncate_to and must
                # order truncate-then-append against concurrent recorders
                # (harvest thread vs. caller thread both classify here) —
                # it used to run after the lock was dropped
                self._append(rec)
        return fresh

    def record_cost(self, sig: CompileSignature, cost: Mapping[str, Any]) -> bool:
        """Merge an XLA cost record (``costmodel.CostRecord.as_dict()``)
        into the signature's row and re-append it so registry-sharing
        processes (and ``katib-tpu cost``) see the analysis.  Idempotent:
        an unchanged cost neither rewrites memory nor grows the file.
        Returns True when the row changed."""
        key = sig.key()
        cost = dict(cost)
        with self._lock:
            self._maybe_load()
            rec = self._seen.get(key)
            if rec is None:
                # cost can arrive before record() (e.g. a model observing
                # its program mid-first-epoch) — synthesize the row
                rec = {
                    "key": key,
                    "program": sig.program,
                    "k": sig.k,
                    "mesh": sig.mesh,
                    "shapes": dict(sig.shapes),
                    "donation": sig.donation,
                    "source": "cost",
                }
                self._seen[key] = rec
            if rec.get("cost") == cost:
                return False
            rec["cost"] = cost
            self._append(rec)
        return True

    def cost_of(self, sig: CompileSignature) -> dict | None:
        """The persisted cost record for a signature, or None."""
        with self._lock:
            self._maybe_load()
            rec = self._seen.get(sig.key())
        cost = rec.get("cost") if isinstance(rec, dict) else None
        return dict(cost) if isinstance(cost, dict) else None

    def classify(self, sig: CompileSignature) -> str:
        """``"warm"`` when the signature was compiled before (this process
        or a registry-sharing one), else ``"cold"`` — no counter side
        effects (see :meth:`note_first_step`)."""
        return "warm" if self.seen(sig) else "cold"

    def note_first_step(
        self, sig: CompileSignature, seconds: float, source: str = "trial"
    ) -> str:
        """Classify a first step warm/cold, bump the hit/miss counters,
        feed the warm-vs-cold histogram, and record the signature so the
        next same-shape execution classifies warm.  Returns the label."""
        label = self.classify(sig)
        if label == "warm":
            obs.compile_cache_hits.inc(program=sig.program)
        else:
            obs.compile_cache_misses.inc(program=sig.program)
        try:
            obs.first_step_compile_seconds.observe(float(seconds), cache=label)
        except (TypeError, ValueError):
            pass
        self.record(sig, source=source, compile_seconds=seconds)
        return label

    def signatures(self) -> list[dict]:
        with self._lock:
            self._maybe_load()
            return list(self._seen.values())

    def reset(self) -> None:
        """Forget everything (tests); the on-disk file is left alone."""
        with self._lock:
            self._seen.clear()
            self._loaded_dir = None


REGISTRY = ShapeRegistry()
