"""Serialized AOT executables in a content-addressed, tiered artifact cache.

PR 8's prewarm worker and the persistent XLA cache amortize compilation
*within* one host: the first process pays the 470s (live) / 1554s (AOT)
compile and every later process on the same cache dir deserializes.  A
brand-new host still starts cold — which is exactly the step the
multi-host async dispatch (ROADMAP items 2 and 4) cannot afford.  This
module makes compiled executables *portable*: one host serializes its
AOT-compiled programs (``jax.experimental.serialize_executable``) into
checksummed envelopes published to a shared artifact tier, and a fresh
host's first step deserializes a fetched envelope instead of compiling.

Lookup order (cheapest first)::

    in-process loaded map -> local tier (<compile_cache>/artifacts)
        -> shared tier (KATIB_ARTIFACT_DIR / ExperimentSpec.artifact_dir)
        -> cold compile

Artifacts are **content-addressed**: the file name is the SHA-256 of the
:class:`~katib_tpu.compile.registry.CompileSignature` key plus an
*environment fingerprint* (jax/jaxlib/libtpu versions, platform, device
kind, topology).  A toolchain or topology change therefore produces a
different address — stale artifacts invalidate by construction instead
of misloading.  Defense in depth on the fetch path: every envelope
carries its own checksum and fingerprint, and anything corrupt,
truncated, or mismatched is **quarantined** (renamed ``*.quarantined``,
same idiom as ``orchestrator/fsck.py`` snapshots) and counted — a fetch
failure always degrades to a cold compile, never a crash.

The shared tier speaks through the small :class:`ArtifactBackend`
interface (get/put/exists/list/delete) so a directory today can become
an object store later without touching the cache logic.  Publication is
atomic (temp file + rename via ``utils/fsio.py``) so concurrent
publishers — a whole fleet warming at once — can never surface a torn
envelope, and publish dedupes on the content address.

Cost records (``costmodel.CostRecord``) ride inside the envelope, so a
fetched program publishes its MFU/roofline gauges without re-tracing
(``costmodel.live.observe_program`` consults :meth:`ArtifactCache.cost_for`
before paying the extra trace).

Everything here is strictly best-effort telemetry-grade plumbing: an
unreadable tier, an unserializable executable, or a full disk never
fails a trial — the jit path is always the fallback.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Mapping

from katib_tpu.analysis import guarded_by, make_lock
from katib_tpu.compile.registry import REGISTRY, CompileSignature, _cache_dir
from katib_tpu.utils import observability as obs
from katib_tpu.utils.fsio import atomic_replace

_log = logging.getLogger(__name__)

MAGIC = b"KATIBART1\n"
SUFFIX = ".katibx"
QUARANTINE_SUFFIX = ".quarantined"
_ENV_VAR = "KATIB_ARTIFACT_DIR"


class ArtifactCorrupt(Exception):
    """Envelope failed integrity verification (magic/header/checksum)."""


class ArtifactMismatch(Exception):
    """Envelope is intact but belongs to a different signature or
    environment than its address claims (tampered or misplaced file)."""


# -- environment fingerprint --------------------------------------------------

_FP_CACHE: dict | None = None


def _libtpu_version() -> str:
    """Installed libtpu version, best-effort ('' off-TPU)."""
    try:
        from importlib import metadata

        for dist in ("libtpu", "libtpu-nightly"):
            try:
                return f"{dist}-{metadata.version(dist)}"
            except metadata.PackageNotFoundError:
                continue
    except Exception:
        pass
    return ""


def env_fingerprint(refresh: bool = False) -> dict:
    """The fields that decide whether a serialized executable from another
    process can safely load here: toolchain versions, platform, device
    kind, and topology.  Computed once per process (``refresh`` for
    tests).  Serialized executables are XLA-version- and target-specific;
    two hosts agreeing on this fingerprint can exchange them."""
    global _FP_CACHE
    if _FP_CACHE is not None and not refresh:
        return dict(_FP_CACHE)
    fp = {
        "jax": "?",
        "jaxlib": "?",
        "libtpu": _libtpu_version(),
        "platform": "?",
        "device_kind": "?",
        "device_count": 0,
        "process_count": 1,
    }
    try:
        import jax
        import jaxlib

        fp["jax"] = jax.__version__
        fp["jaxlib"] = jaxlib.__version__
        devs = jax.devices()
        fp["platform"] = devs[0].platform
        fp["device_kind"] = devs[0].device_kind
        fp["device_count"] = len(devs)
        fp["process_count"] = jax.process_count()
    except Exception:
        pass  # a deviceless/odd env still fingerprints (just coarsely)
    _FP_CACHE = fp
    return dict(fp)


def fingerprint_key(fp: Mapping[str, Any]) -> str:
    return json.dumps(dict(fp), sort_keys=True)


def artifact_name(sig_key: str, fp: Mapping[str, Any]) -> str:
    """Content address: SHA-256 over (signature key, env fingerprint).
    A different toolchain/topology yields a different name, so a stale
    artifact is simply never looked up — invalidation by construction."""
    digest = hashlib.sha256(
        (sig_key + "\x00" + fingerprint_key(fp)).encode()
    ).hexdigest()
    return digest + SUFFIX


def sig_from_key(key: str) -> CompileSignature:
    """Reconstruct a :class:`CompileSignature` from its ``key()`` json
    (artifact headers carry the key; replication and family fetches need
    the structured form back)."""
    rec = json.loads(key)
    return CompileSignature(
        program=str(rec.get("program", "?")),
        shapes=tuple((str(a), str(b)) for a, b in rec.get("shapes") or []),
        k=int(rec.get("k", 1)),
        mesh=str(rec.get("mesh", "")),
        donation=bool(rec.get("donation", True)),
    )


def _aval_list(tree: Any) -> list[list]:
    """Flattened [(shape, dtype)] of a pytree of arrays/avals — the
    envelope's calling-convention record and the (program, avals) index
    key the dispatch seam matches against."""
    import jax

    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        out.append([list(int(d) for d in shape), dtype])
    return out


def aval_digest(tree: Any) -> str:
    return hashlib.sha256(
        json.dumps(_aval_list(tree), sort_keys=True).encode()
    ).hexdigest()


# -- envelope (checksummed container) -----------------------------------------


def pack_envelope(
    sig: CompileSignature,
    fp: Mapping[str, Any],
    payload: bytes,
    in_tree: Any,
    out_tree: Any,
    *,
    avals: list | None = None,
    devices: list[int] | None = None,
    cost: Mapping[str, Any] | None = None,
    parent: str | None = None,
) -> bytes:
    """``MAGIC + header-json + \\n + body``: the body is the pickled
    (serialized executable, in/out treedefs) and the header carries the
    signature identity, the environment fingerprint, the program's input
    avals, the ids of the devices it was compiled for (assignment order),
    the optional cost record, and the body's length + SHA-256."""
    body = pickle.dumps(
        {"payload": payload, "in_tree": in_tree, "out_tree": out_tree},
        protocol=pickle.HIGHEST_PROTOCOL,
    )
    header = {
        "version": 1,
        "key": sig.key(),
        "program": sig.program,
        "k": sig.k,
        "mesh": sig.mesh,
        "shapes": dict(sig.shapes),
        "donation": sig.donation,
        "fingerprint": dict(fp),
        "avals": avals or [],
        "devices": list(devices or []),
        "cost": dict(cost) if cost else None,
        # the request-level signature this program was compiled under —
        # a prewarm twin observes several step programs, each published
        # as its own envelope; fetch_family collects them by this link
        "parent": parent,
        "created": time.time(),
        "body_len": len(body),
        "body_sha256": hashlib.sha256(body).hexdigest(),
    }
    return MAGIC + json.dumps(header, sort_keys=True).encode() + b"\n" + body


def unpack_envelope(data: bytes) -> tuple[dict, dict]:
    """Parse + verify an envelope; returns ``(header, body_dict)``.
    Raises :class:`ArtifactCorrupt` on any structural or checksum
    failure — callers quarantine and degrade, never crash."""
    if not data.startswith(MAGIC):
        raise ArtifactCorrupt("bad magic")
    rest = data[len(MAGIC):]
    nl = rest.find(b"\n")
    if nl < 0:
        raise ArtifactCorrupt("no header terminator")
    try:
        header = json.loads(rest[:nl].decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise ArtifactCorrupt(f"unparseable header: {e}") from e
    if not isinstance(header, dict):
        raise ArtifactCorrupt("header is not an object")
    body = rest[nl + 1:]
    if len(body) != int(header.get("body_len", -1)):
        raise ArtifactCorrupt(
            f"body length {len(body)} != declared {header.get('body_len')}"
        )
    if hashlib.sha256(body).hexdigest() != header.get("body_sha256"):
        raise ArtifactCorrupt("body checksum mismatch")
    try:
        body_dict = pickle.loads(body)
    except Exception as e:
        raise ArtifactCorrupt(f"unpicklable body: {e}") from e
    if not isinstance(body_dict, dict) or "payload" not in body_dict:
        raise ArtifactCorrupt("body missing payload")
    return header, body_dict


def read_header(data: bytes) -> dict:
    """Header-only parse with the same integrity checks minus the body
    unpickle (``cache``/``fsck`` inspection: no executable load)."""
    if not data.startswith(MAGIC):
        raise ArtifactCorrupt("bad magic")
    rest = data[len(MAGIC):]
    nl = rest.find(b"\n")
    if nl < 0:
        raise ArtifactCorrupt("no header terminator")
    try:
        header = json.loads(rest[:nl].decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise ArtifactCorrupt(f"unparseable header: {e}") from e
    if not isinstance(header, dict):
        raise ArtifactCorrupt("header is not an object")
    body = rest[nl + 1:]
    if len(body) != int(header.get("body_len", -1)):
        raise ArtifactCorrupt(
            f"body length {len(body)} != declared {header.get('body_len')}"
        )
    if hashlib.sha256(body).hexdigest() != header.get("body_sha256"):
        raise ArtifactCorrupt("body checksum mismatch")
    return header


# -- backends (object-store-shaped) -------------------------------------------


class ArtifactBackend:
    """Minimal blob-store surface a tier needs.  A directory implements it
    today; an object store (GCS/S3) implements the same five methods
    later without the cache logic changing."""

    def get(self, name: str) -> bytes | None:  # pragma: no cover - interface
        raise NotImplementedError

    def put(self, name: str, data: bytes) -> None:  # pragma: no cover
        raise NotImplementedError

    def exists(self, name: str) -> bool:  # pragma: no cover - interface
        raise NotImplementedError

    def list(self) -> list[str]:  # pragma: no cover - interface
        raise NotImplementedError

    def delete(self, name: str) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def quarantine(self, name: str) -> bool:
        """Move a blob out of the lookup namespace, preserving the bytes
        for diagnosis.  Default: copy-then-delete through the interface."""
        data = self.get(name)
        if data is None:
            return False
        self.put(name + QUARANTINE_SUFFIX, data)
        self.delete(name)
        return True

    def describe(self) -> str:  # pragma: no cover - interface
        return type(self).__name__


class DirectoryBackend(ArtifactBackend):
    """Shared-filesystem tier: one envelope file per artifact, atomic
    publication (temp + rename) so concurrent publishers and readers
    never see a torn file."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)

    def _path(self, name: str) -> str:
        # content addresses are hex digests — no separators — but never
        # trust a name to stay inside the root
        safe = os.path.basename(name)
        return os.path.join(self.root, safe)

    def get(self, name: str) -> bytes | None:
        try:
            with open(self._path(name), "rb") as f:
                return f.read()
        except OSError:
            return None

    def put(self, name: str, data: bytes) -> None:
        os.makedirs(self.root, exist_ok=True)
        # durable atomic replace: a concurrent reader sees the old file or
        # the new one, never a prefix — and a same-content racer is
        # harmless because both write identical bytes
        atomic_replace(self._path(name), data, prefix=".pub-")

    def exists(self, name: str) -> bool:
        return os.path.exists(self._path(name))

    def list(self) -> list[str]:
        try:
            return sorted(
                n for n in os.listdir(self.root) if n.endswith(SUFFIX)
            )
        except OSError:
            return []

    def delete(self, name: str) -> None:
        try:
            os.unlink(self._path(name))
        except OSError:
            pass

    def quarantine(self, name: str) -> bool:
        src = self._path(name)
        try:
            os.replace(src, src + QUARANTINE_SUFFIX)
            return True
        except OSError:
            return False

    def describe(self) -> str:
        return self.root


# -- loaded artifacts ---------------------------------------------------------


@dataclass
class LoadedArtifact:
    """A fetched, deserialized executable ready to dispatch."""

    sig_key: str
    program: str
    compiled: Any  # jax.stages.Compiled
    tier: str
    avals: list = field(default_factory=list)
    aval_key: str = ""
    cost: dict | None = None
    parent: str | None = None

    def __call__(self, *args):
        return self.compiled(*args)

    def dummy_args(self) -> tuple:
        """Zero-filled concrete operands matching the executable's input
        avals — enough to execute one real step (bench/CLI verification:
        a fetched executable that cannot run is worse than a cold
        compile, so prove it dispatches)."""
        import jax
        import jax.numpy as jnp

        def zero(a):
            return jnp.zeros(a.shape, a.dtype)

        info = self.compiled.args_info
        # AOT Compiled reports ((args...), {kwargs}) — unwrap to the
        # positional tuple (empty kwargs: these programs are jit steps)
        if (
            isinstance(info, tuple)
            and len(info) == 2
            and isinstance(info[1], dict)
            and not info[1]
        ):
            info = info[0]
        return tuple(jax.tree_util.tree_map(zero, tuple(info)))


# -- the tiered cache ---------------------------------------------------------


class ArtifactCache:
    """Process-wide tiered executable cache with per-tier hit/miss
    telemetry.

    Reached from the prewarm worker thread, trial pool threads (the
    runner's pre-trace fetch), and the caller thread (CLI verbs) — the
    loaded maps and the shared-dir config go through ``_lock``.  Fetch
    deserialization happens outside the lock (it is slow and jax-side
    thread-safe); a racing duplicate load is harmless, last-in wins.
    """

    _GUARDS = guarded_by(
        _lock=("_loaded", "_by_program", "_families", "_misses", "_shared_dir")
    )

    def __init__(self) -> None:
        self._lock = make_lock("compile.artifacts")
        self._loaded: dict[str, LoadedArtifact] = {}
        self._by_program: dict[tuple[str, str], LoadedArtifact] = {}
        self._families: dict[str, list[LoadedArtifact]] = {}
        # signatures whose family fetch came up empty: every trial's
        # dispatch seam probes, and rescanning the tier directories per
        # trial would be pure waste — a publish() invalidates this
        self._misses: set[str] = set()
        self._shared_dir: str | None = None

    # -- configuration -------------------------------------------------------

    def configure(self, shared_dir: str | None = None) -> str | None:
        """Wire the shared tier: ``KATIB_ARTIFACT_DIR`` env var first, then
        the argument (``ExperimentSpec.artifact_dir``).  First caller
        wins, like ``init_compile_cache`` — a second caller asking for a
        different directory gets a ``RuntimeWarning`` and the original.
        Returns the effective dir (None = shared tier disabled)."""
        resolved = os.environ.get(_ENV_VAR) or shared_dir
        with self._lock:
            if self._shared_dir is not None:
                if resolved and os.path.abspath(resolved) != self._shared_dir:
                    import warnings

                    warnings.warn(
                        "shared artifact tier already wired to "
                        f"{self._shared_dir!r}; ignoring the requested "
                        f"{os.path.abspath(resolved)!r} (first caller wins)",
                        RuntimeWarning,
                        stacklevel=2,
                    )
                return self._shared_dir
            if not resolved:
                return None
            self._shared_dir = os.path.abspath(resolved)
            return self._shared_dir

    def shared_dir(self) -> str | None:
        with self._lock:
            d = self._shared_dir
        return d or (os.environ.get(_ENV_VAR) or None)

    def local_dir(self) -> str | None:
        """The local artifact tier rides next to the persistent XLA cache
        (``<compile_cache>/artifacts``): wiring one cache dir wires both
        halves of the "local" story."""
        d = _cache_dir()
        return os.path.join(d, "artifacts") if d else None

    def tiers(self) -> list[tuple[str, ArtifactBackend]]:
        """Ordered (name, backend) lookup chain, cheapest first."""
        out: list[tuple[str, ArtifactBackend]] = []
        local = self.local_dir()
        if local:
            out.append(("local", DirectoryBackend(local)))
        shared = self.shared_dir()
        if shared:
            out.append(("shared", DirectoryBackend(shared)))
        return out

    def enabled(self) -> bool:
        return bool(self.tiers())

    # -- publish -------------------------------------------------------------

    def publish(
        self,
        sig: CompileSignature,
        compiled: Any,
        *,
        cost: Mapping[str, Any] | None = None,
        parent: str | None = None,
    ) -> list[str]:
        """Serialize ``compiled`` and publish the envelope to every
        configured tier (deduped on the content address).  Returns the
        tier names actually written.  Never raises — an executable the
        backend cannot serialize (no unloaded form) publishes nowhere."""
        tiers = self.tiers()
        if not tiers:
            return []
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            avals = _aval_list(compiled.args_info)
            fp = env_fingerprint()
            data = pack_envelope(
                sig,
                fp,
                payload,
                in_tree,
                out_tree,
                avals=avals,
                # the executable's own device assignment, in order: the
                # loader must hand deserialize_and_load exactly these
                devices=[
                    d.id for d in compiled.runtime_executable().local_devices()
                ],
                cost=cost,
                parent=parent,
            )
            name = artifact_name(sig.key(), fp)
        except Exception:
            _log.warning(
                "artifact serialize failed for %s (trial unaffected)",
                sig.program,
                exc_info=True,
            )
            return []
        written: list[str] = []
        for tier, backend in tiers:
            try:
                if backend.exists(name):
                    continue  # fleet publish dedupe: first writer wins
                backend.put(name, data)
                obs.artifact_publishes.inc(tier=tier)
                written.append(tier)
            except Exception:
                _log.warning(
                    "artifact publish to %s tier failed", tier, exc_info=True
                )
        # same-process reuse: the publisher's own dispatch seam can adopt
        # the executable it just serialized
        la = LoadedArtifact(
            sig_key=sig.key(),
            program=sig.program,
            compiled=compiled,
            tier="published",
            avals=avals,
            aval_key=hashlib.sha256(
                json.dumps(avals, sort_keys=True).encode()
            ).hexdigest(),
            cost=dict(cost) if cost else None,
            parent=parent,
        )
        self._adopt(la)
        return written

    def replicate(self, la: LoadedArtifact) -> list[str]:
        """Re-publish a loaded artifact so it exists in *every* configured
        tier (publish mode: a local-tier hit still warms the fleet's
        shared tier).  Dedupe makes this a no-op where it already lives."""
        try:
            sig = sig_from_key(la.sig_key)
        except Exception:
            return []
        return self.publish(sig, la.compiled, cost=la.cost, parent=la.parent)

    # -- fetch ---------------------------------------------------------------

    def _adopt(self, la: LoadedArtifact) -> None:
        with self._lock:
            self._loaded[la.sig_key] = la
            if la.aval_key:
                self._by_program[(la.program, la.aval_key)] = la
            # new material invalidates negative family-fetch results
            self._misses.clear()

    def lookup_loaded(self, sig: CompileSignature) -> LoadedArtifact | None:
        with self._lock:
            return self._loaded.get(sig.key())

    def fetch(self, sig: CompileSignature) -> LoadedArtifact | None:
        """Walk the tiers for ``sig``'s artifact under the current env
        fingerprint.  On a hit: verify, deserialize, promote a shared hit
        into the local tier, register the signature warm, and index the
        executable for the dispatch seam.  On any integrity failure:
        quarantine + keep walking.  Returns None on a full miss (callers
        compile cold).  Never raises."""
        try:
            loaded = self.lookup_loaded(sig)
            if loaded is not None:
                return loaded
            tiers = self.tiers()
            if not tiers:
                return None
            key = sig.key()
            fp = env_fingerprint()
            name = artifact_name(key, fp)
            for tier, backend in tiers:
                data = backend.get(name)
                if data is None:
                    obs.artifact_misses.inc(tier=tier)
                    continue
                try:
                    la = self._load(tier, data, key, fp)
                except (ArtifactCorrupt, ArtifactMismatch) as e:
                    _log.warning(
                        "quarantining %s artifact %s: %s", tier, name, e
                    )
                    try:
                        backend.quarantine(name)
                    except Exception:
                        pass
                    obs.artifact_quarantines.inc(tier=tier)
                    obs.artifact_misses.inc(tier=tier)
                    continue
                obs.artifact_hits.inc(tier=tier)
                if tier != "local":
                    self._promote_local(name, data)
                self._adopt(la)
                # the registry is how first steps classify warm and how
                # `katib-tpu cache`/cost see the program without a run
                REGISTRY.record(sig, source=f"artifact:{tier}")
                if la.cost:
                    try:
                        REGISTRY.record_cost(sig, la.cost)
                    except Exception:
                        pass
                return la
            return None
        except Exception:
            _log.warning(
                "artifact fetch failed for %s (degrading to cold compile)",
                sig.program,
                exc_info=True,
            )
            return None

    def _load(
        self, tier: str, data: bytes, key: str, fp: Mapping[str, Any]
    ) -> LoadedArtifact:
        header, body = unpack_envelope(data)
        if header.get("key") != key:
            raise ArtifactMismatch("signature key != address")
        if header.get("fingerprint") != dict(fp):
            # the content address should make this unreachable; a file
            # renamed/copied across envs is exactly what it catches
            raise ArtifactMismatch("environment fingerprint mismatch")
        import jax
        from jax.experimental import serialize_executable as se

        # load onto the devices the program was compiled for: without them
        # jax loads for EVERY local device, and a one-device step then
        # refuses its operands on a four-chip host
        by_id = {d.id: d for d in jax.devices()}
        ids = header.get("devices") or []
        if not ids or any(i not in by_id for i in ids):
            raise ArtifactMismatch(f"execution devices {ids} not present here")
        try:
            compiled = se.deserialize_and_load(
                body["payload"],
                body["in_tree"],
                body["out_tree"],
                execution_devices=[by_id[i] for i in ids],
            )
        except Exception as e:
            raise ArtifactCorrupt(f"executable deserialize failed: {e}") from e
        avals = header.get("avals") or []
        return LoadedArtifact(
            sig_key=key,
            program=str(header.get("program", "?")),
            compiled=compiled,
            tier=tier,
            avals=avals,
            aval_key=hashlib.sha256(
                json.dumps(avals, sort_keys=True).encode()
            ).hexdigest(),
            cost=header.get("cost") if isinstance(header.get("cost"), dict) else None,
            parent=header.get("parent"),
        )

    def fetch_family(self, sig: CompileSignature) -> list[LoadedArtifact]:
        """Everything published under ``sig``: the exact-signature
        envelope (if any) plus every program envelope whose ``parent``
        links back to it — a prewarm twin publishes one envelope per step
        program it observes, and a fresh host wants all of them loaded
        before tracing.  One hit/miss per tier for the family as a whole;
        corrupt/misaddressed members quarantine like :meth:`fetch`.  Any
        hit marks ``sig`` warm in the registry.  Never raises."""
        try:
            key = sig.key()
            with self._lock:
                cached = self._families.get(key)
                missed = key in self._misses
            if cached is not None:
                return list(cached)
            if missed:
                return []
            tiers = self.tiers()
            if not tiers:
                return []
            fp = env_fingerprint()
            fp_key = fingerprint_key(fp)
            exact_name = artifact_name(key, fp)
            out: list[LoadedArtifact] = []
            loaded_names: set[str] = set()
            hit_tiers: list[str] = []
            for tier, backend in tiers:
                tier_hit = False
                for name in backend.list():
                    if name in loaded_names:
                        continue
                    data = backend.get(name)
                    if data is None:
                        continue
                    try:
                        header = read_header(data)
                    except ArtifactCorrupt as e:
                        # family scans read every header anyway, so a
                        # corrupt envelope quarantines on sight even when
                        # it belongs to some other signature
                        _log.warning(
                            "quarantining %s artifact %s: %s", tier, name, e
                        )
                        try:
                            backend.quarantine(name)
                        except Exception:
                            pass
                        obs.artifact_quarantines.inc(tier=tier)
                        continue
                    mine = name == exact_name or header.get("parent") == key
                    if not mine:
                        continue
                    if fingerprint_key(header.get("fingerprint") or {}) != fp_key:
                        continue  # another environment's build of this program
                    hkey = str(header.get("key", ""))
                    if artifact_name(hkey, header.get("fingerprint") or {}) != name:
                        _log.warning(
                            "quarantining misaddressed %s artifact %s",
                            tier,
                            name,
                        )
                        try:
                            backend.quarantine(name)
                        except Exception:
                            pass
                        obs.artifact_quarantines.inc(tier=tier)
                        continue
                    try:
                        la = self._load(tier, data, hkey, fp)
                    except (ArtifactCorrupt, ArtifactMismatch) as e:
                        _log.warning(
                            "quarantining %s artifact %s: %s", tier, name, e
                        )
                        try:
                            backend.quarantine(name)
                        except Exception:
                            pass
                        obs.artifact_quarantines.inc(tier=tier)
                        continue
                    tier_hit = True
                    loaded_names.add(name)
                    if tier != "local":
                        self._promote_local(name, data)
                    self._adopt(la)
                    if la.cost:
                        try:
                            REGISTRY.record_cost(sig_from_key(hkey), la.cost)
                        except Exception:
                            pass
                    out.append(la)
                if tier_hit:
                    obs.artifact_hits.inc(tier=tier)
                    hit_tiers.append(tier)
                else:
                    obs.artifact_misses.inc(tier=tier)
            if out:
                REGISTRY.record(sig, source=f"artifact:{hit_tiers[0]}")
                with self._lock:
                    self._families[key] = list(out)
            else:
                with self._lock:
                    self._misses.add(key)
            return out
        except Exception:
            _log.warning(
                "artifact family fetch failed for %s (degrading to cold "
                "compile)",
                sig.program,
                exc_info=True,
            )
            return []

    def _promote_local(self, name: str, data: bytes) -> None:
        """A shared-tier hit seeds the local tier so this host's next
        process fetches locally (and keeps working if the shared tier
        disappears)."""
        local = self.local_dir()
        if not local:
            return
        try:
            backend = DirectoryBackend(local)
            if not backend.exists(name):
                backend.put(name, data)
        except Exception:
            pass  # promotion is an optimization, never a failure

    # -- dispatch + cost seams -----------------------------------------------

    def program_for(self, program: str, args: tuple) -> LoadedArtifact | None:
        """The loaded executable matching ``program`` at exactly these
        input avals, or None — the dispatch seam's lookup."""
        try:
            key = (program, aval_digest(args))
        except Exception:
            return None
        with self._lock:
            return self._by_program.get(key)

    def cost_for(self, program: str, args: tuple) -> dict | None:
        """The cost record riding with a loaded artifact for ``program``
        at these avals — lets ``costmodel.observe_program`` skip the
        extra trace for fetched programs."""
        la = self.program_for(program, args)
        return dict(la.cost) if la is not None and la.cost else None

    # -- introspection / tests -----------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            loaded = len(self._loaded)
        tiers = {
            tier: {"dir": backend.describe(), "artifacts": len(backend.list())}
            for tier, backend in self.tiers()
        }
        return {"loaded": loaded, "tiers": tiers}

    def reset(self) -> None:
        """Forget loaded executables and the shared-dir wiring (tests);
        on-disk tiers are left alone."""
        with self._lock:
            self._loaded.clear()
            self._by_program.clear()
            self._families.clear()
            self._misses.clear()
            self._shared_dir = None


ARTIFACTS = ArtifactCache()


# -- the dispatch seam --------------------------------------------------------


class _ResolvedProgram:
    """Callable wrapper binding a jitted fn to a possibly-fetched
    executable.  The first call decides: if a loaded artifact matches the
    program name and the exact input avals, dispatch goes through the
    deserialized executable (arming the ambient cost slot from the
    artifact's record); otherwise — or after any artifact-call failure —
    every call goes through the ordinary jit fn.  Single-trial-thread
    object: no locking, mirrors how the step objects themselves are used.
    Attribute access (``.lower`` for costmodel) delegates to the fn."""

    def __init__(self, fn: Callable, program: str, per_report: int = 1):
        self._fn = fn
        self._program = program
        self._per_report = per_report
        self._target: Callable | None = None
        self.source = "jit"  # "artifact" once adopted (tests/telemetry)

    def _bind(self, args: tuple) -> Callable:
        la = ARTIFACTS.program_for(self._program, args)
        if la is None:
            return self._fn
        self.source = "artifact"
        if la.cost:
            try:
                from katib_tpu.costmodel.live import set_active_cost
                from katib_tpu.costmodel.record import CostRecord

                set_active_cost(
                    CostRecord.from_dict(la.cost), per_report=self._per_report
                )
            except Exception:
                pass
        return la

    def __call__(self, *args):
        if self._target is None:
            self._target = self._bind(args)
        try:
            return self._target(*args)
        except Exception:
            if self._target is self._fn:
                raise
            # a fetched executable that cannot dispatch degrades to the
            # jit path permanently (cold compile beats a dead trial); the
            # aval match makes this effectively unreachable, but a bad
            # artifact must never be worse than no artifact
            _log.warning(
                "fetched executable for %s failed to dispatch; falling "
                "back to jit",
                self._program,
                exc_info=True,
            )
            self._target = self._fn
            self.source = "jit-fallback"
            return self._fn(*args)

    def __getattr__(self, name: str):
        return getattr(self._fn, name)


def resolve(fn: Callable, *, program: str, per_report: int = 1) -> Callable:
    """Wrap a jitted step fn so its first dispatch prefers a fetched
    artifact executable (model-side opt-in, like
    ``costmodel.observe_program``).  Free when no artifact is loaded:
    one dict probe on the first call, then direct dispatch."""
    return _ResolvedProgram(fn, program, per_report=per_report)


# -- publish-side ambient offer (prewarm twins) -------------------------------

# the worker needs the jitted fn + representative args a twin just
# compiled in order to AOT-serialize it; twins already hand exactly that
# pair to costmodel.observe_program, which mirrors it here (thread-local,
# same pattern as the ambient cost slot)
import threading  # noqa: E402  (module-scope slot)

_tls = threading.local()


def note_observed(
    fn: Any,
    args: tuple,
    *,
    program: str = "?",
    cost: Mapping[str, Any] | None = None,
) -> None:
    """Record a (jitted fn, args, cost) this thread observed — a publish
    candidate, keyed by program label (latest observation of a label
    wins).  Called by ``costmodel.live.observe_program``; best-effort."""
    offered = getattr(_tls, "offered", None)
    if offered is None:
        offered = _tls.offered = {}
    offered[program] = (fn, args, program, dict(cost) if cost else None)


def take_observed() -> list[tuple[Any, tuple, str, dict | None]]:
    """Drain this thread's publish candidates (prewarm worker, post-twin)."""
    offered = getattr(_tls, "offered", None)
    _tls.offered = None
    return list(offered.values()) if offered else []


def clear_observed() -> None:
    _tls.offered = None


def serialize_compiled(fn: Any, args: tuple) -> Any:
    """AOT-compile ``fn`` at ``args``' avals (sharding-preserving) into a
    serializable ``jax.stages.Compiled``.  With the persistent XLA cache
    wired — the prewarm contract — the twin's just-finished compile makes
    this a deserialization, not a second XLA run.  Raises on programs
    jax cannot AOT here; callers treat that as "don't publish"."""
    import jax

    def aval(a):
        kw = {}
        sharding = getattr(a, "sharding", None)
        if sharding is not None:
            kw["sharding"] = sharding
        return jax.ShapeDtypeStruct(a.shape, a.dtype, **kw)

    avals = jax.tree_util.tree_map(aval, tuple(args))
    return fn.lower(*avals).compile()


def publish_observed(sig: CompileSignature) -> int:
    """Drain this thread's observed programs and publish each as an
    artifact linked to ``sig`` — the prewarm worker's post-twin step,
    shared with benches/CLI paths that ran a twin inline.  Returns how
    many programs actually published (dedupe and failures both skip)."""
    offers = take_observed()
    if not offers or not ARTIFACTS.enabled():
        return 0
    n = 0
    for ofn, oargs, oprog, ocost in offers:
        try:
            compiled = serialize_compiled(ofn, oargs)
            derived = CompileSignature(
                program=oprog,
                shapes=sig.shapes,
                k=sig.k,
                mesh=sig.mesh,
                donation=sig.donation,
            )
            if ARTIFACTS.publish(
                derived, compiled, cost=ocost, parent=sig.key()
            ):
                n += 1
        except Exception:
            _log.warning(
                "artifact publish failed for %s (the compile itself "
                "succeeded)",
                oprog,
                exc_info=True,
            )
    return n


# -- artifact-dir maintenance (fsck / cache verbs) ----------------------------


@dataclass
class ArtifactFsckReport:
    """What ``katib-tpu fsck`` found (and fixed) in an artifact dir."""

    root: str = ""
    scanned: int = 0
    valid: int = 0
    stale: list[str] = field(default_factory=list)  # other-env, intact
    corrupt: list[str] = field(default_factory=list)
    quarantined: list[str] = field(default_factory=list)
    misaddressed: list[str] = field(default_factory=list)

    @property
    def consistent(self) -> bool:
        """True when every remaining envelope is intact and correctly
        addressed (stale-but-intact artifacts are fine: they serve other
        environments sharing the tier)."""
        bad = set(self.corrupt) | set(self.misaddressed)
        return not (bad - set(self.quarantined))

    def summary(self) -> str:
        return (
            f"{self.scanned} artifact(s): {self.valid} valid, "
            f"{len(self.stale)} stale(other-env), "
            f"{len(self.corrupt)} corrupt, "
            f"{len(self.misaddressed)} misaddressed, "
            f"{len(self.quarantined)} quarantined"
        )


def is_artifact_dir(path: str) -> bool:
    """True when ``path`` holds artifact envelopes (``fsck``'s dispatch:
    an experiment workdir and an artifact tier share one verb)."""
    try:
        names = os.listdir(path)
    except OSError:
        return False
    if any(n.endswith(SUFFIX) for n in names):
        return True
    return os.path.basename(os.path.normpath(path)) == "artifacts" or any(
        n.endswith(SUFFIX + QUARANTINE_SUFFIX) for n in names
    )


def fsck_artifacts(path: str, repair: bool = True) -> ArtifactFsckReport:
    """Verify every envelope under an artifact dir: structural integrity,
    checksum, and address correctness (file name == content address of
    its own header).  ``repair`` quarantines corrupt/misaddressed files;
    stale-fingerprint artifacts are reported but left — they are valid
    for the environment that published them."""
    backend = DirectoryBackend(path)
    report = ArtifactFsckReport(root=backend.root)
    fp_now = fingerprint_key(env_fingerprint())
    for name in backend.list():
        report.scanned += 1
        data = backend.get(name)
        if data is None:
            continue  # raced a concurrent quarantine/delete
        try:
            header = read_header(data)
        except ArtifactCorrupt:
            report.corrupt.append(name)
            if repair and backend.quarantine(name):
                report.quarantined.append(name)
                obs.artifact_quarantines.inc(tier="fsck")
            continue
        expect = artifact_name(
            str(header.get("key", "")), header.get("fingerprint") or {}
        )
        if expect != name:
            report.misaddressed.append(name)
            if repair and backend.quarantine(name):
                report.quarantined.append(name)
                obs.artifact_quarantines.inc(tier="fsck")
            continue
        if fingerprint_key(header.get("fingerprint") or {}) != fp_now:
            report.stale.append(name)
        else:
            report.valid += 1
    return report


def scan_dir(path: str) -> list[dict]:
    """Header inventory of an artifact dir (the ``cache`` verb's table):
    one row per envelope with identity, env match, size, and cost."""
    backend = DirectoryBackend(path)
    fp_now = fingerprint_key(env_fingerprint())
    rows: list[dict] = []
    for name in backend.list():
        data = backend.get(name)
        if data is None:
            continue
        row: dict = {"name": name, "bytes": len(data)}
        try:
            header = read_header(data)
        except ArtifactCorrupt as e:
            row.update(status="corrupt", error=str(e))
            rows.append(row)
            continue
        fp = header.get("fingerprint") or {}
        row.update(
            status="ok" if fingerprint_key(fp) == fp_now else "stale",
            program=header.get("program", "?"),
            k=header.get("k", 1),
            mesh=header.get("mesh", ""),
            platform=fp.get("platform", "?"),
            device_kind=fp.get("device_kind", "?"),
            jax=fp.get("jax", "?"),
            cost=bool(header.get("cost")),
            created=header.get("created", 0),
        )
        rows.append(row)
    return rows
