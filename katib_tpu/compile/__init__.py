"""Compile amortization: shape bucketing, a compile-signature registry, and
a background prewarm worker.

An earlier round's record puts a live XLA compile at 470s against a 0.54s
step — at fleet trial volumes compilation, not training, is the bill.  Three coordinated
pieces keep cohort dispatches on a warm cache:

- :mod:`katib_tpu.compile.buckets` quantizes cohort width K onto a few
  padded power-of-two sizes, so heterogeneous cohorts collapse onto a
  handful of cached executables (the inert ghost-member padding from
  ``runner/cohort.py`` makes the extra rows free);
- :mod:`katib_tpu.compile.registry` records every (program, shapes, mesh,
  donation) signature compiled and classifies each trial's first step
  warm/cold, exporting hit/miss counters and compile-time histograms;
- :mod:`katib_tpu.compile.prewarm` runs a strictly best-effort background
  worker that compiles upcoming cohort programs (fed by the orchestrator's
  proposal groups) while current trials execute, so the next cohort's
  first step deserializes instead of recompiling;
- :mod:`katib_tpu.compile.artifacts` makes compiled executables portable
  *across hosts*: serialized AOT executables in a content-addressed,
  tiered artifact cache (local dir → shared dir → cold compile) keyed by
  compile signature + environment fingerprint, so a brand-new host's
  first step fetches instead of compiling.
"""

from katib_tpu.compile.artifacts import (  # noqa: F401
    ARTIFACTS,
    ArtifactCache,
    DirectoryBackend,
    LoadedArtifact,
    env_fingerprint,
    fsck_artifacts,
    is_artifact_dir,
    resolve,
)
from katib_tpu.compile.buckets import (  # noqa: F401
    bucket_size,
    bucket_table,
    bucketed_cohort_size,
    next_pow2,
)
from katib_tpu.compile.prewarm import (  # noqa: F401
    PrewarmRequest,
    PrewarmWorker,
    attach_prewarm_fn,
    prewarm_fn_of,
)
from katib_tpu.compile.registry import (  # noqa: F401
    REGISTRY,
    CompileSignature,
    ShapeRegistry,
    cohort_signature,
    shared_structural,
    trial_signature,
)
