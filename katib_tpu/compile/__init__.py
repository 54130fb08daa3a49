"""Where a trial's compiled program comes from.

A compiled program has two homes, and no third:

- below, jax's persistent compilation cache: one directory a process,
  placed once by ``runner.trial_runner.init_compile_cache``.  A program
  some process has compiled there is read back instead of compiled again;
  an entry that is missing or damaged is a cache miss, and the program
  compiles;
- above, a process-wide table of jitted programs, kept by the model that
  owns them (``models/transformer.py:_PROGRAMS``,
  ``models/mnist.py:_STEP_CACHE``): trials of one structure call the same
  function object, so a process traces and loads a program once.

What is in this package serves those two:

- :mod:`katib_tpu.compile.buckets` quantizes cohort width K onto a few
  padded power-of-two sizes, so heterogeneous cohorts collapse onto a
  handful of programs (the inert ghost-member padding from
  ``runner/cohort.py`` makes the extra rows free);
- :mod:`katib_tpu.compile.prewarm` warms the lower home: a strictly
  best-effort background worker (and the ``prewarm`` verb) that calls a
  train function's compile-only twin for the programs the orchestrator's
  proposal groups will need, while current trials execute;
- :mod:`katib_tpu.compile.registry` records every (program, shapes, mesh,
  donation) signature compiled, dedupes prewarm requests on it, and labels
  each trial's first step warm or cold.
"""

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
