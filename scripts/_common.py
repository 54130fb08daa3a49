"""Shared bootstrap for the repo's run scripts — one copy of the
environment/bootstrap logic so the harnesses can't drift.

Import `REPO` and call `setup_jax(...)` BEFORE importing jax-heavy modules.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def setup_jax(
    *,
    force_platform: str | None = None,
    virtual_devices: int = 0,
    compile_cache: bool = False,
):
    """Configure JAX and return the imported module.

    ``force_platform``: hard-select a platform before jax is imported (CPU
    demos pass "cpu").  ``None`` honors the ambient JAX_PLATFORMS.
    ``virtual_devices``: forced-host-platform CPU device count for mesh demos.
    ``compile_cache``: wire the persistent XLA cache through the runner's
    ``init_compile_cache`` (JAX_COMPILATION_CACHE_DIR, else .jax_cache).
    """
    if virtual_devices and "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={virtual_devices}"
        ).strip()
    if force_platform is not None:
        os.environ["JAX_PLATFORMS"] = force_platform

    import jax

    if compile_cache:
        from katib_tpu.runner.trial_runner import init_compile_cache

        init_compile_cache()
    return jax


def artifacts_root() -> str:
    """The artifact tree root.  KATIB_ARTIFACTS_DIR redirects it —
    integration tests run the real scripts without clobbering the
    committed artifacts/ — and every writer AND reader of artifact paths
    must resolve through here so a redirect can't split them.  One
    definition, shared with in-package readers (the dashboard's
    flagship-progress endpoint)."""
    from katib_tpu.utils.paths import artifacts_root as _shared

    return _shared()


def write_artifact(subdir: str, name: str, payload: dict) -> str:
    out_dir = os.path.join(artifacts_root(), subdir)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path
