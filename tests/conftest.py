"""Test configuration: an 8-device virtual CPU platform, so sharding tests
exercise real multi-device meshes without TPU hardware (the driver's dryrun
uses the same mechanism), and a compile cache of the test session's own.
"""

import atexit
import os
import shutil
import sys
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# the program's persistent compile cache is always on (last resort:
# <checkout>/.jax_cache).  Tests get a directory per worker process
# instead: a shape registry left by an earlier run would turn every
# "cold first step" a test expects into a warm one.
if not os.environ.get("KATIB_COMPILE_CACHE"):
    _cache = tempfile.mkdtemp(prefix="katib-test-cache-")
    os.environ["KATIB_COMPILE_CACHE"] = _cache
    atexit.register(shutil.rmtree, _cache, ignore_errors=True)
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)  # it would outrank the above

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
