"""Deviceless TPU-topology AOT compile — cost analysis without a device.

The pip ``libtpu`` can compile a program against a TPU topology
description (``jax.experimental.topologies``): no device, no runtime —
which means the cost/HBM analysis works from a CPU host.
``bench.py``'s AOT child pioneered the
path; it lives here so the bench and the ``katib-tpu cost`` verb share
one implementation.
"""

from __future__ import annotations

import time
from typing import Any

from katib_tpu.costmodel.record import CostRecord, cost_of_compiled

DEFAULT_TOPOLOGY = "v5e:1x1x1"


def topology_device(topology_name: str = DEFAULT_TOPOLOGY) -> Any:
    """First device of a deviceless TPU topology description.  Raises on
    hosts without a TPU-target compiler — callers gate on that."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(
        platform="tpu",
        topology_name=topology_name,
        chips_per_host_bounds=(1, 1, 1),
        num_slices=1,
    )
    return topo.devices[0]


def aot_compile(fn: Any, args: tuple, device: Any) -> tuple[Any, float]:
    """Jit-compile ``fn`` at ``args`` avals for ``device`` (deviceless
    target ok).  Returns ``(compiled, compile_seconds)``; ``args`` may be
    concrete arrays or pytrees thereof — they are reduced to
    single-device-sharded avals before lowering."""
    import jax
    from jax.sharding import SingleDeviceSharding

    def place(a):
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=SingleDeviceSharding(device)
        )

    avals = jax.tree.map(place, tuple(args))
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*avals).compile()
    secs = time.perf_counter() - t0  # lint: unguarded-ok(deviceless AOT: client-side compile is synchronous host work)
    return compiled, secs


def aot_cost(
    fn: Any,
    args: tuple,
    *,
    program: str = "?",
    steps: int = 1,
    dtype: str = "bf16",
    topology_name: str = DEFAULT_TOPOLOGY,
) -> CostRecord | None:
    """One-call deviceless cost extraction: topology -> AOT compile ->
    :class:`CostRecord` (None when no TPU-target compiler is present)."""
    try:
        dev = topology_device(topology_name)
        compiled, _ = aot_compile(fn, args, dev)
    except Exception:
        return None
    return cost_of_compiled(compiled, program=program, steps=steps, dtype=dtype)
