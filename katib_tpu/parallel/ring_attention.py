"""Sequence/context parallelism: ring attention and all-to-all (Ulysses).

The reference has no long-context machinery at all (SURVEY.md §5 "absent");
this module makes the reserved ``seq`` mesh axis real so HP/NAS search over
long-context transformer trials can shard the sequence dimension across
chips instead of replicating O(S) activations.

Two strategies, both over ``jax.shard_map`` on a named mesh axis:

- **ring**: K/V chunks rotate around the ring via ``ppermute`` while every
  device keeps its resident Q chunk; partial attention outputs merge through
  the streaming-softmax identity using the per-row logsumexp emitted by the
  inner kernel (``katib_tpu.ops.flash_attention``).  Communication rides
  neighbour ICI links and overlaps with the block matmuls.
- **ulysses**: two ``all_to_all``s re-shard [heads ↔ sequence] so each
  device runs dense attention for H/size heads over the full sequence.
  Cheaper collectives on small meshes; requires heads % axis_size == 0.

Causality is decided at chunk granularity: a device's Q chunk attends fully
to earlier chunks, causally to its own, and skips later ones (the skip
branch contributes logsumexp=-1e30, an exact no-op in the merge — and
``lax.switch`` means the skipped matmuls are never executed).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from katib_tpu.ops.flash_attention import (
    _MASK_VALUE,
    flash_attention_with_lse,
    reference_attention_with_lse,
)
from katib_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS

InnerAttention = Callable[..., tuple[jax.Array, jax.Array]]


def default_inner(block_q: int = 128, block_k: int = 128) -> InnerAttention:
    """Per-chunk attention kernel: Pallas flash on TPU, dense jnp elsewhere
    (interpret-mode Pallas inside shard_map is correct but far too slow for
    the 8-device CPU test mesh)."""
    if jax.default_backend() == "tpu":
        # positional call: custom_vjp functions reject keyword arguments
        return lambda q, k, v, causal: flash_attention_with_lse(
            q, k, v, causal, None, block_q, block_k, None
        )
    return reference_attention_with_lse


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = SEQ_AXIS,
    axis_size: int,
    causal: bool = True,
    inner: InnerAttention | None = None,
) -> jax.Array:
    """Ring attention over local chunks — call inside ``shard_map`` with
    q/k/v of shape [batch, heads, seq_local, head_dim], sequence dimension
    sharded on ``axis_name`` in contiguous chunks."""
    if inner is None:
        inner = default_inner()
    my = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    perm = [(r, (r + 1) % axis_size) for r in range(axis_size)]

    def chunk_full(kv):
        kc, vc = kv
        return inner(q, kc, vc, False)

    def chunk_diag(kv):
        kc, vc = kv
        return inner(q, kc, vc, True)

    def chunk_skip(kv):
        return (
            jnp.zeros((b, h, s_local, d), q.dtype),
            jnp.full((b, h, s_local), _MASK_VALUE, jnp.float32),
        )

    def step(carry, t):
        o_acc, lse_acc, k_cur, v_cur = carry
        j = (my - t) % axis_size  # origin rank of the kv chunk we now hold
        if causal:
            branch = jnp.where(j < my, 0, jnp.where(j == my, 1, 2))
            o_i, lse_i = jax.lax.switch(
                branch, [chunk_full, chunk_diag, chunk_skip], (k_cur, v_cur)
            )
        else:
            o_i, lse_i = chunk_full((k_cur, v_cur))
        lse_new = jnp.logaddexp(lse_acc, lse_i)
        w_acc = jnp.exp(lse_acc - lse_new)[..., None]
        w_i = jnp.exp(lse_i - lse_new)[..., None]
        # the accumulator stays float32 across the whole ring: casting back
        # to bf16 every step would round-trip the output axis_size times
        o_new = o_acc * w_acc + o_i.astype(jnp.float32) * w_i
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o_new, lse_new, k_nxt, v_nxt), None

    o0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    lse0 = jnp.full((b, h, s_local), _MASK_VALUE, jnp.float32)
    (o, _, _, _), _ = jax.lax.scan(
        step, (o0, lse0, k, v), jnp.arange(axis_size)
    )
    return o.astype(q.dtype)


def ulysses_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = SEQ_AXIS,
    axis_size: int,
    causal: bool = True,
    inner: InnerAttention | None = None,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism: re-shard
    [B, H, S/n, D] → [B, H/n, S, D], attend over the full sequence, shard
    back.  Heads must divide by the axis size."""
    if inner is None:
        inner = default_inner()
    h = q.shape[1]
    if h % axis_size:
        raise ValueError(
            f"heads ({h}) must be a multiple of the seq-axis size ({axis_size})"
        )

    def scatter_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    o, _ = inner(qg, kg, vg, causal)
    return jax.lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1, tiled=True)


def make_sequence_parallel_attention(
    mesh: Mesh,
    *,
    strategy: str = "ring",
    causal: bool = True,
    axis_name: str = SEQ_AXIS,
    inner: InnerAttention | None = None,
) -> Callable[[jax.Array, jax.Array, jax.Array], jax.Array]:
    """Build ``attn(q, k, v) -> o`` over global [B, H, S, D] arrays: batch
    sharded on the mesh's data axis, sequence on its seq axis.

    With a size-1 (or absent) seq axis this degenerates to plain single-chip
    flash attention — the same code path from one chip to a v5e-64 slice.
    """
    axis_size = mesh.shape.get(axis_name, 1)
    batch_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None

    if axis_size == 1:
        def attn_single(q, k, v):
            inn = inner if inner is not None else default_inner()
            o, _ = inn(q, k, v, causal)
            return o

        return attn_single

    if strategy == "ring":
        local = functools.partial(
            ring_attention_local,
            axis_name=axis_name, axis_size=axis_size, causal=causal, inner=inner,
        )
    elif strategy == "ulysses":
        local = functools.partial(
            ulysses_attention_local,
            axis_name=axis_name, axis_size=axis_size, causal=causal, inner=inner,
        )
    else:
        raise ValueError(f"unknown sequence-parallel strategy {strategy!r}")

    spec = P(batch_axis, None, axis_name, None)

    def attn(q, k, v):
        # check_vma off: the ring's ppermute carry confuses the
        # replication check
        return jax.shard_map(
            lambda a, b, c: local(a, b, c),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return attn
