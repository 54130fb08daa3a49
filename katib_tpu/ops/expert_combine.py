"""The way back from an expert layer's sorted buffer to the tokens: one Pallas
kernel that reads only the rows the buffer holds.

An expert layer (``models/mla_moe.py:_routed_rung``) sorts its assignments
by expert.  Its buffer ``rows`` [C, D] holds the rows of the experts held,
group after group (``sizes``); the assignments are numbered token-major
(``t * k + j``: token t's j-th choice) and the sort is stable, so inside a
group the rows lie in token order.  Token t's result is

    sum over j of  dtype(weights[j, t] * rows[pos[j, t]])

in float32, rounded to ``dtype`` once; a choice whose row the buffer does
not hold (``pos`` -1) adds nothing.  A token picks an expert at most once,
so inside expert e the rows of a tile of tokens ``[t0, t0 + tile)`` form one
contiguous run.  ``_runs`` finds every run's start and length from the
counts of each (tile, expert) and their cumulative sums, with no sort.

The kernel (``_kernel``) walks the token tiles.  A tile copies each of its
runs from HBM into VMEM in whole granules (8 rows of 32 bits, 16 of 16: the
DMA's tile), a run's granules split into copies of powers of two, and its
next tile's copies start before its own sum (the grid runs in order).  The
staged rows are summed in blocks of ``ROW_BLOCK`` into a float32 ``[tile,
D]`` accumulator by a 0/1 product on the MXU: a staged row times one for the
token whose choice it is, so the product only moves rows and every sum is of
at most k terms.  Each staged row is scaled by its weight and rounded to
``dtype`` before the product, and a staged row that is no choice of the tile
(a granule's edge, a row past the buffer's held rows) is cleared.  Nothing
``k * T`` rows long exists, forward or backward: the backward's gradient to
the rows is a gather of C rows (``combine``'s rule), and the gradient to the
tokens of ``rows`` (the transpose of the gather into the buffer) is this
kernel again with weights of one.

The kernel's first operand is the weights (float32).  The benchmark finds
the attention kernels by a bfloat16 first operand and the grouped products
by an int32 one, so this kernel is counted as neither.

Where it runs: Mosaic lowers a kernel for one device, or inside shard_map
over every axis of a mesh.  Under a mesh that GSPMD partitions, the way back
is the gather of every assignment's row that the kernel replaces
(``_gathered_way_back``), chosen when the program is lowered, from the axis context
that the lowering sees; the rows it reads are counted as such.  On a backend
other than the TPU the kernel runs interpreted.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive
from jax.interpreters import mlir

from katib_tpu.ops.flash_attention import _TN, VMEM_LIMIT_BYTES

#: tokens a grid step sums (its output block's rows), at most
TOKEN_TILE = 256
#: staged rows one one-hot product takes
ROW_BLOCK = 128


class Routes(NamedTuple):
    """Where the assignments' rows lie in the sorted buffer."""

    pos: jax.Array  # [k, T] int32: the row of token t's j-th choice, -1 where the buffer holds none
    expert: jax.Array  # [k, T] int32: that choice's expert among the held, -1 where ``pos`` is
    index: jax.Array  # [C] int32: the assignment (``t * k + j``) of every row
    sizes: jax.Array  # [held] int32: each held expert's rows, in the buffer's order


def granule(dtype) -> int:
    """Rows of the DMA's tile for an array of ``dtype``: a run is copied in
    whole granules."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _staged_rows(tile: int, k: int, held: int, g: int) -> int:
    """The most rows a tile stages: every token's choices held, each run
    widened to whole granules at both ends."""
    most = tile * min(k, held) + held * (2 * g - 2)
    return -(-most // ROW_BLOCK) * ROW_BLOCK


def _vmem_bytes(tile: int, k: int, held: int, width: int, dtype) -> int:
    """Estimate of the kernel's VMEM at a token tile: two tiles' staged rows
    (the next tile's copies land while this one sums), the float32
    accumulator and two output blocks (float32 at most), and a row block's
    float32 temporaries four times."""
    staged = _staged_rows(tile, k, held, granule(dtype)) * width * jnp.dtype(dtype).itemsize
    return 2 * staged + 3 * tile * width * 4 + 4 * ROW_BLOCK * width * 4


def plan_tile(tokens: int, k: int, held: int, width: int, dtype) -> int:
    """The kernel's token tile for rows of ``dtype``: ``TOKEN_TILE``, or the
    tokens rounded up to a lane's 128 where fewer, halved while
    ``_vmem_bytes`` is over ``VMEM_LIMIT_BYTES``."""
    tile = min(TOKEN_TILE, -(-tokens // 128) * 128)
    while tile > 128 and _vmem_bytes(tile, k, held, width, dtype) > VMEM_LIMIT_BYTES:
        tile //= 2
    return tile


def _runs(expert, sizes, tile: int, g: int):
    """The kernel's table (int32, in SMEM) and the rows it copies.  For each
    token tile, each held expert's run ``[s, s + c)`` of the buffer: the
    granule it starts in (``a``), where its copy lands among the tile's
    staged rows (``o``), how many granules it copies (``n``: 0 for an empty
    run), and after the ``3 * held`` of them the tile's staged rows."""
    k, tokens = expert.shape
    held = sizes.shape[0]
    tiles = tokens // tile
    counts = jnp.sum(
        (expert[:, :, None] == jnp.arange(held)).reshape(k, tiles, tile, held), axis=(0, 2), dtype=jnp.int32
    )  # [tiles, held]
    start = (jnp.cumsum(sizes) - sizes)[None, :] + jnp.cumsum(counts, axis=0) - counts
    a = start // g * g
    n = jnp.where(counts > 0, (start + counts - a + g - 1) // g, 0)
    o = jnp.cumsum(n * g, axis=1) - n * g
    staged = jnp.sum(n * g, axis=1)
    return jnp.concatenate([a, o, n, staged[:, None]], axis=1).reshape(-1), jnp.sum(staged)


def _varying(*avals) -> frozenset:
    """The mesh axes over which any of ``avals`` varies inside shard_map."""
    return frozenset().union(*(aval.vma for aval in avals))


def _kernel(w_ref, e_ref, p_ref, table, rows, out_ref, stage, acc, sem, *, held, g, bits, dtype, precision):
    """One token tile: its runs' copies (the next tile's started), the
    choices' places among the staged rows, then the sum block by block."""
    i = pl.program_id(0)
    k, tile = w_ref.shape
    fields = 3 * held + 1

    def copies(tile_at, slot, act):
        """``act`` on every copy of tile ``tile_at``'s runs into ``stage[slot]``."""
        base = tile_at * fields

        def run(e, carry):
            n = table[base + 2 * held + e]

            @pl.when(n > 0)
            def _():
                a, o, done = table[base + e], table[base + held + e], 0
                for b in reversed(range(bits)):  # the granules as powers of two
                    size, take = g << b, (n >> b) & 1

                    @pl.when(take == 1)
                    def _():
                        act(pltpu.make_async_copy(
                            rows.at[pl.ds(pl.multiple_of(a + done, g), size)],
                            stage.at[slot, pl.ds(pl.multiple_of(o + done, g), size)],
                            sem.at[slot],
                        ))

                    done = done + take * size

            return carry

        jax.lax.fori_loop(0, held, run, 0)

    slot = i % 2

    @pl.when(i == 0)
    def _():
        copies(i, slot, lambda c: c.start())

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        copies(i + 1, 1 - slot, lambda c: c.start())

    # each choice's place among the staged rows, -1 where it has none
    base = i * fields
    expert, pos = e_ref[...], p_ref[...]
    shift = jax.lax.fori_loop(
        0, held, lambda e, s: jnp.where(expert == e, table[base + held + e] - table[base + e], s), jnp.zeros_like(pos)
    )
    place = jnp.where(pos >= 0, pos + shift, -1)
    w = w_ref[...]
    acc[...] = jnp.zeros_like(acc)
    copies(i, slot, lambda c: c.wait())

    def block(b, carry):
        first = pl.multiple_of(b * ROW_BLOCK, ROW_BLOCK)
        staged = first + jax.lax.broadcasted_iota(jnp.int32, (ROW_BLOCK, tile), 0)
        hit = jnp.zeros((ROW_BLOCK, tile), jnp.float32)  # [staged row, token]
        scale = jnp.zeros((ROW_BLOCK, tile), jnp.float32)
        for j in range(k):
            here = staged == place[j : j + 1, :]
            hit = jnp.where(here, 1.0, hit)
            scale = jnp.where(here, w[j : j + 1, :], scale)
        chosen = jnp.max(hit, axis=1, keepdims=True) > 0
        x = stage[slot, pl.ds(first, ROW_BLOCK), :].astype(jnp.float32)
        x = x * jnp.sum(scale, axis=1, keepdims=True)  # a staged row is one choice's at most
        x = jnp.where(chosen, x.astype(dtype), jnp.zeros((), dtype))
        acc[...] += jax.lax.dot_general(
            hit.astype(dtype), x, _TN, preferred_element_type=jnp.float32, precision=precision
        )
        return carry

    blocks = (table[base + 3 * held] + ROW_BLOCK - 1) // ROW_BLOCK
    jax.lax.fori_loop(0, blocks, block, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _kernel_way_back(weights, expert, pos, sizes, rows, *, dtype, interpret):
    k, tokens = weights.shape
    width = rows.shape[1]
    held = sizes.shape[0]
    g = granule(rows.dtype)
    tile = plan_tile(tokens, k, held, width, rows.dtype)
    padded = -(-tokens // tile) * tile
    if padded != tokens:  # the last tile's missing tokens choose nothing
        pad = ((0, 0), (0, padded - tokens))
        weights = jnp.pad(weights, pad)
        expert, pos = jnp.pad(expert, pad, constant_values=-1), jnp.pad(pos, pad, constant_values=-1)
    if rows.shape[0] % g:  # whole granules to copy
        rows = jnp.pad(rows, ((0, g - rows.shape[0] % g), (0, 0)))
    table, copied = _runs(expert, sizes, tile, g)
    dtype = jnp.dtype(dtype)
    # a run's granules: at most its tile's tokens widened at both ends, and
    # never more than the buffer has (the largest copy is a power of two)
    bits = min(-(-(tile + 2 * g - 2) // g), rows.shape[0] // g).bit_length()
    by_tile = pl.BlockSpec((k, tile), lambda i: (0, i))
    out = pl.pallas_call(
        functools.partial(
            _kernel, held=held, g=g, bits=bits, dtype=dtype,
            precision=jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None,
        ),
        grid=(padded // tile,),
        in_specs=[by_tile, by_tile, by_tile, pl.BlockSpec(memory_space=pltpu.SMEM), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (padded, width), dtype, vma=_varying(*(jax.typeof(a) for a in (weights, expert, pos, rows)))
        ),
        scratch_shapes=[
            pltpu.VMEM((2, _staged_rows(tile, k, held, g), width), rows.dtype),
            pltpu.VMEM((tile, width), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # a tile starts its successor's copies: the tiles run in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="expert_way_back",
    )(weights, expert, pos, table, rows)
    return out[:tokens], copied


def _gathered_way_back(weights, expert, pos, sizes, rows, *, dtype):
    """The way back as the gather it replaces: every choice's row, ``k x T``
    of them, a row the buffer does not hold read as nothing."""
    picked = rows.at[jnp.maximum(pos, 0)].get(mode="promise_in_bounds").astype(jnp.float32)
    terms = (weights[..., None] * picked).astype(dtype).astype(jnp.float32)
    return jnp.where(pos[..., None] >= 0, terms, 0.0).sum(axis=0).astype(dtype), jnp.int32(pos.size)


def _kernel_lowers(axis_context) -> bool:
    """Whether Mosaic lowers a kernel in ``axis_context`` (jax's own rule
    for a TPU custom call): one device, or shard_map over every axis."""
    manual = getattr(axis_context, "manual_axes", None)
    if manual is not None:
        every = frozenset(axis_context.mesh.axis_names)
        return not manual or (manual | set(axis_context.mesh.manual_axes)) == every
    return getattr(axis_context, "num_devices", 1) == 1


_way_back = Primitive("expert_way_back")
_way_back.multiple_results = True
_way_back.def_impl(lambda *args, dtype: jax.jit(functools.partial(_way_back.bind, dtype=dtype))(*args))
_way_back.def_abstract_eval(
    lambda weights, expert, pos, sizes, rows, *, dtype: (
        jax.core.ShapedArray((weights.shape[1], rows.shape[1]), dtype, vma=_varying(weights, expert, pos, rows)),
        jax.core.ShapedArray((), jnp.int32, vma=_varying(expert, sizes)),
    )
)


def _lower(ctx, *args, dtype):
    module = ctx.module_context
    if _kernel_lowers(module.axis_context):
        fn = functools.partial(_kernel_way_back, dtype=dtype, interpret=tuple(module.platforms) != ("tpu",))
    else:
        fn = functools.partial(_gathered_way_back, dtype=dtype)
    return mlir.lower_fun(fn, multiple_results=True)(ctx, *args)


mlir.register_lowering(_way_back, _lower)


def way_back(weights, rows, routes: Routes, dtype):
    """Every token's sum of its choices' rows of ``rows`` [C, D], each scaled
    by its weight (``weights`` [k, T] float32) and rounded to ``dtype``, in
    float32, rounded to ``dtype`` once; and the rows read to get it (the
    kernel's copies, granules' edges included; ``k x T`` where the gather
    runs).  No gradient: ``combine`` is the differentiable form."""
    return _way_back.bind(weights, routes.expert, routes.pos, routes.sizes, rows, dtype=jnp.dtype(dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine(weights, rows, routes: Routes, dtype):
    """``way_back`` with its gradients to ``weights`` and ``rows``.  Rows at
    or past ``routes.sizes.sum()`` belong to no choice: their gradient is
    zero whatever they hold."""
    return way_back(weights, rows, routes, dtype)


def _combine_fwd(weights, rows, routes, dtype):
    return way_back(weights, rows, routes, dtype), (weights, rows, routes)


def _combine_bwd(dtype, res, cts):
    weights, rows, routes = res
    g = cts[0]
    k = weights.shape[0]
    tok, choice = routes.index // k, routes.index % k
    held = (jnp.arange(rows.shape[0]) < routes.sizes.sum())[:, None]
    g_rows = g.at[tok].get(mode="promise_in_bounds").astype(jnp.float32)  # C rows
    w_row = weights.at[choice, tok].get(mode="promise_in_bounds")
    d_rows = jnp.where(held, w_row[:, None] * g_rows, 0.0).astype(rows.dtype)
    d_w_row = jnp.where(held, rows.astype(jnp.float32) * g_rows, 0.0).sum(axis=1)
    d_weights = jnp.where(routes.pos >= 0, d_w_row.at[jnp.maximum(routes.pos, 0)].get(mode="promise_in_bounds"), 0.0)
    return d_weights, d_rows, None


combine.defvjp(_combine_fwd, _combine_bwd)
