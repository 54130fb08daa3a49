"""Blockwise (flash) attention as Pallas TPU kernels.

The reference delegates all attention math to PyTorch/TF inside trial
containers (it has none of its own — SURVEY.md §2.4); here attention is a
first-class fused kernel so HP/NAS search over transformer trials runs at
MXU speed without materialising the [S, S] score matrix in HBM.

Design (FlashAttention-2 style, adapted to the TPU memory hierarchy):

- forward: grid over (batch, head, q tile); a head's K and V sit in VMEM and
  stream through in k tiles while an online softmax keeps running (max, sum,
  output) accumulators in f32.  Emits the per-row logsumexp so
  sequence-parallel ring attention (``katib_tpu.parallel.ring_attention``)
  can merge partial results from other sequence shards.
- backward: ONE kernel that walks every visible (q tile, k tile) pair once
  (``_walk_kernel``): it recomputes the probabilities from the saved
  logsumexp instead of storing the score matrix (rematerialisation trades
  FLOPs for HBM, the TPU-native default), forms ``dS`` once and takes dq, dk
  and dv from them: five products a pair.  It walks as the dq kernel does (a
  q tile against a head's K and V) and dk, dv add up in two float32
  accumulators a head long, in VMEM.  Where those do not fit (``one_walk``:
  the estimate of ``vmem_bytes`` against ``VMEM_LIMIT_BYTES``, from the
  call's shapes, dtype and tiles alone; a 16384-key head of width 128 fits, a
  32768-key one does not) two kernels run instead, dq over q tiles and dk/dv
  over k tiles, each walking the pairs: seven products a pair.
- both are exposed through one ``jax.custom_vjp`` so ``jax.grad`` composes
  with jit/shard_map/scan.  Its forward rule names the kernel's two results
  (``KERNEL_RESULTS``), and ``remat_block`` rematerialises a flax module
  under the policy that keeps arrays of those names: the backward kernels and
  the output projection's weight gradient read the output and logsumexp the
  forward wrote, and the rematerialised forward holds no attention kernel.

Precision follows the inputs' dtype.  Every product takes its operands as
they come (q, k, v, dO) or cast to that dtype (the probabilities ``p`` and
``dS`` before P.V, P^T.dO, dS.K and dS^T.Q) and accumulates in float32; the
softmax scale is applied to the float32 scores; the running max and sum, the
logsumexp, ``delta``, the exponent and every accumulator are float32.  So
float32 inputs agree with a dense jnp reference to ~1e-5, and bfloat16
inputs to bfloat16's rounding of ``p`` (2**-8 of the largest value summed,
and as much again for the output's own rounding): the precision a bfloat16
model states, and what a dense bfloat16 attention does to its ``probs``.

Tiles: ``block_q`` / ``block_k`` where the caller names them, else
``plan_tiles`` from the lengths, widths and dtype under a stated VMEM
budget.  The row statistics (logsumexp, delta) cross HBM lane-dense,
``[batch, heads, 1, seq]``.

Keys and values may have fewer heads than the queries (grouped-query
attention: query head ``j`` reads key-value head ``j // group``); ``dk`` and
``dv`` are then summed over a group's query heads inside the kernel, in
float32: the walk's accumulators take every head of the group, the dkv kernel
sums them over one more grid axis.  ``window``: a query sees the ``window``
newest keys up to itself; tiles wholly outside the band are skipped by the
loop bounds as tiles wholly above the diagonal are (``tile_visits`` counts
what the loops walk against what holds a visible pair).

On non-TPU backends (CPU tests, the 8-device virtual mesh) the kernels run
in interpreter mode automatically.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
_MASK_VALUE = -1e30  # large-negative instead of -inf inside kernels (no NaNs)

#: what a kernel may hold in VMEM (``vmem_limit_bytes``; the compiler's own
#: default is 16 MiB of a v5e core's 128 MiB).  ``plan_tiles`` keeps its
#: estimate of the streaming kernels' blocks under half of it: the other half
#: is the compiler's, for the temporaries of a tile's elementwise work.  The
#: single walk's blocks are a head long whatever the tiles, and it runs where
#: its estimate is within the limit itself (``one_walk``).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES // 2
#: tiles in order of preference, read on the chip at the benchmark's shapes
#: (PERF.md section 6, PR 32): q tiles (rows streamed through the MXU per
#: k tile loaded), then k tiles (the width of a score tile)
_Q_TILES = (512, 256, 128)
_K_TILES = (512, 256, 128)

#: the names ``_vjp_fwd`` gives the forward kernel's output and logsumexp
KERNEL_RESULTS = ("flash_out", "flash_lse")
# one policy object for every rematerialised block: a block's program names it
_KEEP_KERNEL_RESULTS = jax.checkpoint_policies.save_only_these_names(*KERNEL_RESULTS)

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _padded(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a [rows, cols] block in VMEM: (8, 128) tiles of 32 bits,
    (16, 128) of 16."""
    sublanes = 8 * 4 // itemsize
    return -(-rows // sublanes) * sublanes * -(-cols // 128) * 128 * itemsize


def _kernel_bytes(seq_q, seq_k, d_k, d_v, dtype, bq, bk) -> dict[str, int]:
    """Estimate of each kernel's VMEM blocks at tiles ``(bq, bk)``, the score
    tiles aside: pipelined blocks twice (double-buffered) and the float32
    accumulators."""
    size = jnp.dtype(dtype).itemsize

    def row(n):  # a lane-dense row of statistics
        return _padded(1, n, 4)

    kv_head = _padded(seq_k, d_k, size) + _padded(seq_k, d_v, size)  # a head's K and V, or dk and dv
    q_tiles = 2 * _padded(bq, d_k, size) + _padded(bq, d_v, size) + 2 * row(bq)  # q, dq, dO, lse, dmd
    return {
        "forward": 2 * (_padded(bq, d_k, size) + kv_head + _padded(bq, d_v, size) + row(bq))
        + _padded(d_v, bq, 4),
        "dq": 2 * (q_tiles + kv_head) + _padded(d_k, bq, 4),
        "dkv": 2 * (
            _padded(seq_q, d_k, size) + _padded(seq_q, d_v, size) + 2 * row(seq_q)
            + 2 * _padded(bk, d_k, size) + 2 * _padded(bk, d_v, size)
        ) + _padded(bk, d_k, 4) + _padded(bk, d_v, 4),
        # the dq kernel's blocks, a head's dk and dv on the way out, and the
        # two whole-head accumulators
        "walk": 2 * (q_tiles + 2 * kv_head) + _padded(d_k, bq, 4)
        + _padded(seq_k, d_k, 4) + _padded(seq_k, d_v, 4),
    }


def one_walk(seq_q: int, seq_k: int, d_k: int, d_v: int, dtype, bq: int, bk: int) -> bool:
    """Whether the backward at these shapes and tiles is the single walk: where
    its blocks (a head's K, V, dk, dv twice and two float32 accumulators as
    long as the keys) and the score tiles are within ``VMEM_LIMIT_BYTES``, what
    the kernel asks of the compiler; where not, the dq and dkv kernels run.
    The estimate counts everything the kernel allocates, so it is held to the
    limit, not to the budget the tiles are planned under: the v5e's compiler
    accepts the walk up to an estimate of 77 MiB under this limit and refuses
    it at 89 (PERF.md section 6, PR 40; ``tests/test_chip_compile.py``
    compiles the rule's edge in bfloat16 and float32, at 512 and 1024 tiles)."""
    return vmem_bytes(seq_q, seq_k, d_k, d_v, dtype, bq, bk, "walk") <= VMEM_LIMIT_BYTES


def vmem_bytes(seq_q: int, seq_k: int, d_k: int, d_v: int, dtype, bq: int, bk: int, backward: str) -> int:
    """Estimate of the largest VMEM need of the kernels that run at tiles
    ``(bq, bk)`` where the backward is ``backward`` (``"walk"`` or
    ``"dq+dkv"``): four float32 tiles of scores (s, p, dp, ds) and the largest
    kernel's blocks, the forward's or the backward's (the larger of dq's and
    dkv's where those run)."""
    blocks = _kernel_bytes(seq_q, seq_k, d_k, d_v, dtype, bq, bk)
    largest = blocks["walk"] if backward == "walk" else max(blocks["dq"], blocks["dkv"])
    return 4 * _padded(bq, bk, 4) + max(blocks["forward"], largest)


def plan_tiles(seq_q: int, seq_k: int, d_k: int, d_v: int, dtype) -> tuple[int, int]:
    """The (q tile, k tile) the kernels run where the caller names none, from
    what they can observe: the lengths, the widths and the operand dtype.
    The first pair of ``_Q_TILES`` x ``_K_TILES`` that divides the lengths and
    at which the blocks of the kernels that stream tiles (forward, dq, dkv:
    ``vmem_bytes`` of that backward) are within ``VMEM_BUDGET_BYTES``; a length
    that none divides (or shorter than 128) is one tile."""

    def candidates(seq, tiles):
        return [t for t in tiles if seq % t == 0] or [seq]

    pairs = [(bq, bk) for bq in candidates(seq_q, _Q_TILES) for bk in candidates(seq_k, _K_TILES)]
    for bq, bk in pairs:
        if vmem_bytes(seq_q, seq_k, d_k, d_v, dtype, bq, bk, "dq+dkv") <= VMEM_BUDGET_BYTES:
            return bq, bk
    return pairs[-1]


def _block_sizes(q, k, v, block_q: int | None, block_k: int | None):
    """Explicit tiles as given (clamped to the lengths), the plan's where
    ``None``; a tile that does not divide its length raises."""
    seq_q, seq_k = q.shape[2], k.shape[2]
    if block_q is None or block_k is None:
        planned = plan_tiles(seq_q, seq_k, q.shape[3], v.shape[3], q.dtype)
        block_q = planned[0] if block_q is None else block_q
        block_k = planned[1] if block_k is None else block_k
    bq, bk = min(block_q, seq_q), min(block_k, seq_k)
    if seq_q % bq or seq_k % bk:
        raise ValueError(
            f"block sizes ({bq}, {bk}) must divide sequence lengths ({seq_q}, {seq_k})"
        )
    return bq, bk


def _pallas_call(kernel, *, grid, parallel=3, **kwargs):
    """The first ``parallel`` grid axes are parallel (batch, head, tile); the
    rest accumulate into blocks that stay in VMEM across them: the query heads
    of a key-value head in the dkv kernel, they and the q tiles in the walk."""
    return pl.pallas_call(
        kernel,
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * parallel + ("arbitrary",) * (len(grid) - parallel),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        **kwargs,
    )


def _group_size(q, k) -> int:
    """Query heads a key-value head."""
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"{q.shape[1]} query heads are not a multiple of {k.shape[1]} key-value heads"
        )
    return q.shape[1] // k.shape[1]


def _check_window(window, causal) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window {window!r} needs causal attention and at least one key")


# ---------------------------------------------------------------------------
# which tiles the causal mask and the window leave
# ---------------------------------------------------------------------------
#
# ``shift = seq_k - seq_q`` makes the causal mask bottom-right aligned (last
# query row sees every key), matching ``reference_attention_with_lse`` for
# seq_q != seq_k: key ``c`` is visible to query ``r`` when ``c <= r + shift``
# and, under a window, ``c > r + shift - window``.  Tiles wholly above the
# diagonal or wholly below the window are skipped by the loop bounds; every
# other tile of a causal call is masked (a second, mask-free loop body for the
# tiles wholly inside read slower on the chip at both benchmark shapes:
# PERF.md section 6, PR 32).


def _k_tile_range(qi, bq, bk, n_kb, shift, causal, window):
    """k tiles ``[first, end)`` hold a key that a row of q tile ``qi`` sees:
    up to its last row's own key, from the oldest key its first row's window
    reaches."""
    if not causal:
        return 0, n_kb
    end = jnp.minimum(pl.cdiv(jnp.maximum((qi + 1) * bq + shift, 0), bk), n_kb)
    if window is None:
        return 0, end
    return jnp.maximum(qi * bq + shift - window + 1, 0) // bk, end


def _q_tile_range(ki, bq, bk, n_qb, shift, causal, window):
    """q tiles ``[first, end)`` hold a row that sees a key of k tile ``ki``:
    from the row of its first key, to the last row whose window reaches its
    last key."""
    if not causal:
        return 0, n_qb
    first = jnp.minimum(jnp.maximum(ki * bk - shift, 0) // bq, n_qb)
    if window is None:
        return first, n_qb
    rows = jnp.maximum((ki + 1) * bk - 1 - shift + window, 0)  # rows [0, rows) can
    return first, jnp.minimum(pl.cdiv(rows, bq), n_qb)


def _visible(shape, k0, q0, window):
    """The mask of a [keys, queries] tile: keys from ``k0``, queries from
    ``q0`` (the shift included)."""
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if window is None:
        return k_pos <= q_pos
    return (k_pos <= q_pos) & (k_pos > q_pos - window)


@functools.lru_cache(maxsize=64)
def tile_visits(
    seq_q: int, seq_k: int, bq: int, bk: int, causal: bool = True, window: int | None = None, *, walk: bool
) -> tuple[int, int]:
    """For one batch row and query head: the (q tile, k tile) pairs the
    kernels' loops walk in a forward and a backward (their own bounds,
    evaluated), and the least any such walks can make: the tiles of this size
    that hold a visible pair, once a walk.  Two walks where the backward is
    the single ``walk`` (forward and it: ``_k_tile_range`` of every q tile,
    twice), three where dq and dkv run (dkv: ``_q_tile_range`` of every k
    tile)."""
    n_qb, n_kb, shift = seq_q // bq, seq_k // bk, seq_k - seq_q

    def walked(tile_range, n_tiles, n_across):  # a bound may be one number for all tiles
        first, end = tile_range(jnp.arange(n_tiles), bq, bk, n_across, shift, causal, window)
        return int(jnp.sum(jnp.broadcast_to(jnp.maximum(end - first, 0), (n_tiles,))))

    per_q_tile = walked(_k_tile_range, n_qb, n_kb)
    # a tile holds a visible pair when its last row reaches its first key and
    # its first row's window still reaches its last key
    last_row = (np.arange(n_qb)[:, None] + 1) * bq - 1 + shift
    first_row = np.arange(n_qb)[:, None] * bq + shift
    first_key, last_key = np.arange(n_kb) * bk, (np.arange(n_kb) + 1) * bk - 1
    holds = np.ones((n_qb, n_kb), bool)
    if causal:
        holds = first_key <= last_row
        if window is not None:
            holds &= last_key > first_row - window
    if walk:
        return 2 * per_q_tile, 2 * int(holds.sum())
    return 2 * per_q_tile + walked(_q_tile_range, n_kb, n_qb), 3 * int(holds.sum())


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal, block_k, shift, window):
    """One q tile against the k tiles it sees.  Scores are held transposed,
    [block_k, bq]: the running max and sum are then reductions along sublanes
    (elementwise across registers) and live in lane-dense [1, bq] rows, where
    reductions along lanes cost more than the products at these widths."""
    bq, d_v = q_ref.shape[-2], v_ref.shape[-1]
    n_kb = k_ref.shape[-2] // block_k
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :]

    def body(j, carry):
        o_acc, m_acc, l_acc = carry  # [d_v, bq], [1, bq], [1, bq]
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, 0, pl.ds(start, block_k), :]
        v = v_ref[0, 0, pl.ds(start, block_k), :]
        # operands in the inputs' dtype, scores and everything after in f32
        s = sm_scale * jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(_visible(s.shape, start, qi * bq + shift, window), s, _MASK_VALUE)
        m_new = jnp.maximum(m_acc, jnp.max(s, axis=0, keepdims=True))
        # a row that has seen no key yet has m_new == _MASK_VALUE and p == 1
        # on masked keys: wiped by alpha == 0 at its first visible key, or at
        # the end if there is none
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_acc - m_new)
        l_new = l_acc * alpha + jnp.sum(p, axis=0, keepdims=True)
        pv = jax.lax.dot_general(v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
        return o_acc * alpha + pv, m_new, l_new

    o0 = jnp.zeros((d_v, bq), jnp.float32)
    m0 = jnp.full((1, bq), _MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((1, bq), jnp.float32)
    first, end = _k_tile_range(qi, bq, block_k, n_kb, shift, causal, window)
    o, m, l = jax.lax.fori_loop(first, end, body, (o0, m0, l0))

    # rows with every key masked: output 0, log-sum-exp the mask value
    dead = m == _MASK_VALUE
    l_safe = jnp.where(dead, 1.0, l)
    o_ref[0, 0, :, :] = jnp.where(dead, 0.0, o / l_safe).T.astype(o_ref.dtype)
    # row statistics leave the kernel lane-dense, [1, bq]: a [bq, 1] block is
    # padded to 128 lanes in VMEM and in HBM
    lse_ref[0, 0, :, :] = jnp.where(dead, _MASK_VALUE, m + jnp.log(l_safe))


def _kv_head(group: int):
    """The key-value head of query head ``j`` (an index map's head entry)."""
    return (lambda j: j) if group == 1 else (lambda j: j // group)


def _fwd(q, k, v, *, sm_scale, causal, block_q, block_k, interpret, window):
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[3]
    bq, bk = _block_sizes(q, k, v, block_q, block_k)
    kv = _kv_head(_group_size(q, k))
    o, lse = _pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, block_k=bk, shift=sk - sq, window=window
        ),
        grid=(b, h, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda i, j, l: (i, kv(j), 0, 0)),
            pl.BlockSpec((1, 1, sk, d_v), lambda i, j, l: (i, kv(j), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d_v), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda i, j, l: (i, j, 0, l)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d_v), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dmd_ref, dq_ref, *, sm_scale, causal, block_k, shift, window):
    """dq for one q tile; streams K/V tiles.  ``dmd`` = rowsum(dO*O) - d_lse,
    folding the logsumexp cotangent into the usual flash "delta" term.
    Transposed like the forward, [block_k, bq]: the row statistics broadcast
    along sublanes from their lane-dense rows."""
    bq, d = q_ref.shape[-2], q_ref.shape[-1]
    n_kb = k_ref.shape[-2] // block_k
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse = lse_ref[0, 0, :, :]  # [1, bq]
    dmd = dmd_ref[0, 0, :, :]

    def body(j, dq_acc):
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, 0, pl.ds(start, block_k), :]
        v = v_ref[0, 0, pl.ds(start, block_k), :]
        s = sm_scale * jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        e = s - lse
        if causal:
            e = jnp.where(_visible(e.shape, start, qi * bq + shift, window), e, _MASK_VALUE)
        p = jnp.exp(e)  # [block_k, bq]
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - dmd)).astype(k.dtype)
        return dq_acc + jax.lax.dot_general(k, ds, _TN, preferred_element_type=jnp.float32)

    first, end = _k_tile_range(qi, bq, block_k, n_kb, shift, causal, window)
    dq = jax.lax.fori_loop(first, end, body, jnp.zeros((d, bq), jnp.float32))
    dq_ref[0, 0, :, :] = (sm_scale * dq).T.astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dmd_ref, dk_ref, dv_ref, *sums,
    sm_scale, causal, block_q, shift, window, group,
):
    """dk, dv for one k tile; streams the q tiles (with their dO/lse/delta) of
    one query head.  Transposed too, [bk, block_q], which makes every product
    here a plain ``a @ b`` or ``a @ b.T``.  With ``group`` query heads a
    key-value head the fourth grid axis walks them, the float32 ``sums`` (two
    VMEM scratch blocks) add their parts up, and the last writes."""
    bk, d = k_ref.shape[-2], k_ref.shape[-1]
    n_qb = q_ref.shape[-2] // block_q
    ki = pl.program_id(2)
    k = k_ref[0, 0, :, :]
    v = v_ref[0, 0, :, :]

    def body(i, carry):
        dk_acc, dv_acc = carry
        start = pl.multiple_of(i * block_q, block_q)
        q = q_ref[0, 0, pl.ds(start, block_q), :]
        do = do_ref[0, 0, pl.ds(start, block_q), :]
        lse = lse_ref[0, 0, :, pl.ds(start, block_q)]  # [1, block_q]
        dmd = dmd_ref[0, 0, :, pl.ds(start, block_q)]
        s = sm_scale * jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        e = s - lse
        if causal:
            e = jnp.where(_visible(e.shape, ki * bk, start + shift, window), e, _MASK_VALUE)
        p = jnp.exp(e)  # [bk, block_q]
        dv_new = dv_acc + jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - dmd)).astype(q.dtype)
        dk_new = dk_acc + jnp.dot(ds, q, preferred_element_type=jnp.float32)
        return dk_new, dv_new

    zeros = (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, v_ref.shape[-1]), jnp.float32))
    first, end = _q_tile_range(ki, block_q, bk, n_qb, shift, causal, window)
    dk, dv = jax.lax.fori_loop(first, end, body, zeros)

    def write(dk, dv):
        dk_ref[0, 0, :, :] = (sm_scale * dk).astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)

    if group == 1:
        write(dk, dv)
        return
    dk_sum, dv_sum = sums
    member = pl.program_id(3)

    @pl.when(member == 0)
    def _():
        dk_sum[...] = dk
        dv_sum[...] = dv

    @pl.when(member > 0)
    def _():
        dk_sum[...] += dk
        dv_sum[...] += dv

    @pl.when(member == group - 1)
    def _():
        write(dk_sum[...], dv_sum[...])


def _walk_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dmd_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc,
    *, sm_scale, causal, block_k, shift, window,
):
    """The whole backward in one walk: one q tile of one query head against
    the k tiles it sees, as the dq kernel (its scores, transposed, and its
    ``dmd``), and ``p`` and ``dS`` of every pair feed all three gradients.
    dq stays in registers over the k tiles; dk and dv add into two whole-head
    float32 accumulators (VMEM scratch, ``[S_k, d]``) that every q tile and
    every query head of the key-value head share: cleared at the head's first
    grid step, written out at its last."""
    bq, d = q_ref.shape[-2], q_ref.shape[-1]
    n_kb = k_ref.shape[-2] // block_k
    member, qi = pl.program_id(2), pl.program_id(3)

    @pl.when((member == 0) & (qi == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse = lse_ref[0, 0, :, :]  # [1, bq]
    dmd = dmd_ref[0, 0, :, :]

    def body(j, dq_acc):
        start = pl.multiple_of(j * block_k, block_k)
        rows = pl.ds(start, block_k)
        k = k_ref[0, 0, rows, :]
        v = v_ref[0, 0, rows, :]
        s = sm_scale * jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        e = s - lse
        if causal:
            e = jnp.where(_visible(e.shape, start, qi * bq + shift, window), e, _MASK_VALUE)
        p = jnp.exp(e)  # [block_k, bq]
        # dv's product before dp's, as the dkv kernel has them: the other
        # order read 0.4-1.0% slower a step on the chip (PERF.md section 6, PR 40)
        dv_acc[rows, :] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - dmd)).astype(k.dtype)
        dk_acc[rows, :] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        return dq_acc + jax.lax.dot_general(k, ds, _TN, preferred_element_type=jnp.float32)

    first, end = _k_tile_range(qi, bq, block_k, n_kb, shift, causal, window)
    dq = jax.lax.fori_loop(first, end, body, jnp.zeros((d, bq), jnp.float32))
    dq_ref[0, 0, :, :] = (sm_scale * dq).T.astype(dq_ref.dtype)

    @pl.when((member == pl.num_programs(2) - 1) & (qi == pl.num_programs(3) - 1))
    def _():
        dk_ref[0, 0, :, :] = (sm_scale * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, dlse, *, sm_scale, causal, block_q, block_k, interpret, window):
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[3]
    bq, bk = _block_sizes(q, k, v, block_q, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dmd = delta - dlse.astype(jnp.float32)  # [b, h, sq]
    operands = (q, k, v, do, lse[:, :, None, :], dmd[:, :, None, :])
    kernel = dict(sm_scale=sm_scale, causal=causal, shift=sk - sq, window=window)
    backward = _bwd_walk if one_walk(sq, sk, d, d_v, q.dtype, bq, bk) else _bwd_split
    return backward(operands, kernel, bq, bk, interpret)


def _bwd_walk(operands, kernel, bq, bk, interpret):
    """dq, dk, dv from one kernel.  Grid (batch, key-value head, query head of
    it, q tile), the last two in order: a key-value head's K, V, dk and dv
    blocks and the accumulators stay while its query heads' q tiles pass."""
    q, k, v = operands[:3]
    b, _, sq, d = q.shape
    h_kv, sk, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = _group_size(q, k)
    q_tile = lambda i, j, m, l: (i, j * group + m, l, 0)  # noqa: E731
    q_row = lambda i, j, m, l: (i, j * group + m, 0, l)  # noqa: E731
    kv_head = lambda i, j, m, l: (i, j, 0, 0)  # noqa: E731
    return _pallas_call(
        functools.partial(_walk_kernel, block_k=bk, **kernel),
        grid=(b, h_kv, group, sq // bq),
        parallel=2,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_tile),
            pl.BlockSpec((1, 1, sk, d), kv_head),
            pl.BlockSpec((1, 1, sk, d_v), kv_head),
            pl.BlockSpec((1, 1, bq, d_v), q_tile),
            pl.BlockSpec((1, 1, 1, bq), q_row),
            pl.BlockSpec((1, 1, 1, bq), q_row),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), q_tile),
            pl.BlockSpec((1, 1, sk, d), kv_head),
            pl.BlockSpec((1, 1, sk, d_v), kv_head),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((sk, d), jnp.float32), pltpu.VMEM((sk, d_v), jnp.float32)],
        interpret=interpret,
    )(*operands)


def _bwd_split(operands, kernel, bq, bk, interpret):
    """dq from one kernel, dk and dv from another: each walks the pairs."""
    q, k, v = operands[:3]
    b, h, sq, d = q.shape
    h_kv, sk, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = _group_size(q, k)
    kv = _kv_head(group)
    dq = _pallas_call(
        functools.partial(_dq_kernel, block_k=bk, **kernel),
        grid=(b, h, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda i, j, l: (i, kv(j), 0, 0)),
            pl.BlockSpec((1, 1, sk, d_v), lambda i, j, l: (i, kv(j), 0, 0)),
            pl.BlockSpec((1, 1, bq, d_v), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda i, j, l: (i, j, 0, l)),
            pl.BlockSpec((1, 1, 1, bq), lambda i, j, l: (i, j, 0, l)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda i, j, l: (i, j, l, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*operands)

    # one grid point a (k tile, query head): with a group, the heads of a
    # key-value head on a fourth axis, innermost, so that its dk and dv blocks
    # stay in VMEM while the group's parts are summed into them
    if group == 1:
        grid = (b, h, sk // bk)
        q_head = lambda i, j, l: (i, j, 0, 0)  # noqa: E731
        kv_tile = lambda i, j, l: (i, j, l, 0)  # noqa: E731
        scratch = []
    else:
        grid = (b, h_kv, sk // bk, group)
        q_head = lambda i, j, l, m: (i, j * group + m, 0, 0)  # noqa: E731
        kv_tile = lambda i, j, l, m: (i, j, l, 0)  # noqa: E731
        scratch = [pltpu.VMEM((bk, d), jnp.float32), pltpu.VMEM((bk, d_v), jnp.float32)]
    dk, dv = _pallas_call(
        functools.partial(_dkv_kernel, block_q=bq, group=group, **kernel),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, sq, d), q_head),
            pl.BlockSpec((1, 1, bk, d), kv_tile),
            pl.BlockSpec((1, 1, bk, d_v), kv_tile),
            pl.BlockSpec((1, 1, sq, d_v), q_head),
            pl.BlockSpec((1, 1, 1, sq), q_head),
            pl.BlockSpec((1, 1, 1, sq), q_head),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), kv_tile),
            pl.BlockSpec((1, 1, bk, d_v), kv_tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API (custom VJP)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused attention over [batch, heads, seq, head_dim] inputs.  The values
    (and so the output) may have another width than the queries and keys, as
    in latent attention (192-wide keys, 128-wide values); the scale comes from
    the query width unless given.  Keys and values may have fewer heads than
    the queries, a whole number of query heads to each (query head ``j`` reads
    key-value head ``j // group``).  ``window``: query ``t`` sees the keys
    ``t - window < t' <= t`` (causal only; ``None``: the whole prefix).
    ``block_q`` / ``block_k``: the tiles, from ``plan_tiles`` where ``None``.

    Returns ``(output, logsumexp)``; the logsumexp output makes this the
    mergeable building block for ring attention.  Rows with every key masked
    produce output 0 and logsumexp ≈ -1e30 (an exact no-op when merged).
    """
    _check_window(window, causal)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    itp = _interpret_default() if interpret is None else interpret
    return _fwd(
        q, k, v, sm_scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=itp, window=window,
    )


def _vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window):
    o, lse = flash_attention_with_lse(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, window
    )
    # named here, on the arrays that are both the primal results and the
    # residuals: a name given outside the custom_vjp would leave the residuals
    # unnamed, and a rematerialised block would run the kernel again for them
    o, lse = map(checkpoint_name, (o, lse), KERNEL_RESULTS)
    return (o, lse), (q, k, v, o, lse)


def _vjp_bwd(causal, sm_scale, block_q, block_k, interpret, window, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    itp = _interpret_default() if interpret is None else interpret
    return _bwd(
        q, k, v, o, lse, do, dlse,
        sm_scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=itp, window=window,
    )


flash_attention_with_lse.defvjp(_vjp_fwd, _vjp_bwd)


def remat_block(block: type[nn.Module]) -> type[nn.Module]:
    """``nn.remat`` of a block that keeps the attention kernel's output and
    logsumexp (bfloat16 ``[B, H, S, Dv]`` and float32 ``[B, H, 1, S]`` a
    layer application: linear in the context to hold, quadratic to compute
    again) and computes everything else again in the backward pass.  Where
    attention is dense nothing carries the names and nothing is kept."""
    return nn.remat(block, policy=_KEEP_KERNEL_RESULTS)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> jax.Array:
    """Standard entry point: fused attention output only."""
    o, _ = flash_attention_with_lse(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, window
    )
    return o


# ---------------------------------------------------------------------------
# dense reference (tests + tiny shapes where kernel overhead dominates)
# ---------------------------------------------------------------------------


def reference_attention_with_lse(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    sm_scale: float | None = None, window: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """O(S^2)-memory jnp attention returning (output, logsumexp); the same
    arguments as the kernel (fewer key-value heads are repeated)."""
    _check_window(window, causal)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    group = _group_size(q, k)
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    visible = None
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        s = jnp.where(mask, s, _MASK_VALUE)
        if sq > sk:
            visible = mask.any(-1)  # rows before the diagonal see no key
    lse_raw = jax.scipy.special.logsumexp(s, axis=-1)
    if visible is None:
        lse = lse_raw
        p = jnp.exp(s - lse[..., None])
    else:
        # fully-masked rows: output 0 and lse=_MASK_VALUE (a no-op when
        # merged), matching the kernel, instead of uniform-attention junk
        lse = jnp.where(visible, lse_raw, _MASK_VALUE)
        p = jnp.exp(s - jnp.where(visible, lse_raw, 0.0)[..., None])
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


def reference_attention(q, k, v, *, causal: bool = True, sm_scale=None, window=None) -> jax.Array:
    o, _ = reference_attention_with_lse(q, k, v, causal, sm_scale, window)
    return o
