"""Learned sparse attention (DeepSeek Sparse Attention): an indexer scores
every visible key of every query, each query attends to the ``topk`` keys
with the largest scores, and the indexer is trained by a KL loss towards the
attention it selected for.

For queries ``t`` and keys ``s <= t`` (one sequence of a batch):

- indexer scores ``I[t, s] = scale * sum_j w[t, j] * relu(qI[t, j] . kI[s])``
  (``H_I`` indexer heads ``j`` of width ``d_I`` over ONE key, float32);
- the selection ``S_t``: the ``topk`` keys ``s <= t`` with the largest
  ``I[t, s]``, every ``s <= t`` where there are fewer (exact: a radix select
  on the scores' bits, no approximate top-k);
- attention over the selection: ``P_i(t, s) = softmax_{s in S_t}(q_i,t .
  k_g,s / sqrt(d))`` for every query head ``i`` (its key-value head ``g``), as
  the flash kernel computes it (``flash_attention._fwd`` / ``_bwd`` with the
  selection's bitmask: the tile mask is the causal mask AND the selection);
- the indexer's loss ``L_I = mean_t sum_{s in S_t} p (log p - log
  softmax_{S_t}(I[t]))`` with ``p = mean_i stop_gradient(P_i)``; its gradient
  reaches ``qI``, ``kI`` and ``w`` alone: ``dL_I / dI = (softmax_{S_t}(I) -
  p) / rows``.

Three kernels a layer forward: the selection pass (``_select_kernel``: the
scores of a q tile against every visible key into VMEM, 32 counting passes
over their bits for each row's ``topk``-th largest, then the bitmask and the
scores' log-sum-exp over the selection), the flash forward with the
selection, and the indexer's loss (``_indexer_loss_kernel``: a q tile's ``p``
from all query heads, the scores again, ``L_I``'s rows and its gradients to
``qI``, ``w`` and, summed over the q tiles, ``kI``).  The backward is the
flash kernel's single walk with the selection.  ``L_I``'s gradient depends
on forward quantities alone (``P`` is not differentiated), so it is computed
in the forward and scaled by the loss's cotangent in the backward.  Its
forward rule names what the backward reads (``SELECTION_RESULTS`` beside the
flash kernel's ``KERNEL_RESULTS``), and ``remat_sparse_block`` keeps those:
the rematerialised forward runs no kernel.

Layouts: the indexer's operands cross into the kernels with the positions
last (``[B, H_I, d_I, S]``, ``[B, d_I, S]``, ``[B, H_I, S]``), lane-dense at
``d_I`` 64.  Tiles are the flash kernel's plan; the bitmask is
``flash_attention.pack_tile``'s layout at its k tile.
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

from katib_tpu.ops.flash_attention import (
    KERNEL_RESULTS,
    _NT,
    _TN,
    _block_sizes,
    _bwd,
    _fwd,
    _group_size,
    _interpret_default,
    _k_tile_range,
    _pallas_call,
    _visible,
    pack_tile,
    unpack_tile,
    word_rows,
)

#: the names the forward rule gives what the backward reads beside the flash
#: kernel's output and log-sum-exp: the bitmask, the loop bounds and the
#: indexer's gradients
SELECTION_RESULTS = ("dsa_bits", "dsa_bounds", "dsa_indexer_grads")
_KEEP_RESULTS = jax.checkpoint_policies.save_only_these_names(*KERNEL_RESULTS, *SELECTION_RESULTS)

_INT_MIN = -(2**31)


def _order_key(x):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _from_key(key):
    """``_order_key``'s inverse (the map is its own inverse on the bits)."""
    return jax.lax.bitcast_convert_type(jnp.where(key < 0, key ^ 0x7FFFFFFF, key), jnp.float32)


def _index_scores(kt, qi_ref, w_ref, scale):
    """``I`` of one tile, ``[bk, bq]`` float32: the k tile's indexer keys ``kt``
    ``[d_I, bk]`` against the q tile's heads (``qi_ref`` ``[1, H_I, d_I, bq]``,
    weights ``w_ref`` ``[1, H_I, bq]``)."""
    acc = None
    for j in range(qi_ref.shape[1]):
        z = jax.lax.dot_general(kt, qi_ref[0, j], _TN, preferred_element_type=jnp.float32)
        term = w_ref[0, j : j + 1, :] * jnp.maximum(z, 0.0)
        acc = term if acc is None else acc + term
    return scale * acc


# ---------------------------------------------------------------------------
# the selection pass
# ---------------------------------------------------------------------------


def _select_kernel(qi_ref, ki_ref, w_ref, bits_ref, lse_ref, keys, *, topk, block_k, scale):
    """One q tile: its scores against every visible key, each row's ``topk``
    largest, the tile's bitmask column and the scores' log-sum-exp over the
    selection.  ``keys`` (VMEM, ``[S_k, bq]`` int32) holds the scores as
    ordered integers, ``_INT_MIN`` where not visible."""
    bq = qi_ref.shape[-1]
    qi = pl.program_id(1)
    n_kb = ki_ref.shape[-1] // block_k
    _, end = _k_tile_range(qi, bq, block_k, n_kb, 0, True, None)

    def score(j, m):
        start = pl.multiple_of(j * block_k, block_k)
        s = _index_scores(ki_ref[0, :, pl.ds(start, block_k)], qi_ref, w_ref, scale)
        seen = _visible(s.shape, start, qi * bq, None)
        keys[pl.ds(start, block_k), :] = jnp.where(seen, _order_key(s), _INT_MIN)
        return jnp.maximum(m, jnp.max(jnp.where(seen, s, -jnp.inf), axis=0, keepdims=True))

    m = jax.lax.fori_loop(0, end, score, jnp.full((1, bq), -jnp.inf, jnp.float32))

    # the largest threshold that at least ``topk`` visible keys reach, a bit
    # at a time from the top: the ``topk``-th largest score of each row
    def bit(i, prefix):
        cand = prefix + jax.lax.shift_left(jnp.int32(1), 31 - i)  # wraps at bit 31, as meant

        def count(j, c):
            tile = keys[pl.ds(pl.multiple_of(j * block_k, block_k), block_k), :]
            return c + jnp.sum((tile >= cand).astype(jnp.int32), axis=0, keepdims=True)

        n = jax.lax.fori_loop(0, end, count, jnp.zeros((1, bq), jnp.int32))
        return jnp.where(n >= topk, cand, prefix)

    threshold = jax.lax.fori_loop(0, 32, bit, jnp.full((1, bq), _INT_MIN, jnp.int32))

    def count(above):  # visible keys of each row for which ``above(tile, index)``
        def add(j, c):
            start = pl.multiple_of(j * block_k, block_k)
            tile = keys[pl.ds(start, block_k), :]
            index = start + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
            return c + jnp.sum(above(tile, index).astype(jnp.int32), axis=0, keepdims=True)

        return jax.lax.fori_loop(0, end, add, jnp.zeros((1, bq), jnp.int32))

    # keys equal to a row's threshold are taken in order of position, as
    # ``lax.top_k`` takes them, up to ``topk``: the ties' last position taken
    s_k = keys.shape[0]
    ties = (count(lambda t, _: t >= threshold) > topk) & (threshold != _INT_MIN)
    wanted = topk - count(lambda t, _: t > threshold)

    def last_tie():
        def index_bit(i, prefix):
            cand = prefix + jax.lax.shift_left(jnp.int32(1), s_k.bit_length() - 1 - i)
            n = count(lambda t, index: (t == threshold) & (index < cand))
            return jnp.where(n < wanted, cand, prefix)

        return jax.lax.fori_loop(0, s_k.bit_length(), index_bit, jnp.zeros((1, bq), jnp.int32))

    cut = jax.lax.cond(jnp.max(ties.astype(jnp.int32)) > 0, last_tie, lambda: jnp.full((1, bq), s_k, jnp.int32))
    cut = jnp.where(ties, cut, s_k)

    bits_ref[...] = jnp.zeros_like(bits_ref)

    def write(j, total):
        start = pl.multiple_of(j * block_k, block_k)
        tile = keys[pl.ds(start, block_k), :]
        index = start + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
        chosen = ((tile > threshold) | ((tile == threshold) & (index <= cut))) & (tile != _INT_MIN)
        bits_ref[0, word_rows(start, block_k), :] = pack_tile(chosen)
        e = jnp.where(chosen, jnp.exp(_from_key(tile) - m), 0.0)
        return total + jnp.sum(e, axis=0, keepdims=True)

    total = jax.lax.fori_loop(0, end, write, jnp.zeros((1, bq), jnp.float32))
    lse_ref[0, :, :] = m + jnp.log(total)


def select_keys(qi_t, ki_t, w_t, *, topk, block_q, block_k, scale, interpret):
    """The selection: ``bits`` ``[B, S/32, S]`` int32 (``pack_tile``'s layout
    at ``block_k``) and the scores' log-sum-exp over it, ``[B, 1, S]``.
    Operands with the positions last: ``qi_t`` ``[B, H_I, d_I, S]``, ``ki_t``
    ``[B, d_I, S]``, ``w_t`` ``[B, H_I, S]``."""
    b, h_i, d_i, s = qi_t.shape
    return _pallas_call(
        functools.partial(_select_kernel, topk=topk, block_k=block_k, scale=scale),
        grid=(b, s // block_q),
        parallel=2,
        in_specs=[
            pl.BlockSpec((1, h_i, d_i, block_q), lambda i, l: (i, 0, 0, l)),
            pl.BlockSpec((1, d_i, s), lambda i, l: (i, 0, 0)),
            pl.BlockSpec((1, h_i, block_q), lambda i, l: (i, 0, l)),
        ],
        out_specs=[
            pl.BlockSpec((1, s // 32, block_q), lambda i, l: (i, 0, l)),
            pl.BlockSpec((1, 1, block_q), lambda i, l: (i, 0, l)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s // 32, s), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((s, block_q), jnp.int32)],
        interpret=interpret,
    )(qi_t, ki_t, w_t)


def tile_counts(bits, block_q, block_k):
    """Selected pairs of each (q tile, k tile): ``[B, q tiles, k tiles]``."""
    b, words, s = bits.shape
    per_tile = block_k // 32
    per = jax.lax.population_count(bits).reshape(b, words // per_tile, per_tile, s // block_q, block_q)
    return jnp.sum(per, axis=(2, 4)).transpose(0, 2, 1)


def selection_counts(counts, bounds, block_q, block_k):
    """int32 ``[selected pairs, visible pairs, k tiles walked, causal k
    tiles]`` of a layer: pairs over the batch, tiles over its rows and q
    tiles for one head (the least a causal walk could make, against what the
    selection's loop bounds make)."""
    b, n_qb, n_kb = counts.shape
    seq = n_qb * block_q
    causal = np.minimum(-(-(np.arange(1, n_qb + 1) * block_q) // block_k), n_kb)
    walked = jnp.sum(bounds[..., 1] - bounds[..., 0])
    return jnp.stack(
        [jnp.sum(counts), jnp.int32(b * seq * (seq + 1) // 2), walked, jnp.int32(b * int(causal.sum()))]
    ).astype(jnp.int32)


def loop_bounds(counts):
    """``[B, q tiles, 2]``: the k tiles ``[first, end)`` that hold a selected
    pair of each q tile (the loops skip the rest)."""
    n_kb = counts.shape[-1]
    held = counts > 0
    first = jnp.argmax(held, axis=-1)
    end = n_kb - jnp.argmax(held[..., ::-1], axis=-1)
    return jnp.stack([first, end], axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the indexer's loss and its gradients
# ---------------------------------------------------------------------------


def _indexer_loss_kernel(
    q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, bits_ref, bounds_ref, lse_i_ref,
    kl_ref, dqi_ref, dw_ref, dki_ref, *, sm_scale, scale, block_k, inv_rows,
):
    """One q tile against the k tiles that hold its selection: ``p`` (the mean
    over every query head of the kernel's probabilities, from the scores again
    and the forward's log-sum-exp), the indexer's scores again, the rows of
    ``sum p (log p - log q)`` and the gradients of ``L_I`` (``dI = (q - p) /
    rows``) to the q tile's ``qI`` and ``w``, and to ``kI``, summed over every
    q tile in ``dki_ref`` (float32, the whole sequence; the q tiles are in
    order)."""
    n_heads, bq = q_ref.shape[1], q_ref.shape[2]
    n_kv = k_ref.shape[1]
    group = n_heads // n_kv
    b, qi = pl.program_id(0), pl.program_id(1)

    @pl.when(qi == 0)
    def _():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    kl_ref[...] = jnp.zeros_like(kl_ref)
    dqi_ref[...] = jnp.zeros_like(dqi_ref)
    dw_ref[...] = jnp.zeros_like(dw_ref)
    lse_i = lse_i_ref[0, :, :]  # [1, bq]

    def body(j, carry):
        start = pl.multiple_of(j * block_k, block_k)
        rows = pl.ds(start, block_k)
        chosen = unpack_tile(bits_ref[0, word_rows(start, block_k), :])
        p = jnp.zeros((block_k, bq), jnp.float32)
        for g in range(n_kv):
            k = k_ref[0, g, rows, :]
            for m in range(group):
                i = g * group + m
                s = sm_scale * jax.lax.dot_general(k, q_ref[0, i], _NT, preferred_element_type=jnp.float32)
                # a selected pair's score is at most its row's log-sum-exp
                p = p + jnp.exp(jnp.minimum(s - lse_ref[0, i], 0.0))
        p = jnp.where(chosen, p * (1.0 / n_heads), 0.0)
        kt = ki_ref[0, :, rows]  # [d_I, bk]
        log_q = _index_scores(kt, qi_ref, w_ref, scale) - lse_i
        p_log_p = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
        kl_ref[0, :, :] += jnp.sum(p_log_p - jnp.where(chosen, p * log_q, 0.0), axis=0, keepdims=True)
        d_scores = (jnp.where(chosen, jnp.exp(log_q), 0.0) - p) * (inv_rows * scale)
        for h in range(qi_ref.shape[1]):
            qh = qi_ref[0, h]  # [d_I, bq]
            z = jax.lax.dot_general(kt, qh, _TN, preferred_element_type=jnp.float32)
            dw_ref[0, h : h + 1, :] += jnp.sum(d_scores * jnp.maximum(z, 0.0), axis=0, keepdims=True)
            # operands in the inputs' dtype, accumulation in float32, as the
            # flash kernel's backward casts dS
            dz = jnp.where(z > 0, d_scores * w_ref[0, h : h + 1, :], 0.0).astype(kt.dtype)
            dqi_ref[0, h] += jnp.dot(kt, dz, preferred_element_type=jnp.float32)
            dki_ref[0, :, rows] += jax.lax.dot_general(qh, dz, _NT, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(bounds_ref[b, qi, 0], bounds_ref[b, qi, 1], body, 0)


def indexer_loss(q, k, lse, qi_t, ki_t, w_t, bits, bounds, lse_i, *, sm_scale, scale, block_q, block_k, interpret):
    """``L_I``'s rows ``[B, 1, S]`` and its gradients to ``qi_t`` (``[B, H_I,
    d_I, S]``), ``w_t`` (``[B, H_I, S]``) and ``ki_t`` (``[B, d_I, S]``),
    float32."""
    b, n_heads, s, d = q.shape
    n_kv = k.shape[1]
    h_i, d_i = qi_t.shape[1], qi_t.shape[2]
    whole = functools.partial(pl.BlockSpec, pipeline_mode=pl.Buffered(1))  # the same block every q tile
    return _pallas_call(
        functools.partial(
            _indexer_loss_kernel, sm_scale=sm_scale, scale=scale, block_k=block_k, inv_rows=1.0 / (b * s)
        ),
        grid=(b, s // block_q),
        parallel=1,
        in_specs=[
            pl.BlockSpec((1, n_heads, block_q, d), lambda i, l: (i, 0, l, 0)),
            whole((1, n_kv, s, d), lambda i, l: (i, 0, 0, 0)),
            pl.BlockSpec((1, n_heads, 1, block_q), lambda i, l: (i, 0, 0, l)),
            pl.BlockSpec((1, h_i, d_i, block_q), lambda i, l: (i, 0, 0, l)),
            whole((1, d_i, s), lambda i, l: (i, 0, 0)),
            pl.BlockSpec((1, h_i, block_q), lambda i, l: (i, 0, l)),
            pl.BlockSpec((1, s // 32, block_q), lambda i, l: (i, 0, l)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q), lambda i, l: (i, 0, l)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q), lambda i, l: (i, 0, l)),
            pl.BlockSpec((1, h_i, d_i, block_q), lambda i, l: (i, 0, 0, l)),
            pl.BlockSpec((1, h_i, block_q), lambda i, l: (i, 0, l)),
            pl.BlockSpec((1, d_i, s), lambda i, l: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
            jax.ShapeDtypeStruct((b, h_i, d_i, s), jnp.float32),
            jax.ShapeDtypeStruct((b, h_i, s), jnp.float32),
            jax.ShapeDtypeStruct((b, d_i, s), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, lse[:, :, None, :], qi_t, ki_t, w_t, bits, bounds, lse_i)


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------


def _plan(q, k, v, block_q, block_k):
    if q.shape[2] != k.shape[2]:
        raise ValueError("sparse_attention: queries and keys of one sequence, as many of each")
    bq, bk = _block_sizes(q, k, v, block_q, block_k)
    if bk % 32:
        raise ValueError(f"sparse_attention: a k tile of {bk} keys is not a whole number of 32-bit words")
    return bq, bk


def _forward(q, k, v, qi, ki, w, topk, sm_scale, index_scale, block_q, block_k, interpret):
    """Everything the op computes: the output and log-sum-exp, ``L_I``, the
    layer's ``selection_counts`` and what the backward reads (the bitmask, the
    loop bounds and ``L_I``'s gradients to ``qi``, ``ki``, ``w``)."""
    bq, bk = _plan(q, k, v, block_q, block_k)
    qi_t, ki_t, w_t = qi.transpose(0, 2, 3, 1), ki.transpose(0, 2, 1), w.transpose(0, 2, 1)
    bits, lse_i = select_keys(
        qi_t, ki_t, w_t, topk=topk, block_q=bq, block_k=bk, scale=index_scale, interpret=interpret
    )
    counts = tile_counts(bits, bq, bk)
    bounds = loop_bounds(counts)
    o, lse = _fwd(
        q, k, v, sm_scale=sm_scale, causal=True, block_q=bq, block_k=bk, interpret=interpret,
        window=None, selection=(bits, bounds),
    )
    kl, dqi_t, dw_t, dki_t = indexer_loss(
        q, k, lse, qi_t, ki_t, w_t, bits, bounds, lse_i,
        sm_scale=sm_scale, scale=index_scale, block_q=bq, block_k=bk, interpret=interpret,
    )
    loss = jnp.sum(kl) / (q.shape[0] * q.shape[2])
    stats = selection_counts(counts, bounds, bq, bk)
    grads = (dqi_t.transpose(0, 3, 1, 2), dki_t.transpose(0, 2, 1), dw_t.transpose(0, 2, 1))
    return o, lse, loss, stats, bits, bounds, grads


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def sparse_attention(
    q, k, v, qi, ki, w, topk: int, sm_scale: float | None = None, index_scale: float | None = None,
    block_q: int | None = None, block_k: int | None = None, interpret: bool | None = None,
):
    """Attention of every query over the ``topk`` visible keys its indexer
    scores highest, and the indexer's loss.

    ``q`` ``[B, H, S, d]``, ``k``, ``v`` ``[B, G, S, d]`` (query head ``i``
    reads key-value head ``i // (H / G)``), as the flash kernel takes them;
    the indexer's ``qi`` ``[B, S, H_I, d_I]``, ``ki`` ``[B, S, d_I]`` and
    ``w`` ``[B, S, H_I]``.  ``index_scale``: the scores' factor, ``1 /
    sqrt(d_I)`` where ``None``.  Returns ``(output, L_I, stats)``: the output
    ``[B, H, S, d]``, ``L_I`` (float32, the mean over the ``B x S`` rows),
    and ``selection_counts``'s four int32 counts."""
    sm_scale, index_scale, itp = _scales(q, qi, sm_scale, index_scale, interpret)
    o, _, loss, stats, *_ = _forward(q, k, v, qi, ki, w, topk, sm_scale, index_scale, block_q, block_k, itp)
    return o, loss, stats


def _scales(q, qi, sm_scale, index_scale, interpret):
    return (
        1.0 / math.sqrt(q.shape[-1]) if sm_scale is None else sm_scale,
        1.0 / math.sqrt(qi.shape[-1]) if index_scale is None else index_scale,
        _interpret_default() if interpret is None else interpret,
    )


def _vjp_fwd(q, k, v, qi, ki, w, topk, sm_scale, index_scale, block_q, block_k, interpret):
    sms, ids, itp = _scales(q, qi, sm_scale, index_scale, interpret)
    o, lse, loss, stats, bits, bounds, grads = _forward(q, k, v, qi, ki, w, topk, sms, ids, block_q, block_k, itp)
    # named on the residuals, inside the rule: a rematerialised block keeps them
    o, lse = map(checkpoint_name, (o, lse), KERNEL_RESULTS)
    bits, bounds = checkpoint_name(bits, SELECTION_RESULTS[0]), checkpoint_name(bounds, SELECTION_RESULTS[1])
    grads = tuple(checkpoint_name(g.astype(x.dtype), SELECTION_RESULTS[2]) for g, x in zip(grads, (qi, ki, w)))
    return (o, loss, stats), (q, k, v, o, lse, bits, bounds, grads)


def _vjp_bwd(topk, sm_scale, index_scale, block_q, block_k, interpret, res, cts):
    q, k, v, o, lse, bits, bounds, grads = res
    do, d_loss, _ = cts
    sms, _, itp = _scales(q, grads[0], sm_scale, index_scale, interpret)
    bq, bk = _plan(q, k, v, block_q, block_k)
    dq, dk, dv = _bwd(
        q, k, v, o, lse, do, jnp.zeros(lse.shape, jnp.float32), sm_scale=sms, causal=True,
        block_q=bq, block_k=bk, interpret=itp, window=None, selection=(bits, bounds),
    )
    d_index = tuple((d_loss * g).astype(g.dtype) for g in grads)
    return (dq, dk, dv, *d_index)


sparse_attention.defvjp(_vjp_fwd, _vjp_bwd)


def remat_sparse_block(block: type[nn.Module]) -> type[nn.Module]:
    """``nn.remat`` of a block with ``sparse_attention``, keeping what its
    backward reads (``KERNEL_RESULTS`` and ``SELECTION_RESULTS``: a layer's
    output, log-sum-exp, bitmask, loop bounds and the indexer's gradients)
    and computing everything else again."""
    return nn.remat(block, policy=_KEEP_RESULTS)


# ---------------------------------------------------------------------------
# dense reference (the tests', and a model's attention off the TPU)
# ---------------------------------------------------------------------------


def index_scores(qi, ki, w, scale=None):
    """``I`` ``[B, S, S]`` (queries, keys), float32, no mask."""
    scale = 1.0 / math.sqrt(qi.shape[-1]) if scale is None else scale
    z = jnp.einsum("bthd,bsd->bths", qi.astype(jnp.float32), ki.astype(jnp.float32))
    return scale * jnp.einsum("bth,bths->bts", w.astype(jnp.float32), jax.nn.relu(z))


def reference_selection(scores, topk):
    """``[B, S, S]`` bool: each row's ``topk`` largest visible scores
    (``lax.top_k``), or every visible key where there are fewer."""
    s = scores.shape[-1]
    visible = jnp.tril(jnp.ones((s, s), bool))
    masked = jnp.where(visible, scores, -jnp.inf)
    k = min(topk, s)
    _, idx = jax.lax.top_k(masked, k)
    chosen = jnp.any(jax.nn.one_hot(idx, s, dtype=bool), axis=-2)
    return chosen & visible


def dense_sparse_attention(q, k, v, qi, ki, w, topk, sm_scale=None, index_scale=None):
    """The op in dense float32 ``jnp`` (the tests' reference, and a model's
    attention off the TPU): ``(output, L_I, counts)``, the counts at the
    tiles the kernels would plan."""
    sm_scale = 1.0 / math.sqrt(q.shape[-1]) if sm_scale is None else sm_scale
    group = _group_size(q, k)
    scores = index_scores(qi, ki, w, index_scale)
    chosen = reference_selection(jax.lax.stop_gradient(scores), topk)[:, None]  # [B, 1, S, S]
    kk, vv = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)) * sm_scale
    probs = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, vv.astype(jnp.float32)).astype(q.dtype)
    p = jnp.mean(jax.lax.stop_gradient(probs), axis=1)  # [B, S, S]
    log_q = jax.nn.log_softmax(jnp.where(chosen[:, 0], scores, -jnp.inf), axis=-1)
    terms = jnp.where(chosen[:, 0] & (p > 0), p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0)
    b, s = q.shape[0], q.shape[2]
    bq, bk = _block_sizes(q, k, v, None, None)
    counts = jnp.sum(chosen[:, 0].reshape(b, s // bq, bq, s // bk, bk), axis=(2, 4))
    return o, jnp.mean(jnp.sum(terms, axis=-1)), selection_counts(counts, loop_bounds(counts), bq, bk)
