"""Blockwise (flash) attention as Pallas TPU kernels.

The reference delegates all attention math to PyTorch/TF inside trial
containers (it has none of its own — SURVEY.md §2.4); here attention is a
first-class fused kernel so HP/NAS search over transformer trials runs at
MXU speed without materialising the [S, S] score matrix in HBM.

Design (FlashAttention-2 style, adapted to the TPU memory hierarchy):

- forward: grid over (batch, head, q-block); K/V stream through VMEM while
  an online softmax keeps running (max, sum, output) accumulators in f32.
  Emits the per-row logsumexp so sequence-parallel ring attention
  (``katib_tpu.parallel.ring_attention``) can merge partial results from
  other sequence shards.
- backward: two kernels — dq over q-blocks, dk/dv over k-blocks — that
  recompute probabilities from the saved logsumexp instead of storing the
  score matrix (rematerialisation trades FLOPs for HBM, the TPU-native
  default).
- both are exposed through one ``jax.custom_vjp`` so ``jax.grad`` composes
  with jit/shard_map/scan.

On non-TPU backends (CPU tests, the 8-device virtual mesh) the kernels run
in interpreter mode automatically; numerics match a dense jnp reference to
~1e-5 (f32).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")
_MASK_VALUE = -1e30  # large-negative instead of -inf inside kernels (no NaNs)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _block_sizes(seq_q: int, seq_k: int, block_q: int, block_k: int):
    bq = min(block_q, seq_q)
    bk = min(block_k, seq_k)
    if seq_q % bq or seq_k % bk:
        raise ValueError(
            f"block sizes ({bq}, {bk}) must divide sequence lengths ({seq_q}, {seq_k})"
        )
    return bq, bk


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal, block_k, shift):
    """``shift = seq_k - seq_q`` makes the causal mask bottom-right aligned
    (last query row sees every key), matching ``reference_attention_with_lse``
    for seq_q != seq_k; both collapse to the usual mask when shift == 0."""
    bq, d_v = q_ref.shape[-2], v_ref.shape[-1]
    seq_k = k_ref.shape[-2]
    n_kb = seq_k // block_k
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :].astype(jnp.float32) * sm_scale

    if causal:
        # only k-blocks starting at or before the last query row's diagonal
        last_col = jnp.maximum((qi + 1) * bq + shift, 0)
        n_kb_live = jnp.clip(pl.cdiv(last_col, block_k), 0, n_kb)
    else:
        n_kb_live = n_kb

    def body(j, carry):
        o_acc, m_acc, l_acc = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, block_k]
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            mask = cols <= rows + shift
            s = jnp.where(mask, s, _MASK_VALUE)
        m_new = jnp.maximum(m_acc, jnp.max(s, axis=1))
        # mask the exponent, not just the score: a fully-masked row has
        # s == m_new == _MASK_VALUE, where exp(s - m_new) would be exp(0)=1
        e = s - m_new[:, None]
        if causal:
            e = jnp.where(mask, e, _MASK_VALUE)
        p = jnp.exp(e)
        alpha = jnp.exp(m_acc - m_new)
        l_new = l_acc * alpha + jnp.sum(p, axis=1)
        o_new = o_acc * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((bq, d_v), jnp.float32)
    m0 = jnp.full((bq,), _MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, n_kb_live, body, (o0, m0, l0))

    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0, :, :] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse = jnp.where(l == 0.0, _MASK_VALUE, m + jnp.log(l_safe))
    # trailing singleton keeps the block 4-D: TPU tiling requires the last
    # two block dims divide (8, 128) or equal the array dims
    lse_ref[0, 0, :, 0] = lse


def _fwd(q, k, v, *, sm_scale, causal, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[3]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    grid = (b, h, sq // bq)
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, block_k=bk,
            shift=sk - sq,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda i, j, l: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, sk, d_v), lambda i, j, l: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d_v), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda i, j, l: (i, j, l, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d_v), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dmd_ref, dq_ref, *, sm_scale, causal, block_k, shift):
    """dq for one q-block; streams K/V blocks.  ``dmd`` = rowsum(dO*O) - d_lse,
    folding the logsumexp cotangent into the usual flash "delta" term."""
    bq, d = q_ref.shape[-2], q_ref.shape[-1]
    seq_k = k_ref.shape[-2]
    n_kb = seq_k // block_k
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :].astype(jnp.float32)
    do = do_ref[0, 0, :, :].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]
    dmd = dmd_ref[0, 0, :, 0]

    n_kb_live = (
        jnp.clip(pl.cdiv(jnp.maximum((qi + 1) * bq + shift, 0), block_k), 0, n_kb)
        if causal
        else n_kb
    )

    def body(j, dq_acc):
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        e = s - lse[:, None]
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            e = jnp.where(cols <= rows + shift, e, _MASK_VALUE)
        p = jnp.exp(e)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - dmd[:, None])
        return dq_acc + sm_scale * jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, n_kb_live, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0, :, :] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dmd_ref, dk_ref, dv_ref, *, sm_scale, causal, block_q, shift):
    """dk, dv for one k-block; streams q-blocks (with their dO/lse/delta rows)."""
    bk, d = k_ref.shape[-2], k_ref.shape[-1]
    seq_q = q_ref.shape[-2]
    n_qb = seq_q // block_q
    ki = pl.program_id(2)
    k = k_ref[0, 0, :, :].astype(jnp.float32)
    v = v_ref[0, 0, :, :].astype(jnp.float32)

    # with causal masking, q-blocks strictly above this k-block's diagonal
    # (bottom-right aligned: row + shift >= col) contribute 0
    first_qb = jnp.maximum(0, ki * bk - shift) // block_q if causal else 0

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = q_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), 0]
        dmd = dmd_ref[0, 0, pl.ds(i * block_q, block_q), 0]
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, bk]
        e = s - lse[:, None]
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            e = jnp.where(cols <= rows + shift, e, _MASK_VALUE)
        p = jnp.exp(e)
        dv_new = dv_acc + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - dmd[:, None])
        dk_new = dk_acc + sm_scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk_new, dv_new

    zk = jnp.zeros((bk, d), jnp.float32)
    zv = zk if v_ref.shape[-1] == d else jnp.zeros((bk, v_ref.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(first_qb, n_qb, body, (zk, zv))
    dk_ref[0, 0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, dlse, *, sm_scale, causal, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk, d_v = k.shape[2], v.shape[3]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dmd = delta - dlse.astype(jnp.float32)  # [b, h, sq]
    lse4 = lse[..., None]
    dmd4 = dmd[..., None]

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal, block_k=bk, shift=sk - sq
        ),
        grid=(b, h, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, sk, d), lambda i, j, l: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, sk, d_v), lambda i, j, l: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, bq, d_v), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda i, j, l: (i, j, l, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda i, j, l: (i, j, l, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse4, dmd4)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal, block_q=bq, shift=sk - sq
        ),
        grid=(b, h, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, sq, d), lambda i, j, l: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, bk, d_v), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, sq, d_v), lambda i, j, l: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, sq, 1), lambda i, j, l: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, sq, 1), lambda i, j, l: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda i, j, l: (i, j, l, 0)),
            pl.BlockSpec((1, 1, bk, d_v), lambda i, j, l: (i, j, l, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse4, dmd4)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API (custom VJP)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused attention over [batch, heads, seq, head_dim] inputs.  The values
    (and so the output) may have another width than the queries and keys, as
    in latent attention (192-wide keys, 128-wide values); the scale comes from
    the query width unless given.

    Returns ``(output, logsumexp)``; the logsumexp output makes this the
    mergeable building block for ring attention.  Rows with every key masked
    produce output 0 and logsumexp ≈ -1e30 (an exact no-op when merged).
    """
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    itp = _interpret_default() if interpret is None else interpret
    return _fwd(q, k, v, sm_scale=scale, causal=causal, block_q=block_q, block_k=block_k, interpret=itp)


def _vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    o, lse = flash_attention_with_lse(
        q, k, v, causal, sm_scale, block_q, block_k, interpret
    )
    return (o, lse), (q, k, v, o, lse)


def _vjp_bwd(causal, sm_scale, block_q, block_k, interpret, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    itp = _interpret_default() if interpret is None else interpret
    return _bwd(
        q, k, v, o, lse, do, dlse,
        sm_scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=itp,
    )


flash_attention_with_lse.defvjp(_vjp_fwd, _vjp_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Standard entry point: fused attention output only."""
    o, _ = flash_attention_with_lse(
        q, k, v, causal, sm_scale, block_q, block_k, interpret
    )
    return o


# ---------------------------------------------------------------------------
# dense reference (tests + tiny shapes where kernel overhead dominates)
# ---------------------------------------------------------------------------


def reference_attention_with_lse(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    sm_scale: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """O(S^2)-memory jnp attention returning (output, logsumexp)."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    visible = None
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
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


def reference_attention(q, k, v, *, causal: bool = True, sm_scale=None) -> jax.Array:
    o, _ = reference_attention_with_lse(q, k, v, causal, sm_scale)
    return o
