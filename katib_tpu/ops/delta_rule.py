"""The gated delta rule with one decay per channel (Kimi Delta Attention), as a
chunked scan in Pallas TPU kernels, forward and backward.

For one head, keys and queries of width ``D`` and values of width ``Dv``, a
state ``S`` [D, Dv] in float32 that starts at 0:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t`` [D] is the log of the decay (<= 0, one value a channel of the key),
``beta_t`` the step (in (0, 2) where negative eigenvalues are allowed: the
eigenvalue of ``I - beta k k^T`` along ``k`` is ``1 - beta``).

**The chunked form.** Positions are taken ``CHUNK`` (64) at a time; inside a
chunk ``G`` is the sum of ``g`` from the chunk's first position to each one
(inclusive), ``S_0`` the state before it.  With ``S_r = Diag(e^{G_r}) S_0 +
sum_{s<=r} Diag(e^{G_r - G_s}) k_s delta_s^T`` the rule gives ``(I + L)
Delta = beta (V - K_G S_0)``, where ``L_rs = beta_r sum_c k_rc k_sc
e^{G_rc - G_sc}`` (s < r) and ``K_G = k e^{G}``.  So, with ``T = (I + L)^-1``
(the WY/UT form):

    Delta = T (beta V) - T (beta K_G) S_0
    O     = (q e^{G}) S_0 + P Delta,     P_rs = sum_c q_rc k_sc e^{G_rc - G_sc}, s <= r
    S_C   = Diag(e^{G_C}) S_0 + (k e^{G_C - G})^T Delta

``T`` is computed without a substitution loop: ``L`` is split into its
diagonal blocks of ``SUB`` (16) positions and the rest, ``(I + L_D)^-1 =
(I - L_D)(I + L_D^2)(I + L_D^4)(I + L_D^8)`` (``L_D^16 = 0``), ``N = (I +
L_D)^-1 L_O`` is strictly lower by blocks (``N^4 = 0``) and ``T = (I - N)(I +
N^2)(I + L_D)^-1``: ten 64 x 64 products, powers no higher than the eighth.

**Strong decay.** ``e^{G_r - G_s}`` is never formed as ``e^{G_r} e^{-G_s}``
(which overflows where a channel decays by more than 88 nats inside a chunk).
Between a row of sub-block ``i`` and a column of an earlier sub-block the
decay goes through the boundary ``B_i`` (``G`` at the last position before the
sub-block): ``e^{G_r - B_i} e^{B_i - G_s}``, both factors <= 1.  Inside a
sub-block every exponent is taken of the difference itself, one column offset
of the sixteen at a time.  Every exponent is masked before ``exp``, never
after, so no masked-out infinity meets a zero in the gradient.

**The kernels.** The forward walks (batch, head, chunk), the chunks in order
(``arbitrary``), the state in float32 VMEM scratch; it writes ``o`` and the
state at each chunk's start (``[B, H, chunks, D, Dv]``, 32 MiB a layer at 64
chunks of 8 heads of 128).  The backward walks the chunks in reverse with the
state's gradient in VMEM scratch; each step is the VJP of the very function
the forward ran on that chunk (``_chunk``, traced into the kernel by
``jax.vjp``), from the chunk's stored start state, so forward and backward
cannot disagree.  The within-chunk sums ``G`` are formed outside (their
gradient becomes ``g``'s by the reverse sum, in XLA).  Every product is
float32 at ``HIGHEST``.

The forward rule names the kernel's two results (``KDA_RESULTS``): a block
rematerialised under ``remat_kda_block`` keeps them, and its rematerialised
forward runs no scan kernel.  ``chunked_delta_rule`` is the same chunk
function under ``lax.scan`` in plain XLA (a model's path off the TPU);
``reference_delta_rule`` is the token-by-token recurrence (the tests').

Every kernel is a ``tpu_custom_call`` whose first operand is float32 and whose
first result is ``f32[B,H,S,Dv]`` (the forward's output; the backward's dq is
written float32 too): ``KDA_MARK`` tells them from the attention kernels
(bfloat16 first), the grouped products (int32 first) and the expert layer's
way back (float32 first, 2-D).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from katib_tpu.ops.flash_attention import KERNEL_RESULTS, VMEM_LIMIT_BYTES, _interpret_default

#: positions a chunk, and a sub-block of one (the diagonal blocks of ``L``)
CHUNK = 64
SUB = 16
#: the names the forward rule gives the kernel's output and chunk start states
KDA_RESULTS = ("kda_out", "kda_states")
#: the device trace names an operation by its HLO text: every scan kernel's
#: operands start with a float32 array of four dimensions (q, cast), as no
#: other kernel of the repository's do (a regular expression)
KDA_MARK = r"operand_layout_constraints=\{f32\[\d+,\d+,\d+,\d+\]"
# one policy object for every rematerialised block: a block's program names it
_KEEP_RESULTS = jax.checkpoint_policies.save_only_these_names(*KERNEL_RESULTS, *KDA_RESULTS)

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HI, preferred_element_type=jnp.float32)


def _chunk(q, k, v, G, beta, s0):
    """One chunk: ``q``, ``k`` [C, D], ``v`` [C, Dv], ``G`` [C, D] (the
    within-chunk sums of the log decay), ``beta`` [C, 1], ``s0`` [D, Dv], all
    float32.  Returns the chunk's output [C, Dv] and the state after it."""
    c, d = q.shape
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)
    row, col = iota((c, c), 0), iota((c, c), 1)
    pos = iota((c, d), 0)  # a position's index, beside each channel
    sub = pos // SUB

    def at(x, r):  # row ``r`` of ``x`` [C, D] as [1, D]
        return jnp.sum(jnp.where(pos == r, x, 0.0), axis=0, keepdims=True)

    g_last = at(G, c - 1)
    qg, kg = q * jnp.exp(G), k * jnp.exp(G)
    kend = k * jnp.exp(g_last - G)

    # -- M (keys against keys, strictly lower) and P (queries against keys,
    # the diagonal too), each sum over the channels of the decay between them
    m = jnp.zeros((c, c), jnp.float32)
    p = jnp.zeros((c, c), jnp.float32)
    for i in range(1, c // SUB):  # rows of sub-block i against every earlier column
        boundary = at(G, i * SUB - 1)
        rows, before = sub == i, pos < i * SUB
        a_scale = jnp.exp(jnp.where(rows, G - boundary, 0.0))
        keys = jnp.where(before, k * jnp.exp(jnp.where(before, boundary - G, 0.0)), 0.0)
        m = m + _mm(jnp.where(rows, k * a_scale, 0.0), keys, _NT)
        p = p + _mm(jnp.where(rows, q * a_scale, 0.0), keys, _NT)
    offset = pos % SUB
    for j in range(SUB):  # inside the sub-blocks: the column at offset j of each
        picks = (col == (row // SUB) * SUB + j).astype(jnp.float32)  # row r -> its sub-block's column j
        g_ref, k_ref = _mm(picks, G), _mm(picks, k)
        seen = offset >= j
        terms = k_ref * jnp.exp(jnp.where(seen, G - g_ref, 0.0))
        m_col = jnp.sum(jnp.where(offset > j, k * terms, 0.0), axis=1, keepdims=True)
        p_col = jnp.sum(jnp.where(seen, q * terms, 0.0), axis=1, keepdims=True)
        m = m + picks * m_col
        p = p + picks * p_col

    # -- T = (I + beta M)^-1: the diagonal blocks by a product of powers, then
    # the blocks below them
    lower = beta * m
    eye = (row == col).astype(jnp.float32)
    ld = jnp.where(row // SUB == col // SUB, lower, 0.0)
    lo = lower - ld
    l2 = _mm(ld, ld)
    l4 = _mm(l2, l2)
    l8 = _mm(l4, l4)
    d_inv = _mm(_mm(_mm(eye - ld, eye + l2), eye + l4), eye + l8)
    n = _mm(d_inv, lo)
    t = _mm(_mm(eye - n, eye + _mm(n, n)), d_inv)

    delta = _mm(t, beta * v) - _mm(_mm(t, beta * kg), s0)
    o = _mm(qg, s0) + _mm(p, delta)
    eye_d = iota((d, d), 0) == iota((d, d), 1)
    s1 = _mm(jnp.where(eye_d, jnp.exp(g_last), 0.0), s0) + _mm(kend, delta, _TN)
    return o, s1


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _load(*refs):
    return tuple(r[0, 0].astype(jnp.float32) for r in refs)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, state):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    s0 = state[...]
    s_ref[0, 0, 0] = s0
    o, s1 = _chunk(*_load(q_ref, k_ref, v_ref, g_ref, b_ref), s0)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    state[...] = s1


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    _, vjp = jax.vjp(_chunk, *_load(q_ref, k_ref, v_ref, g_ref, b_ref), s_ref[0, 0, 0])
    dq, dk, dv, dg, db, ds0 = vjp((do_ref[0, 0].astype(jnp.float32), dstate[...]))
    for ref, x in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv), (dg_ref, dg), (db_ref, db)):
        ref[0, 0] = x.astype(ref.dtype)
    dstate[...] = ds0


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(scratch, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )


def _specs(d, dv, chunk_of):
    """Blocks of one chunk of q, k, v, G, beta and the state at its start;
    ``chunk_of`` maps the grid's third index to the chunk."""
    at = lambda width: pl.BlockSpec((1, 1, CHUNK, width), lambda i, j, c: (i, j, chunk_of(c), 0))  # noqa: E731
    state = pl.BlockSpec((1, 1, 1, d, dv), lambda i, j, c: (i, j, chunk_of(c), 0, 0))
    return at, state


def _scan_fwd(q, k, v, G, beta, interpret):
    b, h, s, d = q.shape
    dv = v.shape[-1]
    chunks = s // CHUNK
    at, state = _specs(d, dv, lambda c: c)
    return _call(
        _fwd_kernel, (b, h, chunks),
        [at(d), at(d), at(dv), at(d), at(1)],
        [at(dv), state],
        [jax.ShapeDtypeStruct((b, h, s, dv), jnp.float32), jax.ShapeDtypeStruct((b, h, chunks, d, dv), jnp.float32)],
        (d, dv), interpret,
    )(q.astype(jnp.float32), k, v, G, beta)


def _scan_bwd(q, k, v, G, beta, states, do, interpret):
    b, h, s, d = q.shape
    dv = v.shape[-1]
    chunks = s // CHUNK
    at, state = _specs(d, dv, lambda c: chunks - 1 - c)
    return _call(
        _bwd_kernel, (b, h, chunks),
        [at(d), at(d), at(dv), at(d), at(1), state, at(dv)],
        [at(d), at(d), at(dv), at(d), at(1)],
        [jax.ShapeDtypeStruct((b, h, s, d), jnp.float32), jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct(G.shape, jnp.float32),
         jax.ShapeDtypeStruct(beta.shape, jnp.float32)],
        (d, dv), interpret,
    )(q.astype(jnp.float32), k, v, G, beta, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, G, beta, interpret):
    return _scan_fwd(q, k, v, G, beta, interpret)[0]


def _scan_vjp_fwd(q, k, v, G, beta, interpret):
    o, states = _scan_fwd(q, k, v, G, beta, interpret)
    # named on the arrays that are both results and residuals (as the flash
    # kernel's rule does): a rematerialised block keeps them and runs no kernel
    o, states = map(checkpoint_name, (o, states), KDA_RESULTS)
    return o, (q, k, v, G, beta, states)


def _scan_vjp_bwd(interpret, res, do):
    q, k, v, G, beta, states = res
    dq, dk, dv, dg, db = _scan_bwd(q, k, v, G, beta, states, do.astype(jnp.float32), interpret)
    return dq.astype(q.dtype), dk, dv, dg, db


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def _chunked(q, k, v, g, beta):
    """The operands padded to whole chunks, and ``g`` summed within each."""
    b, h, s, d = q.shape
    pad = -s % CHUNK
    if pad:  # positions after the last change nothing before them
        widen = lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))  # noqa: E731
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    G = jnp.cumsum(g.astype(jnp.float32).reshape(b, h, -1, CHUNK, d), axis=3).reshape(g.shape)
    return q, k, v, G, beta.astype(jnp.float32)[..., None]


def delta_rule(q, k, v, g, beta, *, interpret: bool | None = None):
    """The gated delta rule over ``q``, ``k`` [B, H, S, D], ``v`` [B, H, S, Dv]
    (any float dtype; ``q`` and ``k`` as the layer scaled them), ``g`` [B, H,
    S, D] the log decay (float32, <= 0) and ``beta`` [B, H, S]; returns ``o``
    [B, H, S, Dv] in float32, the state starting at 0.  The Pallas kernels
    (interpreted off the TPU); differentiable in every operand."""
    s = q.shape[2]
    itp = _interpret_default() if interpret is None else interpret
    return _scan(*_chunked(q, k, v, g, beta), itp)[:, :, :s]


def chunked_delta_rule(q, k, v, g, beta):
    """The same chunks in plain XLA (``lax.scan`` over ``_chunk``), the
    gradient by ``jax.grad``: a model's path off the TPU."""
    s = q.shape[2]
    q, k, v, G, beta = _chunked(q, k, v, g, beta)
    b, h, sp, d = q.shape

    def by_chunk(x):  # [B, H, S, W] -> [chunks, B*H, CHUNK, W]
        return jnp.moveaxis(x.astype(jnp.float32).reshape(b * h, sp // CHUNK, CHUNK, x.shape[-1]), 1, 0)

    def step(state, xs):
        o, state = jax.vmap(_chunk)(*xs, state)
        return state, o

    zero = jnp.zeros((b * h, d, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, zero, tuple(map(by_chunk, (q, k, v, G, beta))))
    return jnp.moveaxis(o, 0, 1).reshape(b, h, sp, -1)[:, :, :s]


def reference_delta_rule(q, k, v, g, beta):
    """The recurrence token by token in float32 at ``HIGHEST`` (the tests')."""

    def step(state, xs):
        qt, kt, vt, gt, bt = xs  # [B, H, D], ..., [B, H]
        decayed = jnp.exp(gt)[..., None] * state
        pred = jnp.einsum("bhd,bhde->bhe", kt, decayed, precision=_HI)
        state = decayed + bt[..., None, None] * kt[..., None] * (vt - pred)[..., None, :]
        return state, jnp.einsum("bhd,bhde->bhe", qt, state, precision=_HI)

    b, h, _, d = q.shape
    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 2, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2)


def remat_kda_block(block: type[nn.Module]) -> type[nn.Module]:
    """``nn.remat`` of a block whose mixer is the delta rule's kernels or the
    flash kernel: it keeps both kernels' named results (``KDA_RESULTS``,
    ``KERNEL_RESULTS``) and computes everything else again."""
    return nn.remat(block, policy=_KEEP_RESULTS)
