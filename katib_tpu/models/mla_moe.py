"""Latent attention and sparse experts: the second block family of
``transformer_trial`` (``block: mla_moe``), beside the GPT-2 ``Block``.

The layer is DeepSeek-V3's as ``kanana-2-30b-a3b-instruct-2601`` configures
it (``benchmark/configs/kanana-2-30b-a3b-ep8.json`` has the published keys):

- RMSNorm before attention and before the MLP, and after the last layer;
- latent attention: queries uncompressed (``q_lora_rank`` null), keys and
  values through one ``kv_lora_rank``-wide compressed vector with its own
  norm, up-projected to ``n_heads`` heads of ``qk_nope_dim`` + ``v_head_dim``;
  a ``qk_rope_dim``-wide rotary slice on every query head and ONE rotary key
  head shared by all heads (pairs interleaved); keys are ``qk_nope_dim +
  qk_rope_dim`` wide, values ``v_head_dim``: the widened flash kernel;
- the first ``first_dense_layers`` layers have a gated (SwiGLU) MLP of
  ``dense_width``; every later layer ``n_shared_experts`` shared experts (one
  SwiGLU of their summed width) plus ``n_experts`` routed experts of
  ``expert_width``, ``experts_per_token`` a token: sigmoid scores in float32,
  the largest chosen (no group limit), weights normalised over the chosen and
  scaled by ``routed_scaling``;
- an untied head, logits in float32.

**The expert layer is told which experts it holds** (``experts_held``: first
index and count): it routes over all ``n_experts``, and computes the part of
the result that its own experts give, for the tokens routed to them: a sort
of the assignments by expert and a grouped product (``jax.lax.ragged_dot``)
over the rows that are held.  **The sorted buffer is as long as the rows
held**: how many are held is known only on the device, so the buffer's length
is one of a short ladder fixed by the shapes (``buffer_rungs``: twice what the
share expects, and all ``T x k`` assignments), and each layer
picks its rung every step from the counted rows; gathers, clears and grouped
products run at that length, forward and backward, and the way back to the
tokens reads the held rows alone (``ops/expert_combine.py``, one kernel).
The last rung holds any routing, so there is no capacity factor,
no dropped token, and no dense product over all experts.  What the absent
experts would have added is left out; the weights' normalisation still runs
over all chosen.  With all ``n_experts`` held it is the whole layer: one
rung, no choice.  On one chip there is no exchange, and nothing here stands
in for one.

Departures from the published model: the router's ``e_score_correction_bias``
is a buffer the Hugging Face model holds at zero, so it is left out of the
sum; no auxiliary loss; flax's default initialisers.  Activations and
products in bfloat16, parameters, router scores, logits and loss in float32.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, ClassVar, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from katib_tpu.models.lm_head import LMHead, lm_loss, next_token_objective
from katib_tpu.ops.expert_combine import Routes, combine, way_back
from katib_tpu.ops.flash_attention import remat_block

#: the collection an expert layer sows a step's routing counts into
ROUTING = "routing"


@dataclasses.dataclass(frozen=True)
class MlaMoeSizes:
    """The block's sizes, under the names ``transformer_trial`` takes them by.
    Hashable, so that two models of equal sizes share their programs."""

    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    first_dense_layers: int = 1
    qk_nope_dim: int = 32
    qk_rope_dim: int = 16
    v_head_dim: int = 32
    kv_lora_rank: int = 64
    dense_width: int = 384
    expert_width: int = 64
    n_experts: int = 16
    experts_per_token: int = 2
    n_shared_experts: int = 1
    routed_scaling: float = 1.0
    experts_held: tuple[int, int] = (0, 16)  # (first index, count)
    rope_theta: float = 1e6
    eps: float = 1e-6
    # what ``ExpertLayer`` reads besides, fixed in this family
    scoring: ClassVar[str] = "sigmoid"
    expert_act: ClassVar[str] = "silu"


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps)
        return (x * scale).astype(self.dtype)


def rotary(x, theta: float, interleaved: bool = True):
    """Rotary position embedding over the last axis of ``x`` [B, S, H, R]: the
    pair ``i`` turns by ``pos * theta**(-2i/R)``.  Pairs interleaved: (x[2i],
    x[2i+1]); else the halves paired: (x[i], x[i + R/2]).  The result holds
    the first members of the pairs, then the second (queries and keys alike,
    so their products do not see the order)."""
    r = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq  # [S, R/2]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    if interleaved:
        pairs = x32.reshape(*x.shape[:-1], r // 2, 2)
        a, b = pairs[..., 0], pairs[..., 1]
    else:
        a, b = x32[..., : r // 2], x32[..., r // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


class LatentAttention(nn.Module):
    sizes: MlaMoeSizes
    attn_fn: Callable  # (q, k, v) [B,H,S,Dqk] x2, [B,H,S,Dv] -> [B,H,S,Dv]
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        z = self.sizes
        b, s, _ = h.shape
        nh, nope, rope, dv, rank = z.n_heads, z.qk_nope_dim, z.qk_rope_dim, z.v_head_dim, z.kv_lora_rank
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        q = dense(nh * (nope + rope), name="q_proj")(h).reshape(b, s, nh, nope + rope)
        c = dense(rank + rope, name="kv_a_proj")(h)
        c_kv = RMSNorm(z.eps, self.dtype, name="kv_norm")(c[..., :rank])
        kv = dense(nh * (nope + dv), name="kv_b_proj")(c_kv).reshape(b, s, nh, nope + dv)
        q_rope = rotary(q[..., nope:], z.rope_theta)
        k_rope = rotary(c[..., None, rank:], z.rope_theta)  # one head for all
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, nh, rope))], axis=-1)
        heads_first = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        o = self.attn_fn(heads_first(q), heads_first(k), heads_first(kv[..., nope:]))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * dv).astype(self.dtype)
        return dense(z.d_model, name="o_proj")(o)


class SwiGLU(nn.Module):
    width: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        gate = dense(self.width, name="gate_proj")(h)
        up = dense(self.width, name="up_proj")(h)
        return dense(h.shape[-1], name="down_proj")(nn.silu(gate) * up)


#: a rung of the sorted buffer is a whole number of so many rows
ROW_TILE = 128


def buffer_rungs(tokens: int, sizes) -> tuple[int, ...]:
    """The lengths the expert layer's sorted buffer may take for ``tokens``
    tokens, from the shapes alone: twice the rows that a share of
    ``experts_held`` expects (``tokens * experts_per_token * held /
    n_experts``), rounded up to ``ROW_TILE``, and the whole ``tokens *
    experts_per_token``, which holds any routing.  A layer that holds half
    the experts or more has that one rung.  Every rung is another set of
    grouped-product kernels to load when a process starts: a third rung at
    four times the expectation read 1-2% faster steps and 2-4 s more set-up."""
    full = tokens * sizes.experts_per_token
    expected = -(-full * sizes.experts_held[1] // sizes.n_experts)
    short = -(-2 * expected // ROW_TILE) * ROW_TILE
    return (short, full) if short < full else (full,)


def expert_buffer(sizes, tokens: int) -> str:
    """The ladder as the ``trial.init`` span carries it: ``"24576 / 98304"``."""
    return " / ".join(map(str, buffer_rungs(tokens, sizes)))


def expert_combine(mesh) -> str:
    """The way back to the tokens as the ``trial.init`` span carries it:
    ``"held rows, kernel"`` on one device, ``"every assignment, gather"``
    where GSPMD partitions the step over a mesh of several devices (the
    kernel cannot be partitioned: ``ops/expert_combine.py``)."""
    return "held rows, kernel" if mesh is None or mesh.size == 1 else "every assignment, gather"


def _in_bounds(x, index):
    """``x[index]`` for indices that are in bounds by construction: no clamp
    and no fill pass beside the gather."""
    return x.at[index].get(mode="promise_in_bounds")


# The two passes between the tokens [T, D] and the rows of the sorted buffer
# [C, D] are each other's transpose: the gather of every row's token ``tok``
# [C], and the way back, which sums every token's rows where ``routes`` says
# they lie (``ops/expert_combine.py``: a kernel that reads the rows held).


@jax.custom_vjp
def _tokens_to_rows(x, tok, routes):
    """Row ``tok[i]`` of ``x`` [T, D] for every row i of the buffer."""
    return _in_bounds(x, tok)


def _tokens_to_rows_fwd(x, tok, routes):
    return _in_bounds(x, tok), routes


def _tokens_to_rows_bwd(routes, g):
    ones = jnp.ones(routes.pos.shape, jnp.float32)
    return way_back(ones, g, routes, g.dtype)[0], None, None


_tokens_to_rows.defvjp(_tokens_to_rows_fwd, _tokens_to_rows_bwd)


class _Routed(NamedTuple):
    """What the routed part is traced from besides its operands."""

    rungs: tuple[int, ...]  # ``buffer_rungs``
    act: Callable  # the gate's activation
    dtype: jnp.dtype  # of the products' operands


def _routed_rung(rows: int, cfg: _Routed, x, weights, w_gate, w_up, w_down, order, inverse, expert, sizes):
    """What the experts held add to every token of ``x`` [T, D], through a
    sorted buffer of ``rows`` rows, and the rows its way back read.
    ``order`` sorts the assignments (flattened token-major: assignment ``t *
    k + j`` is token t's j-th choice) by expert held, the others last, so
    that inside an expert the rows lie in token order; ``inverse`` is its
    inverse; ``weights`` [k, T] is each choice's weight, ``expert`` [k, T]
    its expert among the held (-1: none), ``sizes`` the rows of each expert
    held that lie inside the buffer."""
    k, tokens = weights.shape
    index = order[:rows]
    held = sizes.sum()
    pos = jnp.where(inverse < held, inverse, -1).reshape(tokens, k).T
    routes = Routes(pos, jnp.where(pos >= 0, expert, -1), index, sizes)
    # Rows past the last group belong to no expert held here.  The grouped
    # product's kernels (forward and transposes) leave such rows of their
    # results unwritten, so the operands and the gate's and up's results are
    # cleared there: no stale memory reaches a product, forward or backward.
    # The way back never reads them (``combine``), so the down product's
    # result goes to it as it is.
    held_row = (jnp.arange(rows) < held)[:, None]

    def grouped(lhs, w):
        return jax.lax.ragged_dot(
            jnp.where(held_row, lhs, 0).astype(cfg.dtype), w.astype(cfg.dtype), sizes,
            preferred_element_type=jnp.float32,
        )

    buffer = _tokens_to_rows(x, index // k, routes)
    gate, up = (jnp.where(held_row, grouped(buffer, w), 0.0) for w in (w_gate, w_up))
    return combine(weights, grouped(cfg.act(gate) * up, w_down), routes, cfg.dtype)


def _on_rung(rung, branches, *operands):
    if len(branches) == 1:
        return branches[0](*operands)
    return jax.lax.switch(rung, branches, *operands)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(cfg: _Routed, rung, x, weights, w_gate, w_up, w_down, order, inverse, expert, sizes):
    """``_routed_rung`` on rung ``rung`` of ``cfg.rungs``, chosen on the
    device.  One operation with its own backward: forward and backward each
    branch on the rung, so what a branch keeps for its backward is never
    padded to the longest rung's; the backward computes its rung's forward
    again from the operands (under ``nn.remat`` that is the one recomputation:
    nothing of the rematerialised forward is kept, so none of it runs)."""
    branches = [partial(_routed_rung, rows, cfg) for rows in cfg.rungs]
    return _on_rung(rung, branches, x, weights, w_gate, w_up, w_down, order, inverse, expert, sizes)


def _routed_fwd(cfg, rung, *operands):
    return _routed(cfg, rung, *operands), (rung, *operands)


def _routed_bwd(cfg, res, g):
    def backward(rows):
        def run(g, x, weights, w_gate, w_up, w_down, order, inverse, expert, sizes):
            _, vjp, _ = jax.vjp(
                lambda *diff: _routed_rung(rows, cfg, *diff, order, inverse, expert, sizes),
                x, weights, w_gate, w_up, w_down, has_aux=True,
            )
            return vjp(g)

        return run

    rung, *operands = res
    grads = _on_rung(rung, [backward(rows) for rows in cfg.rungs], g[0], *operands)
    return (None, *grads, None, None, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


@partial(jax.jit, static_argnums=(0, 1))
def _experts(z, dtype, x, logits, w_gate, w_up, w_down):
    """What the experts held add to the tokens ``x`` [T, D] routed by
    ``logits`` [T, n_experts] (float32), and the step's routing counts.
    ``z``: the sizes ``ExpertLayer`` reads.  Under ``jax.jit`` so that a
    model's layers of equal shapes are traced (and differentiated) once, not
    once a layer: with the ladder's branches that was a third of the time a
    step takes to trace."""
    k = z.experts_per_token
    first, count = z.experts_held
    if z.scoring == "sigmoid":
        top_scores, top_experts = jax.lax.top_k(jax.nn.sigmoid(logits), k)  # [T, k]
        weights = z.routed_scaling * top_scores / (top_scores.sum(-1, keepdims=True) + 1e-20)
    elif z.scoring == "softmax":
        top_logits, top_experts = jax.lax.top_k(logits, k)
        weights = z.routed_scaling * jax.nn.softmax(top_logits, axis=-1)
    else:
        raise ValueError(f"ExpertLayer: scoring {z.scoring!r} is neither 'sigmoid' nor 'softmax'")
    act = {"silu": nn.silu, "relu": nn.relu}[z.expert_act]

    # -- the assignments held here, sorted by expert; the others sort last.
    # An assignment's index is ``t * k + j`` (token t's j-th choice): inside
    # an expert the rows then lie in token order, and a tile of tokens' rows
    # are one run, which the way back reads
    local = top_experts - first  # [T, k]
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    inverse = jnp.argsort(order)
    group_sizes = jnp.sum(key[:, None] == jnp.arange(count), axis=0, dtype=jnp.int32)

    # -- the rung of the sorted buffer that holds them with a row to spare
    # (the last: every assignment), and the rows of each expert that lie
    # inside it: all of them, unless the choice is wrong
    rungs = buffer_rungs(x.shape[0], z)
    rung = jnp.sum(group_sizes.sum() >= jnp.asarray(rungs[:-1], jnp.int32))  # the rungs too short
    buffer_rows = jnp.asarray(rungs, jnp.int32)[rung]
    given = jnp.diff(jnp.minimum(jnp.cumsum(group_sizes), buffer_rows), prepend=0)

    # -- the grouped product over the rows that are held
    routed, combine_rows = _routed(
        _Routed(rungs, act, dtype), rung,
        x, weights.T, w_gate, w_up, w_down, order, inverse, jnp.where(held, local, -1).T, given,
    )
    # the rows computed for each held expert, the assignments as counted
    # before the sort (to an expert held here, to any expert), the rows of
    # the rung that ran, and the rows the forward's way back read
    counts = {
        "expert_tokens": given,
        "assignments": jnp.stack([held.sum(), jnp.int32(held.size)]),
        "buffer_rows": buffer_rows,
        "combine_rows": combine_rows,
    }
    return routed, counts


class ExpertLayer(nn.Module):
    """Router over ``n_experts``, the routed experts held here, and the shared
    experts.  Returns what this share adds to the residual stream; sows the
    step's routing counts into ``ROUTING`` (where that collection is mutable).
    The routed part runs over a sorted buffer as long as the rows held, the
    rung of ``buffer_rungs`` that holds them chosen on the device; the last
    rung is every assignment, so no token is dropped whatever the router does.

    One layer for every block family that has experts.  It reads of ``sizes``:
    ``n_experts``, ``experts_per_token``, ``experts_held``, ``expert_width``,
    ``n_shared_experts`` (0: none), ``routed_scaling``, ``scoring``
    (``sigmoid``: the largest sigmoid scores, normalised over the chosen;
    ``softmax``: the largest logits, a softmax over the chosen) and
    ``expert_act`` (the gate's activation: ``silu`` | ``relu``).  A block
    whose router reads another stream than ``h`` hands the ``router_logits``
    [B, S, n_experts] in (float32); the router's matrix is then the block's."""

    sizes: MlaMoeSizes  # or another family's sizes with the fields above
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h, router_logits=None):
        z = self.sizes
        b, s, d = h.shape
        count = z.experts_held[1]
        x = h.reshape(b * s, d)
        if router_logits is None:  # route over all experts, in float32
            w_router = self.param("router", nn.initializers.lecun_normal(), (d, z.n_experts))
            logits = jnp.dot(x.astype(jnp.float32), w_router, precision=jax.lax.Precision.HIGHEST)
        else:
            logits = router_logits.reshape(b * s, z.n_experts)
        init = nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal", batch_axis=(0,))
        w_gate = self.param("experts_gate", init, (count, d, z.expert_width))
        w_up = self.param("experts_up", init, (count, d, z.expert_width))
        w_down = self.param("experts_down", init, (count, z.expert_width, d))
        routed, counts = _experts(z, jnp.dtype(self.dtype), x, logits, w_gate, w_up, w_down)
        routed = routed.reshape(b, s, d)
        if not self.is_initializing():
            for name, value in counts.items():
                self.sow(ROUTING, name, value)

        if not z.n_shared_experts:
            return routed
        shared = SwiGLU(z.expert_width * z.n_shared_experts, self.dtype, name="shared")(h)
        return shared + routed


def routing_counters(routing) -> dict:
    """A step's routing counts (``ROUTING`` as the step returned it, fetched)
    as the attributes of a span: assignments to the experts held and to all,
    the busiest held expert's tokens in one layer against the mean, the
    assignments to a held expert that its product did not compute (0: the
    rung that ran held every one), the rows of the rungs that ran, and the
    rows the way back to the tokens read (the held rows and the kernel's
    granules' edges; every assignment's where the gather runs), each summed
    over the layers (``moe_assignments_total`` is their worst case)."""
    leaves: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(routing):
        name = [p.key for p in path if hasattr(p, "key")][-1]
        leaves.setdefault(name, []).append(np.asarray(leaf))
    tokens = np.stack(leaves["expert_tokens"])  # [expert layers, held]
    held, total = np.stack(leaves["assignments"]).sum(axis=0)
    return {
        "moe_assignments_held": int(held),
        "moe_assignments_total": int(total),
        "moe_expert_tokens_max": int(tokens.max()),
        "moe_expert_tokens_mean": float(tokens.mean()),
        "moe_tokens_dropped": int(held - tokens.sum()),
        "moe_buffer_rows": int(np.sum(leaves["buffer_rows"])),
        "moe_combine_rows": int(np.sum(leaves["combine_rows"])),
    }


class MlaMoeBlock(nn.Module):
    sizes: MlaMoeSizes
    dense: bool  # a leading dense layer (SwiGLU of ``dense_width``), else an expert layer
    attn_fn: Callable
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        z = self.sizes
        h = RMSNorm(z.eps, self.dtype, name="input_norm")(x)
        x = x + LatentAttention(z, self.attn_fn, self.dtype, name="attn")(h)
        h = RMSNorm(z.eps, self.dtype, name="post_attn_norm")(x)
        if self.dense:
            return x + SwiGLU(z.dense_width, self.dtype, name="mlp")(h)
        return x + ExpertLayer(z, self.dtype, name="moe")(h)


class MlaMoeLM(nn.Module):
    """Decoder-only LM of ``MlaMoeBlock``s; every block is rematerialised in
    the backward pass (one layer's activations at 8192 tokens are over 1 GB)
    and keeps its input and, where the attention kernel runs, the kernel's
    output and logsumexp (``remat_block``)."""

    BLOCK = "mla_moe"  # the block family's name, as ``transformer_trial`` takes it
    REMAT_BLOCKS = True  # every block runs under ``remat_block``

    vocab_size: int
    sizes: MlaMoeSizes = MlaMoeSizes()
    dtype: jnp.dtype = jnp.bfloat16
    attn_fn: Callable | None = None

    @property
    def attn_widths(self) -> tuple[int, int]:
        """A head's key and value widths."""
        return self.sizes.qk_nope_dim + self.sizes.qk_rope_dim, self.sizes.v_head_dim

    @property
    def attn_heads(self) -> int:
        """Query heads: the kernels' grids walk one at a time."""
        return self.sizes.n_heads

    @property
    def attn_kinds(self) -> list[tuple[int | None, str, int]]:
        """(window, positions, layers) of each kind of attention layer."""
        return [(None, "rope", self.sizes.n_layers)]

    @nn.compact
    def __call__(self, tokens, multiply_head: bool = True):
        z = self.sizes
        attn = self.attn_fn
        if attn is None:
            from katib_tpu.models.transformer import _dense_causal_attention as attn
        x = nn.Embed(self.vocab_size, z.d_model, dtype=self.dtype, name="embed")(tokens)
        for i in range(z.n_layers):
            x = remat_block(MlaMoeBlock)(
                z, i < z.first_dense_layers, attn, self.dtype, name=f"layer_{i}"
            )(x)
        x = RMSNorm(z.eps, self.dtype, name="norm")(x)
        return LMHead(self.vocab_size, use_bias=False, name="head")(x, multiply_head)

    training_loss = staticmethod(next_token_objective)
    reported_loss = staticmethod(lm_loss)
    step_counters = staticmethod(routing_counters)
