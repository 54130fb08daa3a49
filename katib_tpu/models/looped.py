"""A stack of layers run several times over the same weights, an exit after
every pass, a learned exit gate and the expected-exit loss: the fourth block
family of ``transformer_trial`` (``block: looped``).

The model is ``Ouro-2.6B``'s as its ``config.json`` gives it and as the
family's paper ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741) describes what the config leaves out
(``benchmark/configs/ouro-2.6b-l6.json`` has the published keys and lists
what is assumed).  With ``T`` = ``ut_steps`` and ``L`` = ``n_layers``:

- block ``l``, four RMSNorms (weights only), no bias anywhere: ``a = x +
  N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``: the sub-layers' OUTPUTS are
  normed too, before they join the residual stream.  ``Attn``: ``n_heads``
  query and as many key-value heads of ``head_dim``, rotary positions over the
  whole head with the halves paired, causal.  ``MLP``: SwiGLU of
  ``mlp_width``;
- pass ``t = 1..T`` over the SAME weights: ``z_0 = Embed(tokens)``, ``z_t =
  N_f(B_L(...B_1(z_{t-1})))``: the final norm closes every pass and its output
  is the next pass's input; positions are the same in every pass.  The passes
  are a loop in the program (``nn.scan`` with the parameters broadcast), so
  the step holds the stack once whatever ``T`` is, and a shared weight's
  gradient is the sum over the passes;
- exit ``t``: ``logits_t = z_t W_head`` (float32) and ``l_t[i]``, the next
  token's cross entropy at position ``i``;
- gate: ``lambda_t[i] = sigmoid(z_t[i] . w_g + b_g)`` in float32, one vector
  and a bias for the whole model;
- a token's exit distribution: ``q_t = lambda_t prod_{j<t} (1 - lambda_j)``
  for ``t < T``, ``q_T = prod_{j<T} (1 - lambda_j)``;
- the training loss (``training_loss``): ``mean_i [sum_t q_t[i] l_t[i] -
  exit_beta H(q[i])]``, ``H`` the entropy;
- the reported loss (``reported_loss``): ``mean_i l_T[i]``.  The published
  ``early_exit_threshold`` is 1: nothing exits early, every pass runs and the
  last exit answers.

Departures from the published model: no early exit at evaluation; flax's
default initialisers; every block rematerialised (it keeps its input and,
where the attention kernel runs, the kernel's output and logsumexp of every
pass).  Activations and products in bfloat16, parameters, gate, logits and
losses in float32.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from katib_tpu.models.lm_head import HeadInputs, LMHead, lm_loss, weighted_token_losses
from katib_tpu.models.mla_moe import RMSNorm, SwiGLU, rotary
from katib_tpu.ops.flash_attention import remat_block


@dataclasses.dataclass(frozen=True)
class LoopedSizes:
    """The block's sizes, under the names ``transformer_trial`` takes them by.
    Hashable, so that two models of equal sizes share their programs."""

    d_model: int = 128
    n_heads: int = 4
    head_dim: int = 32
    mlp_width: int = 384
    n_layers: int = 2
    ut_steps: int = 4
    rope_theta: float = 1e6
    eps: float = 1e-6
    exit_beta: float = 0.1


class LoopedAttention(nn.Module):
    sizes: LoopedSizes
    attn_fn: Callable  # (q, k, v) [B,H,S,D] -> [B,H,S,D]
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        z = self.sizes
        b, s, _ = h.shape
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)

        def heads(name):  # [B, S, D] -> [B, S, H, head_dim]
            return dense(z.n_heads * z.head_dim, name=name)(h).reshape(b, s, z.n_heads, z.head_dim)

        q = rotary(heads("q_proj"), z.rope_theta, interleaved=False)
        k = rotary(heads("k_proj"), z.rope_theta, interleaved=False)
        heads_first = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        o = self.attn_fn(heads_first(q), heads_first(k), heads_first(heads("v_proj")))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, z.n_heads * z.head_dim).astype(self.dtype)
        return dense(z.d_model, name="o_proj")(o)


class LoopedBlock(nn.Module):
    sizes: LoopedSizes
    attn_fn: Callable
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        z = self.sizes
        norm = partial(RMSNorm, z.eps, self.dtype)
        h = LoopedAttention(z, self.attn_fn, self.dtype, name="attn")(norm(name="input_norm")(x))
        x = x + norm(name="attn_out_norm")(h)
        h = SwiGLU(z.mlp_width, self.dtype, name="mlp")(norm(name="post_attn_norm")(x))
        return x + norm(name="mlp_out_norm")(h)


class LoopedPass(nn.Module):
    """One pass of the stack, as the body of the scan over the passes: the
    carry is the pass's input, and every pass's output is kept (its exit).
    Every block is rematerialised in the backward pass and keeps its input
    and, where the attention kernel runs, the kernel's output and logsumexp
    (``remat_block``): the backward loop's body holds no forward kernel."""

    sizes: LoopedSizes
    attn_fn: Callable
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, _):
        z = self.sizes
        for i in range(z.n_layers):
            x = remat_block(LoopedBlock)(z, self.attn_fn, self.dtype, name=f"layer_{i}")(x)
        x = RMSNorm(z.eps, self.dtype, name="norm")(x)
        return x, x


@flax.struct.dataclass
class Exits:
    """What the model hands its losses: every exit's logits ``[T, B, S, V]``,
    or what the head would multiply with the hidden states of every exit
    ``[T, B, S, D]`` (``HeadInputs``), and the gate ``lambda`` ``[T, B, S]``
    (the last pass's is computed and read by nothing)."""

    logits: jnp.ndarray | HeadInputs
    gate: jnp.ndarray

    def last(self) -> jnp.ndarray | HeadInputs:
        """The last exit alone."""
        if isinstance(self.logits, HeadInputs):
            return self.logits.replace(hidden=self.logits.hidden[-1])
        return self.logits[-1]


def exit_distribution(gate):
    """``q`` ``[T, ...]`` from ``lambda`` ``[T, ...]``: the share of a token
    that leaves at exit ``t``; the last exit takes what is left."""
    stay = jnp.cumprod(1.0 - gate[:-1], axis=0)  # [T-1, ...]: still inside after exit t
    before = jnp.concatenate([jnp.ones_like(gate[:1]), stay], axis=0)
    return jnp.concatenate([gate[:-1] * before[:-1], before[-1:]], axis=0)


class LoopedLM(nn.Module):
    """Decoder-only LM of ``LoopedBlock``s run ``ut_steps`` times over shared
    weights, with an exit after every pass."""

    BLOCK = "looped"  # the block family's name, as ``transformer_trial`` takes it
    REMAT_BLOCKS = True  # every block runs under ``remat_block``

    vocab_size: int
    sizes: LoopedSizes = LoopedSizes()
    dtype: jnp.dtype = jnp.bfloat16
    attn_fn: Callable | None = None

    @property
    def attn_widths(self) -> tuple[int, int]:
        """A head's key and value widths."""
        return self.sizes.head_dim, self.sizes.head_dim

    @property
    def attn_heads(self) -> int:
        """Query heads: the kernels' grids walk one at a time."""
        return self.sizes.n_heads

    @property
    def attn_kinds(self) -> list[tuple[int | None, str, int]]:
        """(window, positions, layers) of each kind of attention layer."""
        return [(None, "rope", self.sizes.n_layers)]

    @property
    def passes(self) -> int:
        """Times a step applies every layer, and exits its loss is taken at."""
        return self.sizes.ut_steps

    @nn.compact
    def __call__(self, tokens, multiply_head: bool = True) -> Exits:
        z = self.sizes
        attn = self.attn_fn
        if attn is None:
            from katib_tpu.models.transformer import _dense_causal_attention as attn
        x = nn.Embed(self.vocab_size, z.d_model, dtype=self.dtype, name="embed")(tokens)
        stack = nn.scan(
            LoopedPass, variable_broadcast="params", split_rngs={"params": False}, length=z.ut_steps
        )
        _, exits = stack(z, attn, self.dtype, name="stack")(x, None)  # [T, B, S, D]
        gate = nn.Dense(1, dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST, name="exit_gate")
        lam = jax.nn.sigmoid(gate(exits.astype(jnp.float32))[..., 0])
        return Exits(LMHead(self.vocab_size, use_bias=False, name="head")(exits, multiply_head), lam)

    def training_loss(self, exits: Exits, tokens):
        """The entropy-regularised expected-exit objective, and what a report
        reads of it (``step_counters``): every exit's mean loss and mean share
        and the mean entropy, over the positions that have a next token."""
        t, b, s = exits.gate.shape
        q = exit_distribution(exits.gate)
        counted = (jnp.arange(s) < s - 1).astype(jnp.float32) / (b * (s - 1))
        expected, losses = weighted_token_losses(
            exits.logits, jnp.broadcast_to(tokens, (t, b, s)), q * counted
        )
        entropy = -jnp.sum(q * jnp.log(jnp.maximum(q, 1e-30)), axis=0)  # [B, S]
        mean = lambda a: jnp.sum(a * counted, axis=(-2, -1))  # noqa: E731
        counters = {"exit_loss": mean(losses), "exit_share": mean(q), "exit_entropy": mean(entropy)}
        return expected - self.sizes.exit_beta * counters["exit_entropy"], counters

    def reported_loss(self, exits: Exits, tokens):
        """The last exit's cross entropy: what the published model's forward
        answers with at its ``early_exit_threshold`` of 1."""
        return lm_loss(exits.last(), tokens)

    @staticmethod
    def step_counters(counters) -> dict:
        """A step's exits (``training_loss``'s second result, fetched) as the
        attributes of a span: ``exit_loss_<t>`` and ``exit_share_<t>`` for
        every exit from 1, the mean exit ``exit_step_mean`` and
        ``exit_entropy``."""
        losses, shares = np.asarray(counters["exit_loss"]), np.asarray(counters["exit_share"])
        out = {f"exit_loss_{t + 1}": float(v) for t, v in enumerate(losses)}
        out.update({f"exit_share_{t + 1}": float(v) for t, v in enumerate(shares)})
        out["exit_step_mean"] = float(np.sum(shares * np.arange(1, len(shares) + 1)))
        out["exit_entropy"] = float(counters["exit_entropy"])
        return out
