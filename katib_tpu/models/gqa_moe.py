"""Grouped-query attention in a period of layer kinds, and sparse experts
routed from the layer's input: the third block family of
``transformer_trial`` (``block: gqa_moe``).

The layer is ``SmallThinker-21BA3B-Instruct``'s as its ``config.json`` gives it
(``benchmark/configs/smallthinker-21b-a3b-ep8.json`` has the published keys).
For layer ``l`` and residual stream ``x``:

- the router's logits ``x W_r`` come first, in float32, from the stream as it
  arrives: before any norm and before attention;
- RMSNorm, then grouped-query attention: ``n_heads`` query heads over
  ``n_kv_heads`` key-value heads of ``head_dim`` (query head ``j`` reads
  key-value head ``j // (n_heads / n_kv_heads)``), no bias.  **A period of
  layer kinds**: layer ``l`` is of kind ``l mod period``; ``rope_layout`` says
  which kinds carry rotary positions (over the whole head, the halves paired:
  ``x[i]`` with ``x[i + head_dim/2]``) and which none at all,
  ``window_layout`` which kinds see only the ``window`` newest keys up to
  themselves and which the whole prefix: the flash kernel's ``window``;
- RMSNorm, then the expert layer (``models/mla_moe.py:ExpertLayer``, shared
  with ``block: mla_moe``, not copied): the ``experts_per_token`` largest
  logits of all ``n_experts``, a softmax over the chosen, ReLU-gated experts
  of ``expert_width``, no shared expert, no scaling.  The layer is told which
  experts it holds (``experts_held``) and leaves the others' part out;
- after the last layer an RMSNorm and an untied bias-free head, logits in
  float32.

Departures from the published model: no auxiliary loss; flax's default
initialisers; every block rematerialised (it keeps its input and, where the
attention kernel runs, the kernel's output and logsumexp).  Activations and
products in bfloat16, parameters, router logits, logits and loss in float32.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, ClassVar

import flax.linen as nn
import jax
import jax.numpy as jnp

from katib_tpu.models.lm_head import LMHead, lm_loss, next_token_objective
from katib_tpu.models.mla_moe import ExpertLayer, RMSNorm, rotary, routing_counters
from katib_tpu.ops.flash_attention import remat_block


@dataclasses.dataclass(frozen=True)
class GqaMoeSizes:
    """The block's sizes, under the names ``transformer_trial`` takes them by
    (the layouts as strings of 0 and 1, a period: ``"0111"``).  Hashable, so
    that two models of equal sizes share their programs."""

    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    n_layers: int = 4
    window: int = 16
    window_layout: tuple[int, ...] = (0, 1, 1, 1)  # a period: 1 sees ``window`` keys
    rope_layout: tuple[int, ...] = (0, 1, 1, 1)  # a period: 1 carries rotary positions
    expert_width: int = 64
    n_experts: int = 16
    experts_per_token: int = 2
    experts_held: tuple[int, int] = (0, 16)  # (first index, count)
    rope_theta: float = 1.5e6
    eps: float = 1e-6
    # what ``ExpertLayer`` reads besides, fixed in this family
    n_shared_experts: ClassVar[int] = 0
    routed_scaling: ClassVar[float] = 1.0
    scoring: ClassVar[str] = "softmax"
    expert_act: ClassVar[str] = "relu"

    def layer_kind(self, layer: int) -> tuple[bool, bool]:
        """(windowed, rotary) of layer ``layer``."""
        return (
            bool(self.window_layout[layer % len(self.window_layout)]),
            bool(self.rope_layout[layer % len(self.rope_layout)]),
        )


class GroupedQueryAttention(nn.Module):
    sizes: GqaMoeSizes
    rope: bool
    attn_fn: Callable  # (q [B,H,S,D], k, v [B,Hkv,S,D]) -> [B,H,S,D]
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        z = self.sizes
        b, s, _ = h.shape
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        q = dense(z.n_heads * z.head_dim, name="q_proj")(h).reshape(b, s, z.n_heads, z.head_dim)
        k = dense(z.n_kv_heads * z.head_dim, name="k_proj")(h).reshape(b, s, z.n_kv_heads, z.head_dim)
        v = dense(z.n_kv_heads * z.head_dim, name="v_proj")(h).reshape(b, s, z.n_kv_heads, z.head_dim)
        if self.rope:
            q = rotary(q, z.rope_theta, interleaved=False)
            k = rotary(k, z.rope_theta, interleaved=False)
        heads_first = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        o = self.attn_fn(heads_first(q), heads_first(k), heads_first(v))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, z.n_heads * z.head_dim).astype(self.dtype)
        return dense(z.d_model, name="o_proj")(o)


class GqaMoeBlock(nn.Module):
    sizes: GqaMoeSizes
    rope: bool
    attn_fn: Callable  # this layer's kind of attention: the whole prefix, or a window
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        z = self.sizes
        # the router reads the layer's input as it arrives
        w_router = self.param("router", nn.initializers.lecun_normal(), (z.d_model, z.n_experts))
        router_logits = jnp.dot(x.astype(jnp.float32), w_router, precision=jax.lax.Precision.HIGHEST)
        h = RMSNorm(z.eps, self.dtype, name="input_norm")(x)
        x = x + GroupedQueryAttention(z, self.rope, self.attn_fn, self.dtype, name="attn")(h)
        h = RMSNorm(z.eps, self.dtype, name="post_attn_norm")(x)
        return x + ExpertLayer(z, self.dtype, name="moe")(h, router_logits)


class GqaMoeLM(nn.Module):
    """Decoder-only LM of ``GqaMoeBlock``s; every block is rematerialised in
    the backward pass and keeps its input and, where the attention kernel
    runs, the kernel's output and logsumexp (``remat_block``)."""

    BLOCK = "gqa_moe"  # the block family's name, as ``transformer_trial`` takes it
    REMAT_BLOCKS = True  # every block runs under ``remat_block``

    vocab_size: int
    sizes: GqaMoeSizes = GqaMoeSizes()
    dtype: jnp.dtype = jnp.bfloat16
    attn_fn: Callable | None = None  # the layers that see the whole prefix
    window_attn_fn: Callable | None = None  # the layers that see ``sizes.window`` keys

    @property
    def attn_widths(self) -> tuple[int, int]:
        """A head's key and value widths."""
        return self.sizes.head_dim, self.sizes.head_dim

    @property
    def attn_heads(self) -> int:
        """Query heads: the kernels' grids walk one at a time (the backward a
        key-value head's query heads in turn)."""
        return self.sizes.n_heads

    @property
    def attn_kinds(self) -> list[tuple[int | None, str, int]]:
        """(window, positions, layers) of each kind of attention layer."""
        z = self.sizes
        counts: dict = {}
        for i in range(z.n_layers):
            windowed, rope = z.layer_kind(i)
            kind = (z.window if windowed else None, "rope" if rope else "nope")
            counts[kind] = counts.get(kind, 0) + 1
        return [(window, positions, n) for (window, positions), n in counts.items()]

    @nn.compact
    def __call__(self, tokens, multiply_head: bool = True):
        from katib_tpu.models.transformer import _single_device_attention as dense

        z = self.sizes
        full = self.attn_fn or dense(False)
        windowed = self.window_attn_fn or dense(False, z.window)
        x = nn.Embed(self.vocab_size, z.d_model, dtype=self.dtype, name="embed")(tokens)
        for i in range(z.n_layers):
            in_window, rope = z.layer_kind(i)
            x = remat_block(GqaMoeBlock)(
                z, rope, windowed if in_window else full, self.dtype, name=f"layer_{i}"
            )(x)
        x = RMSNorm(z.eps, self.dtype, name="norm")(x)
        return LMHead(self.vocab_size, use_bias=False, name="head")(x, multiply_head)

    training_loss = staticmethod(next_token_objective)
    reported_loss = staticmethod(lm_loss)
    step_counters = staticmethod(routing_counters)
