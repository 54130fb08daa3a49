"""Kimi delta attention in a period with gated grouped-query attention, beside
sparse experts and a shared one: the sixth block family of
``transformer_trial`` (``block: kda_moe``).

The layer is ``Solar-Open2-250B``'s as its ``config.json`` gives it
(``benchmark/configs/solar-open2-250b-tp8ep40.json`` has the published keys).
For residual stream ``x``, with ``RN`` an RMSNorm with weights only and ``h =
RN_1(x)``:

- **a period of two mixer kinds**: layer ``l`` is grouped-query attention
  where ``l mod gqa_period == 0`` and Kimi delta attention otherwise; no layer
  has positions;
- **Kimi delta attention** (``kda_heads`` heads of ``kda_head_dim``): ``q, k,
  v = SiLU(conv(h W))`` with ``conv`` a causal depthwise convolution over
  positions of ``conv_size`` taps, no bias; ``q`` and ``k`` divided by their
  norm over the head, ``q`` also by ``sqrt(kda_head_dim)``; the log decay,
  one value a channel, ``g = -exp(A) softplus(h W_fa W_fb + b_dt)`` (``A`` one
  a head, ``W_fa`` of rank ``kda_head_dim``); the step ``beta = 2
  sigmoid(h w_b)`` in (0, 2): negative eigenvalues allowed; the gated delta
  rule ``ops/delta_rule.py`` (the chunked scan's kernels on the TPU); its
  output normed over each head (``RN_o``, one weight for all heads) and gated
  by ``sigmoid(h W_ga W_gb)``, then ``W_o``;
- **gated grouped-query attention** (``n_heads`` query heads over
  ``n_kv_heads`` of ``head_dim``, no positions): the flash kernel's output
  times ``sigmoid(h W_gate)`` elementwise, then ``W_o``;
- ``x' + experts(RN_2(x'))``: ``models/mla_moe.py:ExpertLayer`` with its
  router on ``RN_2``'s output, sigmoid scores, the ``experts_per_token``
  largest of all ``n_experts`` normalised over the chosen, SiLU-gated experts
  of ``expert_width`` and ``n_shared_experts`` shared ones; told which experts
  it holds (``experts_held``);
- after the last layer an RMSNorm and an untied bias-free head.

**Heads held.** ``n_heads``, ``n_kv_heads`` and ``kda_heads`` count the heads
THIS chip holds of a layer whose heads are shared out (a tensor-parallel
group): the layer computes its heads' part of ``W_o``'s product and leaves
the others' out, as the expert layer leaves out the experts it does not hold.

Initialisers: flax's defaults, but ``A`` and ``b_dt`` as Kimi Linear draws
them (``log U(1, 16)``; ``b_dt`` the inverse softplus of a step log-uniform
in [1e-3, 1e-1]).  Departures from the published model: no auxiliary loss;
every block rematerialised (it keeps its input, and the scan's or the
attention kernel's results).  Activations and products in bfloat16;
parameters, the decay, the step, the scan's state and output, the router,
logits and loss in float32.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, ClassVar

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from katib_tpu.models.lm_head import LMHead, lm_loss, next_token_objective
from katib_tpu.models.mla_moe import ROUTING, ExpertLayer, RMSNorm, routing_counters
from katib_tpu.ops.delta_rule import remat_kda_block

#: the counts a delta-rule layer sows into ``ROUTING``: (token, head) pairs
#: whose step passes 1 (a negative eigenvalue), and all pairs
STEP_COUNTS = ("kda_neg_eig_pairs", "kda_pairs")


@dataclasses.dataclass(frozen=True)
class KdaMoeSizes:
    """The block's sizes, under the names ``transformer_trial`` takes them by.
    Hashable, so that two models of equal sizes share their programs."""

    d_model: int = 128
    n_heads: int = 4  # query heads held, grouped-query layers
    n_kv_heads: int = 1
    head_dim: int = 32
    kda_heads: int = 4  # delta-rule heads held
    kda_head_dim: int = 32
    conv_size: int = 4
    n_layers: int = 4
    gqa_period: int = 4  # layer l is grouped-query attention where l % gqa_period == 0
    expert_width: int = 64
    n_experts: int = 16
    experts_per_token: int = 2
    n_shared_experts: int = 1
    experts_held: tuple[int, int] = (0, 16)  # (first index, count)
    eps: float = 1e-5
    # what ``ExpertLayer`` reads besides, fixed in this family
    routed_scaling: ClassVar[float] = 1.0
    scoring: ClassVar[str] = "sigmoid"
    expert_act: ClassVar[str] = "silu"

    def is_gqa(self, layer: int) -> bool:
        return layer % self.gqa_period == 0


def a_log_init(key, shape, dtype=jnp.float32):
    """Kimi Linear's ``A_log``: the log of a draw from U(1, 16), a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """Kimi Linear's ``dt_bias``: the inverse softplus of a step drawn
    log-uniform in [1e-3, 1e-1] (floored at 1e-4), a channel."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class ShortConv(nn.Module):
    """Causal depthwise convolution over positions, ``size`` taps a channel,
    no bias: ``y_t = sum_i w_i x_{t-size+1+i}``, zeros before the start."""

    size: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):  # [B, S, C]
        w = self.param("kernel", nn.initializers.lecun_normal(), (self.size, x.shape[-1]))
        x32, w = x.astype(jnp.float32), w.astype(jnp.float32)
        s = x.shape[1]
        padded = jnp.pad(x32, ((0, 0), (self.size - 1, 0), (0, 0)))
        y = sum(w[i] * padded[:, i : i + s] for i in range(self.size))
        return y.astype(self.dtype)


def l2_normalized(x, eps: float = 1e-6):
    """``x`` over its norm along the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


class KimiDeltaAttention(nn.Module):
    """The delta-rule mixer of the heads held.  Returns the output projection
    and the step's counts (``STEP_COUNTS``)."""

    sizes: KdaMoeSizes
    scan_fn: Callable  # (q, k, v [B,H,S,D], g [B,H,S,D], beta [B,H,S]) -> o [B,H,S,D] float32
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        z = self.sizes
        b, s, _ = h.shape
        nh, d = z.kda_heads, z.kda_head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)

        def branch(name):
            x = ShortConv(z.conv_size, self.dtype, name=f"{name}_conv")(dense(nh * d, name=f"{name}_proj")(h))
            return nn.silu(x).reshape(b, s, nh, d)

        q, k, v = branch("q"), branch("k"), branch("v")
        q = (l2_normalized(q) / math.sqrt(d)).astype(self.dtype)
        k = l2_normalized(k).astype(self.dtype)
        f = dense(nh * d, name="f_b_proj")(dense(d, name="f_a_proj")(h)).astype(jnp.float32)
        a_log = self.param("A_log", a_log_init, (nh,))
        dt_bias = self.param("dt_bias", dt_bias_init, (nh * d,))
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus((f + dt_bias).reshape(b, s, nh, d))
        beta = 2.0 * jax.nn.sigmoid(dense(nh, name="b_proj")(h).astype(jnp.float32))  # [B, S, H]

        heads_first = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        o = self.scan_fn(heads_first(q), heads_first(k), heads_first(v), heads_first(g), beta.transpose(0, 2, 1))
        o = RMSNorm(z.eps, jnp.float32, name="o_norm")(o.transpose(0, 2, 1, 3))
        gate = dense(nh * d, name="g_b_proj")(dense(d, name="g_a_proj")(h)).astype(jnp.float32)
        o = (o * jax.nn.sigmoid(gate.reshape(b, s, nh, d))).reshape(b, s, nh * d).astype(self.dtype)
        counts = jnp.stack([jnp.sum(beta > 1.0, dtype=jnp.int32), jnp.int32(beta.size)])
        return dense(z.d_model, name="o_proj")(o), counts


class GatedAttention(nn.Module):
    """Grouped-query attention without positions, its output gated by
    ``sigmoid(h W_gate)`` elementwise before ``W_o``."""

    sizes: KdaMoeSizes
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
        gate = dense(z.n_heads * z.head_dim, name="gate_proj")(h)
        heads_first = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        o = self.attn_fn(heads_first(q), heads_first(k), heads_first(v))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, z.n_heads * z.head_dim)
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
        return dense(z.d_model, name="o_proj")(o)


class KdaMoeBlock(nn.Module):
    sizes: KdaMoeSizes
    gqa: bool  # this layer's mixer: grouped-query attention, else the delta rule
    attn_fn: Callable
    scan_fn: Callable
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        z = self.sizes
        h = RMSNorm(z.eps, self.dtype, name="input_norm")(x)
        if self.gqa:
            o = GatedAttention(z, self.attn_fn, self.dtype, name="attn")(h)
        else:
            o, counts = KimiDeltaAttention(z, self.scan_fn, self.dtype, name="kda")(h)
            if not self.is_initializing():
                self.sow(ROUTING, "kda_counts", counts)
        x = x + o
        h = RMSNorm(z.eps, self.dtype, name="post_attn_norm")(x)
        return x + ExpertLayer(z, self.dtype, name="moe")(h)


class KdaMoeLM(nn.Module):
    """Decoder-only LM of ``KdaMoeBlock``s; every block is rematerialised in
    the backward pass and keeps its input and what its mixer's kernels wrote
    (``remat_kda_block``)."""

    BLOCK = "kda_moe"  # the block family's name, as ``transformer_trial`` takes it
    REMAT_BLOCKS = True  # every block is rematerialised

    vocab_size: int
    sizes: KdaMoeSizes = KdaMoeSizes()
    dtype: jnp.dtype = jnp.bfloat16
    attn_fn: Callable | None = None  # the grouped-query layers'
    scan_fn: Callable | None = None  # the delta-rule layers'

    @property
    def attn_widths(self) -> tuple[int, int]:
        """A head's key and value widths (the grouped-query layers)."""
        return self.sizes.head_dim, self.sizes.head_dim

    @property
    def attn_heads(self) -> int:
        """Query heads: the kernels' grids walk one at a time."""
        return self.sizes.n_heads

    @property
    def attn_kinds(self) -> list[tuple[int | None, str, int]]:
        """(window, positions, layers) of each kind of attention layer: the
        grouped-query layers (the delta-rule layers run no attention)."""
        z = self.sizes
        return [(None, "nope", sum(z.is_gqa(i) for i in range(z.n_layers)))]

    @nn.compact
    def __call__(self, tokens, multiply_head: bool = True):
        from katib_tpu.models.transformer import _dense_causal_attention, kda_scan_fn

        z = self.sizes
        attn = self.attn_fn or _dense_causal_attention
        scan = self.scan_fn or kda_scan_fn(False)
        x = nn.Embed(self.vocab_size, z.d_model, dtype=self.dtype, name="embed")(tokens)
        for i in range(z.n_layers):
            x = remat_kda_block(KdaMoeBlock)(z, z.is_gqa(i), attn, scan, self.dtype, name=f"layer_{i}")(x)
        x = RMSNorm(z.eps, self.dtype, name="norm")(x)
        return LMHead(self.vocab_size, use_bias=False, name="head")(x, multiply_head)

    training_loss = staticmethod(next_token_objective)
    reported_loss = staticmethod(lm_loss)

    @staticmethod
    def step_counters(counters) -> dict:
        """A step's counts as a span's attributes: the expert layers'
        (``routing_counters``), and ``STEP_COUNTS`` summed over the delta-rule
        layers with their ratio, ``kda_neg_eig_share``."""
        out = routing_counters(counters)
        per_layer = [
            np.asarray(leaf, np.int64)
            for path, leaf in jax.tree_util.tree_leaves_with_path(counters)
            if [p.key for p in path if hasattr(p, "key")][-1] == "kda_counts"
        ]
        neg, pairs = (int(v) for v in np.sum(per_layer, axis=0)) if per_layer else (0, 0)
        out.update(zip(STEP_COUNTS, (neg, pairs)), kda_neg_eig_share=neg / max(pairs, 1))
        return out
