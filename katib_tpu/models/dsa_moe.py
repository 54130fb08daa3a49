"""Grouped-query attention over a learned selection of keys (DeepSeek Sparse
Attention), beside sparse experts: the fifth block family of
``transformer_trial`` (``block: dsa_moe``).

The layer is ``Keye-VL-2.0-30B-A3B``'s language model as its ``config.json``
gives it (``benchmark/configs/keye-vl-2.0-30b-a3b-ep8.json`` has the published
keys).  For residual stream ``x``, with ``RN`` an RMSNorm with weights only:

- ``h = RN_1(x)``; queries ``q_i = rope(RN_q(h W_q^i))`` for ``n_heads`` heads,
  keys ``k_g = rope(RN_k(h W_k^g))`` and values ``v_g = h W_v^g`` for
  ``n_kv_heads`` (QK-norm over each head; rotary over the whole head, the
  halves paired; query head ``i`` reads key-value head ``i // group``);
- the indexer reads ``stop_gradient(h)``: ``index_heads`` queries ``qI_j = h
  W_qI^j`` of ``index_head_dim``, ONE key ``kI = LN(h W_kI)`` shared by them,
  weights ``w_j = h W_w^j / sqrt(index_heads)`` (float32), rotary on the first
  half of each indexer head; scores ``I[t, s] = sum_j w_j relu(qI_j[t] .
  kI[s]) / sqrt(index_head_dim)`` in float32;
- each query attends to the ``index_topk`` visible keys with the largest
  ``I`` (every visible key where there are fewer): ``ops/sparse_attention.py``,
  whose selection is exact; ``x' = x + [o_1..o_H] W_o``;
- ``x' + experts(RN_2(x'))``: ``models/mla_moe.py:ExpertLayer`` with its own
  router on ``RN_2``'s output (the Qwen-MoE convention), the ``experts_per_token``
  largest of all ``n_experts`` logits, a softmax over the chosen, SiLU-gated
  experts of ``expert_width``, no shared expert; told which experts it holds
  (``experts_held``);
- after the last layer an RMSNorm and an untied bias-free head.

**The loss belongs to the model**: ``training_loss`` is the next-token cross
entropy plus every layer's indexer loss ``L_I`` (the KL of the indexer's
softmax over the selection from the attention's head-mean probabilities
there); ``reported_loss`` is the cross entropy.  The indexer's input is
detached and the selection discrete, so the cross entropy reaches no indexer
weight and ``L_I`` reaches nothing else.

Departures from the published model: no vision tower (text only: the three
mRoPE sections carry one position id, so mRoPE is rotary over the whole head);
no auxiliary loss; flax's default initialisers; every block rematerialised (it
keeps its input, and where the kernels run the attention's output and
log-sum-exp, the selection and the indexer's gradients).  Activations and
products in bfloat16; parameters, the indexer's scores, the selection, the
router, logits and losses in float32.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, ClassVar, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from katib_tpu.models.lm_head import HeadInputs, LMHead, lm_loss
from katib_tpu.models.mla_moe import ROUTING, ExpertLayer, RMSNorm, rotary, routing_counters
from katib_tpu.ops.sparse_attention import remat_sparse_block

#: the counts a layer's selection sows into ``ROUTING``, in this order
SELECTION_COUNTS = ("dsa_selected_pairs", "dsa_visible_pairs", "dsa_tiles_visited", "dsa_tiles_causal")


@dataclasses.dataclass(frozen=True)
class DsaMoeSizes:
    """The block's sizes, under the names ``transformer_trial`` takes them by.
    Hashable, so that two models of equal sizes share their programs."""

    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    n_layers: int = 4
    index_heads: int = 4
    index_head_dim: int = 16
    index_topk: int = 64
    expert_width: int = 64
    n_experts: int = 16
    experts_per_token: int = 2
    experts_held: tuple[int, int] = (0, 16)  # (first index, count)
    rope_theta: float = 1e7
    eps: float = 1e-6
    # what ``ExpertLayer`` reads besides, fixed in this family
    n_shared_experts: ClassVar[int] = 0
    routed_scaling: ClassVar[float] = 1.0
    scoring: ClassVar[str] = "softmax"
    expert_act: ClassVar[str] = "silu"


class SparseAttention(nn.Module):
    """Grouped-query attention with QK-norm, and the indexer that chooses its
    keys.  Returns the output projection, the layer's ``L_I`` and its counts."""

    sizes: DsaMoeSizes
    attn_fn: Callable  # (q [B,H,S,D], k, v [B,G,S,D], qI, kI, w) -> (o, L_I, counts)
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        z = self.sizes
        b, s, _ = h.shape
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        q = dense(z.n_heads * z.head_dim, name="q_proj")(h).reshape(b, s, z.n_heads, z.head_dim)
        k = dense(z.n_kv_heads * z.head_dim, name="k_proj")(h).reshape(b, s, z.n_kv_heads, z.head_dim)
        v = dense(z.n_kv_heads * z.head_dim, name="v_proj")(h).reshape(b, s, z.n_kv_heads, z.head_dim)
        q = rotary(RMSNorm(z.eps, self.dtype, name="q_norm")(q), z.rope_theta, interleaved=False)
        k = rotary(RMSNorm(z.eps, self.dtype, name="k_norm")(k), z.rope_theta, interleaved=False)

        # the indexer, on the detached input
        hs = jax.lax.stop_gradient(h)
        n_i, d_i = z.index_heads, z.index_head_dim
        qi = dense(n_i * d_i, name="index_q_proj")(hs).reshape(b, s, n_i, d_i)
        ki = nn.LayerNorm(epsilon=z.eps, dtype=self.dtype, name="index_k_norm")(
            dense(d_i, name="index_k_proj")(hs)
        )
        w = nn.Dense(
            n_i, use_bias=False, dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
            name="index_weights_proj",
        )(hs) / np.sqrt(n_i)

        def half_rotary(t):  # [B, S, H, d_I]: rotary on the first half
            return jnp.concatenate([rotary(t[..., : d_i // 2], z.rope_theta, interleaved=False), t[..., d_i // 2 :]], -1)

        qi, ki = half_rotary(qi), half_rotary(ki[:, :, None, :])[:, :, 0, :]
        heads_first = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        o, loss, counts = self.attn_fn(heads_first(q), heads_first(k), heads_first(v), qi, ki, w)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, z.n_heads * z.head_dim).astype(self.dtype)
        return dense(z.d_model, name="o_proj")(o), loss, counts


class DsaMoeBlock(nn.Module):
    sizes: DsaMoeSizes
    attn_fn: Callable
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        z = self.sizes
        h = RMSNorm(z.eps, self.dtype, name="input_norm")(x)
        o, loss, counts = SparseAttention(z, self.attn_fn, self.dtype, name="attn")(h)
        if not self.is_initializing():
            self.sow(ROUTING, "dsa_counts", counts)
        x = x + o
        h = RMSNorm(z.eps, self.dtype, name="post_attn_norm")(x)
        return x + ExpertLayer(z, self.dtype, name="moe")(h), loss


class SparseOutputs(NamedTuple):
    """What ``DsaMoeLM`` answers: the head's logits (or its inputs) and every
    layer's indexer loss ``[n_layers]``."""

    head: jnp.ndarray | HeadInputs
    indexer_losses: jnp.ndarray


class DsaMoeLM(nn.Module):
    """Decoder-only LM of ``DsaMoeBlock``s; every block is rematerialised in
    the backward pass and keeps its input and what the sparse attention's
    backward reads (``remat_sparse_block``)."""

    BLOCK = "dsa_moe"  # the block family's name, as ``transformer_trial`` takes it
    REMAT_BLOCKS = True  # every block is rematerialised
    REPORTED_COUNTERS = ("indexer_kl",)  # reported beside the losses: the store's series

    vocab_size: int
    sizes: DsaMoeSizes = DsaMoeSizes()
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
        return [(None, f"rope top{self.sizes.index_topk}", self.sizes.n_layers)]

    @nn.compact
    def __call__(self, tokens, multiply_head: bool = True) -> SparseOutputs:
        from katib_tpu.models.transformer import sparse_attention_fn

        z = self.sizes
        attn = self.attn_fn or sparse_attention_fn(False, z.index_topk)
        x = nn.Embed(self.vocab_size, z.d_model, dtype=self.dtype, name="embed")(tokens)
        losses = []
        for i in range(z.n_layers):
            x, loss = remat_sparse_block(DsaMoeBlock)(z, attn, self.dtype, name=f"layer_{i}")(x)
            losses.append(loss)
        x = RMSNorm(z.eps, self.dtype, name="norm")(x)
        head = LMHead(self.vocab_size, use_bias=False, name="head")(x, multiply_head)
        return SparseOutputs(head, jnp.stack(losses))

    def training_loss(self, outputs: SparseOutputs, tokens):
        """The cross entropy plus every layer's ``L_I``, and the mean ``L_I``
        over the layers for a report (``indexer_kl``)."""
        kl = outputs.indexer_losses
        return lm_loss(outputs.head, tokens) + jnp.sum(kl), {"indexer_kl": jnp.mean(kl)}

    def reported_loss(self, outputs: SparseOutputs, tokens):
        """The next-token cross entropy."""
        return lm_loss(outputs.head, tokens)

    @staticmethod
    def step_counters(counters) -> dict:
        """A step's counts as a span's attributes: the expert layers'
        (``routing_counters``), each selection count of ``SELECTION_COUNTS``
        summed over the layers, and ``indexer_kl``."""
        out = routing_counters(counters)
        per_layer = [
            np.asarray(leaf, np.int64)
            for path, leaf in jax.tree_util.tree_leaves_with_path(counters)
            if [p.key for p in path if hasattr(p, "key")][-1] == "dsa_counts"
        ]
        out.update(zip(SELECTION_COUNTS, (int(v) for v in np.sum(per_layer, axis=0))))
        out["indexer_kl"] = float(counters["indexer_kl"])
        return out
