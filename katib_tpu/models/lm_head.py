"""The output head and the next-token cross entropy, as one operation with its
own backward.

Autodiff of ``log_softmax`` / ``take_along_axis`` / ``mean`` keeps the
log-probabilities for the backward, scatters a one-hot cotangent and reads it
again for its row sum: six touches of the float32 ``[B, S, V]`` array outside
the head's three products.  Here the forward is a float32 log-sum-exp (max,
then sum of exponentials) and the target's logit by a gather; the logits'
gradient is ``(exp(logits - lse) - [iota == target]) * g / N``, one
elementwise expression.  No log-probability array and no one-hot cotangent
exists, and the compiler is free to put the max into the forward product's
fusion and the gradient's expression into the operands of the two backward
products (on a TPU v5e it does: PERF.md, section 5).

All ``S`` positions are computed, with weight 0 on the last (it has no next
token): slicing to ``S - 1`` and padding the gradient back are copies.

Two entries: ``next_token_loss`` on dense logits, and ``head_loss`` on what
the head would multiply (``HeadInputs``), with the product inside, a chunk of
whole sequences at a time; ``lm_loss`` takes either.  A model whose loss
weighs every token by itself (several exits, each token's share of each
learned: ``models/looped.py``) calls ``weighted_token_losses``: the same two
paths with a weight a token, which give the tokens' losses back beside the
weighted sum, so that the weights get their gradient too.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp


def _next_tokens(tokens):
    """Each position's next token (``tokens`` ``[..., S]`` shifted left; the
    last position gets token 0) and which positions have one: all but the
    last."""
    s = tokens.shape[-1]
    shifted = [tokens[..., 1:], jnp.zeros((*tokens.shape[:-1], 1), tokens.dtype)]
    return jnp.concatenate(shifted, axis=-1), jnp.arange(s) < s - 1


def _targets(tokens):
    """Each position's next token and each position's weight in the mean:
    ``1 / (B (S - 1))``, 0 on the last."""
    b, s = tokens.shape
    targets, counted = _next_tokens(tokens)
    return targets, counted.astype(jnp.float32) / (b * (s - 1))


def _lse_and_picked(logits, targets):
    """Float32 log-sum-exp over the vocabulary and the target's logit."""
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse, picked


def _softmax_minus_onehot(logits, lse, targets, scale):
    """``(softmax(logits) - onehot(targets)) * scale`` with ``scale`` a row:
    one elementwise expression (an ``iota`` compare fuses, a scatter does not)."""
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    hit = jax.lax.broadcasted_iota(targets.dtype, logits.shape, logits.ndim - 1) == targets[..., None]
    return (p - hit.astype(jnp.float32)) * jnp.broadcast_to(scale, lse.shape)[..., None]


# ---------------------------------------------------------------------------
# on dense logits
# ---------------------------------------------------------------------------


@jax.custom_vjp
def next_token_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token cross entropy of ``[B, S, V]`` logits and ``[B, S]``
    tokens, float32: ``-mean(log_softmax(logits[:, :-1])[tokens[:, 1:]])``."""
    return _next_token_loss_fwd(logits, tokens)[0]


def _next_token_loss_fwd(logits, tokens):
    targets, weight = _targets(tokens)
    lse, picked = _lse_and_picked(logits, targets)
    return jnp.sum((lse - picked) * weight), (logits, lse, targets, weight)


def _next_token_loss_bwd(res, g):
    logits, lse, targets, weight = res
    return _softmax_minus_onehot(logits, lse, targets, weight * g).astype(logits.dtype), None


next_token_loss.defvjp(_next_token_loss_fwd, _next_token_loss_bwd)


# ---------------------------------------------------------------------------
# the head's product inside the loss, in chunks of whole sequences
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class HeadInputs:
    """What the output head multiplies: the last hidden states ``[B, S, D]``,
    the kernel ``[D, V]`` and the bias ``[V]`` or ``None``.  Its length is the
    batch and it slices along it, as dense logits do."""

    hidden: jnp.ndarray
    kernel: jnp.ndarray
    bias: jnp.ndarray | None

    def __len__(self) -> int:
        return len(self.hidden)

    def __getitem__(self, rows) -> "HeadInputs":
        return self.replace(hidden=self.hidden[rows])

    def logits(self) -> jnp.ndarray:
        """The product multiplied out in float32, as ``nn.Dense(V, dtype=
        float32)`` computes it."""
        out = jnp.dot(self.hidden.astype(jnp.float32), self.kernel)
        return out if self.bias is None else out + self.bias


class LMHead(nn.Module):
    """The output head, float32: the parameters of ``nn.Dense(features, dtype=
    float32)`` under the same names and initialisers, multiplied out into
    logits or handed to the loss as they are (``HeadInputs``)."""

    features: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, x, multiply: bool = True):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (x.shape[-1], self.features), jnp.float32
        )
        bias = None
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros_init(), (self.features,), jnp.float32)
        head = HeadInputs(x, kernel, bias)
        return head.logits() if multiply else head


#: a chunk's float32 logits stay under this many bytes where more than one
#: sequence would pass it.  Read on a TPU v5e at [8, 1024, 50257] (PERF.md,
#: section 6, PR 34): two chunks of 0.8 GB step faster than one of 1.6 GB and
#: than four of 0.4 GB.
CHUNK_BYTES = 1 << 30


def chunk_rows(batch: int, seq_len: int, vocab: int) -> int:
    """Sequences a chunk: the largest divisor of ``batch`` whose float32
    logits fit ``CHUNK_BYTES``, and 1 where a single sequence passes it."""
    fit = max(1, CHUNK_BYTES // (seq_len * vocab * 4))
    return max(r for r in range(1, batch + 1) if batch % r == 0 and r <= fit)


def _head_loss_chunks(head: HeadInputs, tokens, rows: int, grads: bool, weights=None):
    """``(loss, tokens' losses, gradients)``, ``rows`` sequences at a time: a
    chunk's logits, log-sum-exp and loss and, with ``grads``, its logits'
    gradient, and from that ``dx`` for the chunk and the chunk's addend to
    ``dW`` and ``db``: the loss's gradients with respect to the hidden states,
    kernel and bias, as a ``HeadInputs`` (``None`` without ``grads``).  No
    product is computed twice and no ``[B, S, V]`` array is alive at once.
    The loss is the mean over the positions that have a next token; with
    ``weights`` ``[B, S]`` it is the tokens' losses weighted by them, and the
    tokens' losses ``[B, S]`` (0 on a sequence's last position) come back too
    (``None`` without ``weights``)."""
    kernel, bias = head.kernel, head.bias

    def chunks(a):
        return a.reshape(a.shape[0] // rows, rows, *a.shape[1:])

    if weights is None:
        targets, weight = _targets(tokens)
    else:
        targets, counted = _next_tokens(tokens)

    def one(carry, chunk):
        x, tgt, *own = chunk
        logits = HeadInputs(x, kernel, bias).logits()
        lse, picked = _lse_and_picked(logits, tgt)
        scale = own[0] if own else weight
        loss = carry[0] + jnp.sum((lse - picked) * scale)
        ys = {"losses": (lse - picked) * counted} if own else {}
        if not grads:
            return (loss,), ys
        d = _softmax_minus_onehot(logits, lse, tgt, scale)
        ys["dx"] = jnp.dot(d, kernel.T)
        x = x.astype(jnp.float32)
        if bias is not None:
            # db is the gradient's sum over rows: a column of ones beside the
            # hidden states takes it from the dW product, which reads the
            # gradient anyway; summed apart it is one more pass over the chunk
            x = jnp.concatenate([x, jnp.ones_like(x[..., :1])], axis=-1)
        return (loss, carry[1] + jnp.einsum("rsd,rsv->dv", x, d)), ys

    init = (jnp.zeros((), jnp.float32),)
    if grads:
        init += (jnp.zeros((kernel.shape[0] + (bias is not None), kernel.shape[1]), jnp.float32),)
    xs = (chunks(head.hidden), chunks(targets))
    if weights is not None:
        xs += (chunks(weights * counted),)
    if len(xs[0]) == 1:
        carry, ys = one(init, jax.tree_util.tree_map(lambda a: a[0], xs))
    else:
        carry, ys = jax.lax.scan(one, init, xs)
    losses = ys["losses"].reshape(tokens.shape) if "losses" in ys else None
    if not grads:
        return carry[0], losses, None
    loss, dw = carry
    dw, db = (dw, None) if bias is None else (dw[:-1], dw[-1])
    # float32 until the backward has scaled them; the hidden states' dtype rides along
    dx = ys["dx"].reshape(head.hidden.shape)
    return loss, losses, (HeadInputs(dx, dw, db), jnp.zeros((), head.hidden.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def head_loss(head: HeadInputs, tokens: jnp.ndarray, rows: int | None = None) -> jnp.ndarray:
    """``next_token_loss(head.logits(), tokens)`` without the dense logits:
    the head's product runs inside, ``rows`` sequences at a time (a divisor
    of the batch; from the shapes where ``None``), and the forward pass of a
    differentiated call computes the gradients too: its backward only scales
    them."""
    return _head_loss_chunks(head, tokens, _rows(head, rows), grads=False)[0]


def _rows(head, rows):
    b, s, _ = head.hidden.shape
    rows = chunk_rows(b, s, head.kernel.shape[1]) if rows is None else rows
    if b % rows:
        raise ValueError(f"head_loss: {rows} rows a chunk do not divide a batch of {b}")
    return rows


def _head_loss_fwd(head, tokens, rows):
    loss, _, grads = _head_loss_chunks(head, tokens, _rows(head, rows), grads=True)
    return loss, grads


def _scaled(grads: HeadInputs, like_hidden, g) -> HeadInputs:
    grads = jax.tree_util.tree_map(lambda a: a * g, grads)
    return grads.replace(hidden=grads.hidden.astype(like_hidden.dtype))


def _head_loss_bwd(_rows_arg, res, g):
    return _scaled(*res, g), None


head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def lm_loss(logits: jnp.ndarray | HeadInputs, tokens: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross entropy over [B, S, V] logits / [B, S] tokens.  In
    place of dense logits it takes what the head would multiply (a model
    called with ``multiply_head=False``); the product then runs inside the
    loss, in row chunks."""
    if isinstance(logits, HeadInputs):
        return head_loss(logits, tokens)
    return next_token_loss(logits, tokens)


def next_token_objective(outputs: jnp.ndarray | HeadInputs, tokens: jnp.ndarray):
    """What a model whose training loss is its reported loss answers
    ``_build_programs`` with: ``lm_loss``, and nothing for a report to read."""
    return lm_loss(outputs, tokens), {}


# ---------------------------------------------------------------------------
# a weight a token: both paths again, and the tokens' losses beside the sum
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _weighted_dense_losses(logits, tokens, weights):
    return _weighted_dense_losses_fwd(logits, tokens, weights)[0]


def _weighted_dense_losses_fwd(logits, tokens, weights):
    targets, counted = _next_tokens(tokens)
    lse, picked = _lse_and_picked(logits, targets)
    losses = (lse - picked) * counted
    return (jnp.sum(losses * weights), losses), (logits, lse, targets, weights * counted, losses)


def _weighted_dense_losses_bwd(res, cts):
    logits, lse, targets, weights, losses = res
    g = cts[0]  # the tokens' losses are read, not differentiated through
    d = _softmax_minus_onehot(logits, lse, targets, weights * g).astype(logits.dtype)
    return d, None, losses * g


_weighted_dense_losses.defvjp(_weighted_dense_losses_fwd, _weighted_dense_losses_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _weighted_head_losses(head: HeadInputs, tokens, weights, rows: int | None = None):
    return _head_loss_chunks(head, tokens, _rows(head, rows), grads=False, weights=weights)[:2]


def _weighted_head_losses_fwd(head, tokens, weights, rows):
    total, losses, grads = _head_loss_chunks(head, tokens, _rows(head, rows), grads=True, weights=weights)
    return (total, losses), (*grads, losses)


def _weighted_head_losses_bwd(_rows_arg, res, cts):
    grads, like_hidden, losses = res
    g = cts[0]  # the tokens' losses are read, not differentiated through
    return _scaled(grads, like_hidden, g), None, losses * g


_weighted_head_losses.defvjp(_weighted_head_losses_fwd, _weighted_head_losses_bwd)


def weighted_token_losses(
    logits: jnp.ndarray | HeadInputs, tokens: jnp.ndarray, weights: jnp.ndarray, rows: int | None = None
):
    """``(sum(weights * losses), losses)`` for ``[..., S, V]`` logits and
    ``[..., S]`` tokens and weights: ``losses`` is every position's next-token
    cross entropy, float32, 0 on the last position of a sequence (it has no
    next token, whatever its weight).  One operation with its own backward, as
    ``lm_loss`` is: the sum carries the gradients of the logits AND of the
    weights (a weight's gradient is its token's loss); ``losses`` is there to
    be read (a report, the weights' own arithmetic) and gives no gradient.

    In place of dense logits it takes what the head would multiply
    (``HeadInputs`` with hidden states ``[..., S, D]``): the leading axes are
    then rows of one batch, and the head's product runs inside, ``rows``
    sequences at a time (from the shapes where ``None``), with no array as
    wide as the vocabulary alive for more than a chunk."""
    if not isinstance(logits, HeadInputs):
        return _weighted_dense_losses(logits, tokens, weights)
    flat = lambda a: a.reshape(-1, *a.shape[tokens.ndim - 1 :])  # noqa: E731
    total, losses = _weighted_head_losses(
        logits.replace(hidden=flat(logits.hidden)), flat(tokens), flat(weights), rows
    )
    return total, losses.reshape(tokens.shape)
