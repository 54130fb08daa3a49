"""What the expert layer's ladder is held to, for ``test_mla_moe.py`` and
``test_gqa_moe.py``: the layer with a sorted buffer of all ``T x k``
assignments, whatever the routing (the arithmetic ``ExpertLayer`` had before
its buffer followed the rows held), and routings planted through the router's
logits so that a chosen number of assignments is held."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from katib_tpu.models.mla_moe import ROUTING, ExpertLayer, SwiGLU, routing_counters


class FullBufferExpertLayer(nn.Module):
    """``ExpertLayer``'s parameters and result; every gather, clear and
    grouped product ``T x k`` rows long; plain autodiff."""

    sizes: object
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, router_logits=None):
        z = self.sizes
        b, s, d = h.shape
        k = z.experts_per_token
        first, count = z.experts_held
        x = h.reshape(b * s, d)
        if router_logits is None:
            w_router = self.param("router", nn.initializers.lecun_normal(), (d, z.n_experts))
            logits = jnp.dot(x.astype(jnp.float32), w_router, precision=jax.lax.Precision.HIGHEST)
        else:
            logits = router_logits.reshape(b * s, z.n_experts)
        if z.scoring == "sigmoid":
            top_scores, top_experts = jax.lax.top_k(jax.nn.sigmoid(logits), k)
            weights = z.routed_scaling * top_scores / (top_scores.sum(-1, keepdims=True) + 1e-20)
        else:
            top_logits, top_experts = jax.lax.top_k(logits, k)
            weights = z.routed_scaling * jax.nn.softmax(top_logits, axis=-1)
        act = {"silu": nn.silu, "relu": nn.relu}[z.expert_act]

        local = top_experts - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count).reshape(-1)
        order = jnp.argsort(key, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.sum(key[:, None] == jnp.arange(count), axis=0, dtype=jnp.int32)

        init = nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal", batch_axis=(0,))
        w_gate = self.param("experts_gate", init, (count, d, z.expert_width))
        w_up = self.param("experts_up", init, (count, d, z.expert_width))
        w_down = self.param("experts_down", init, (count, z.expert_width, d))
        held_row = (jnp.arange(key.shape[0]) < group_sizes.sum())[:, None]

        def grouped(lhs, w):
            out = jax.lax.ragged_dot(
                jnp.where(held_row, lhs, 0).astype(self.dtype), w.astype(self.dtype), group_sizes,
                preferred_element_type=jnp.float32,
            )
            return jnp.where(held_row, out, 0.0)

        rows = x[order // k]
        out = grouped(act(grouped(rows, w_gate)) * grouped(rows, w_up), w_down)
        out = out.astype(self.dtype)[inverse].reshape(b * s, k, d)
        routed = jnp.einsum("tkd,tk->td", out, jnp.where(held, weights, 0.0).astype(self.dtype))
        routed = routed.reshape(b, s, d)
        if not z.n_shared_experts:
            return routed
        return SwiGLU(z.expert_width * z.n_shared_experts, self.dtype, name="shared")(h) + routed


# A planted routing: every token is of one class, and a class names the three
# experts its tokens choose.  The layers under test hold experts 4-7 of 32.
HELD = (4, 4)
CLASSES = {
    "three": (4, 5, 6),  # three assignments held
    "two": (5, 6, 8),
    "one": (7, 8, 9),
    "none": (8, 9, 10),
    "fourth": (4, 8, 9),  # one held, always expert 4
}
TOKENS = 384  # x 3 a token = 1152 assignments; the share expects 144: rungs of 384 and 1152
RUNGS = (384, 1152)
# name: (tokens of each class, the rows of the rung that holds them: the
# short one if it has a row to spare, else every assignment)
ROUTINGS = {
    "short-rung": ({"one": 200, "none": 184}, 384),
    "short-rung-but-for-one-row": ({"one": 383, "none": 1}, 384),
    "the-short-rung's-rows-exactly": ({"one": 384}, 1152),
    "a-little-over-the-short-rung": ({"two": 116, "one": 268}, 1152),
    "half-the-assignments": ({"two": 255, "one": 129}, 1152),
    "most-assignments": ({"three": 132, "two": 252}, 1152),
    "every-assignment-held": ({"three": 384}, 1152),
    "all-on-one-held-expert": ({"fourth": 383, "none": 1}, 384),
    "all-on-one-held-expert-over-the-short-rung": ({"fourth": 384}, 1152),
    "nothing-held": ({"none": 384}, 384),
}


def held_assignments(routing: str) -> int:
    held = range(HELD[0], HELD[0] + HELD[1])
    return sum(n * sum(e in held for e in CLASSES[c]) for c, n in ROUTINGS[routing][0].items())


def _classes(routing: str, rng) -> list[str]:
    """A class for each of the TOKENS tokens, shuffled."""
    classes = [c for c, n in ROUTINGS[routing][0].items() for _ in range(n)]
    assert len(classes) == TOKENS
    rng.shuffle(classes)
    return classes


def planted_logits(routing: str, n_experts: int = 32, seed: int = 0) -> np.ndarray:
    """[TOKENS, n_experts] float32: +3 on a token's class's experts, -3 on the
    others, and noise that cannot change the choice."""
    rng = np.random.default_rng(seed)
    logits = np.full((TOKENS, n_experts), -3.0, np.float32)
    for t, c in enumerate(_classes(routing, rng)):
        logits[t, list(CLASSES[c])] = 3.0
    return logits + rng.normal(scale=0.3, size=logits.shape).astype(np.float32)


def planted_stream(routing: str, d_model: int, n_experts: int = 32, seed: int = 0):
    """A stream ``h`` [2, TOKENS/2, d_model] and a router's matrix whose
    product is ``planted_logits`` but for the random features' small part:
    the first ``len(CLASSES)`` features say a token's class."""
    rng = np.random.default_rng(seed + 1)
    names = list(CLASSES)
    classes = _classes(routing, rng)
    h = 0.5 * rng.normal(size=(TOKENS, d_model)).astype(np.float32)
    h[:, : len(names)] = 0.0
    h[np.arange(TOKENS), [names.index(c) for c in classes]] = 1.0
    router = 0.05 * rng.normal(size=(d_model, n_experts)).astype(np.float32)
    router[: len(names)] = -3.0
    for i, c in enumerate(names):
        router[i, list(CLASSES[c])] = 3.0
    return jnp.asarray(h.reshape(2, TOKENS // 2, d_model)), jnp.asarray(router)


def layer_params(sizes, router=None, seed=21):
    """An expert layer's parameters in the program's tree (the router's
    matrix planted, or none: its logits are handed in)."""
    d, w, count = sizes.d_model, sizes.expert_width, sizes.experts_held[1]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])  # noqa: E731
    params = {
        "experts_gate": normal(ks[0], count, d, w),
        "experts_up": normal(ks[1], count, d, w),
        "experts_down": normal(ks[2], count, w, d),
    }
    if router is not None:
        params["router"] = router
    if sizes.n_shared_experts:
        shared = w * sizes.n_shared_experts
        params["shared"] = {
            "gate_proj": {"kernel": normal(ks[3], d, shared)},
            "up_proj": {"kernel": normal(ks[4], d, shared)},
            "down_proj": {"kernel": normal(ks[5], shared, d)},
        }
    return params


def assert_matches_full_buffer(sizes, params, h, logits, rows: int, held: int):
    """Result, gradients (stream, parameters, the logits where they are handed
    in) and counters of ``ExpertLayer`` against the layer whose buffer is
    ``T x k`` rows long whatever the routing."""
    weight = jax.random.normal(jax.random.PRNGKey(31), h.shape, jnp.float32)

    def run(layer):
        def loss(h, params, logits):
            out, sown = layer.apply({"params": params}, h, logits, mutable=[ROUTING])
            return jnp.sum(out * weight), (out, sown.get(ROUTING))

        return jax.value_and_grad(loss, argnums=(0, 1) if logits is None else (0, 1, 2), has_aux=True)(
            h, params, logits
        )

    (_, (got, sown)), got_grads = run(ExpertLayer(sizes, jnp.float32))
    (_, (want, _)), want_grads = run(FullBufferExpertLayer(sizes, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for g, w in zip(jax.tree_util.tree_leaves(got_grads), jax.tree_util.tree_leaves(want_grads)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)
    counters = routing_counters({"moe": sown})
    assert counters["moe_tokens_dropped"] == 0
    assert counters["moe_assignments_held"] == held == int(sown["expert_tokens"][0].sum())
    assert counters["moe_assignments_total"] == TOKENS * sizes.experts_per_token
    assert counters["moe_buffer_rows"] == rows
    return sown
