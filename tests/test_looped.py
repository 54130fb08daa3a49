"""The fourth block family of ``transformer_trial`` (``block: looped``): a
stack of layers run several times over the same weights, an exit after every
pass, a learned exit gate and the expected-exit loss; and what it asked of the
rest: a fused loss with a weight a token, the loss on the model's side of
``_build_programs``, passes in ``attention_plan``.

Against the benchmark's plain reference (``benchmark/families/looped.py``,
loaded by path: the repo's one copy) and against the formulas written out, at
tiny sizes on the CPU.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.models import transformer
from katib_tpu.models.lm_head import HeadInputs, lm_loss, weighted_token_losses
from katib_tpu.models.looped import Exits, LoopedLM, LoopedPass, LoopedSizes, exit_distribution
from katib_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "ouro-l6-lr4low-steps12"


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def family():
    return _load("families", "looped")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmark", "configs", "ouro-2.6b-l6.json")) as f:
        return json.load(f)


#: a configuration file's keys at a test size: two layers run three times
TINY = {
    "hidden_size": 32, "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 48, "num_hidden_layers": 2, "total_ut_steps": 3, "vocab_size": 64,
    "seq_len": 16, "batch_size": 2, "n_seq": 20, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
    "exit_beta": 0.1,
}
TRAFFIC = {
    "algorithm": "random", "parallelTrialCount": 1, "maxTrialCount": 1, "steps": 12,
    "parameters": [{"name": "lr", "parameterType": "discrete", "feasibleSpace": {"list": ["0.001"]}}],
}


def _trial_params(family, sizes):
    """A trial's parameters as the family's experiment document pins them."""
    doc = family.experiment_doc("x", sizes, TRAFFIC, 3)
    out = {}
    for p in doc["spec"]["parameters"]:
        space = p["feasibleSpace"]
        out[p["name"]] = space["list"][0] if "list" in space else space["min"]
    return out


def _model(family, sizes=TINY):
    """The program's model for the family's sizes, in float32."""
    model = transformer._looped_model(_trial_params(family, sizes), sizes["vocab_size"], None)
    return model.clone(dtype=jnp.float32)


def _tokens(sizes=TINY, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, sizes["vocab_size"], (sizes["batch_size"], sizes["seq_len"])), jnp.int32)


def _as_reference(params, family):
    """The program's parameter tree in the reference's layout."""
    return family.from_program_tree(params["params"])


def _seeded(model, tokens, seed=7):
    """Seeded weights with every norm's scale and the gate's bias away from
    their initial values, so that each has a gradient worth comparing."""
    params = model.init(jax.random.PRNGKey(seed), tokens)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 200))

    def shake(path, leaf):
        name = path[-1].key
        if name in ("scale", "bias"):
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(shake, params)


def _program_losses(model, params, tokens, multiply_head):
    exits = model.apply(params, tokens, multiply_head=multiply_head)
    objective, read = model.training_loss(exits, tokens)
    return objective, model.reported_loss(exits, tokens), read


# ---------------------------------------------------------------------------
# against the plain reference
# ---------------------------------------------------------------------------

#: float32 on both sides at ``highest``: what is left is the order of sums (a
#: fused log-sum-exp against log_softmax, attention in one block against blocks
#: of queries, the sum over passes inside a scan's backward against autodiff's).
#: Read: under 2e-6 relative on losses and logits of about 4.  bfloat16 weights
#: move the objective by 1e-3 and a dropped pass by 4e-2 (the tests below).
LOSS_RTOL = 2e-5
#: gradients: the same sums, over more terms, and entries near zero beside
#: entries of 1e-1: relative to the largest entry of each parameter's gradient
GRAD_RTOL = 2e-4


class TestAgainstReference:
    def test_the_model_is_the_one_the_config_describes(self, family, config):
        sizes = {k: config[k] for k in family.SIZE_KEYS}
        model = transformer._looped_model(_trial_params(family, sizes), config["vocab_size"], None)
        assert model.sizes == LoopedSizes(
            d_model=2048, n_heads=16, head_dim=128, mlp_width=5632, n_layers=6, ut_steps=4,
            rope_theta=1e6, eps=1e-6, exit_beta=0.1,
        )
        assert (model.vocab_size, model.BLOCK, model.passes) == (49152, "looped", 4)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
        assert count == 509_661_185  # the stack's weights exist once, whatever ut_steps is
        assert f"{count:,}" in config["parameters"]

    def test_initial_weights_are_the_references(self, family):
        tokens = _tokens()
        got = _as_reference(_model(family).init(jax.random.PRNGKey(0), tokens), family)
        want = family.init_params(TINY)
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want), strict=True):
            np.testing.assert_allclose(g, w, rtol=0, atol=1e-7)

    @pytest.mark.parametrize("multiply_head", [True, False], ids=["dense", "fused"])
    def test_logits_gate_and_losses_of_every_exit_follow_the_reference(self, family, multiply_head):
        model, tokens = _model(family), _tokens()
        params = _seeded(model, tokens)
        ref = _as_reference(params, family)
        shape = family.shape_of(TINY)
        with jax.default_matmul_precision("highest"):
            exits = model.apply(params, tokens)
            objective, reported, read = _program_losses(model, params, tokens, multiply_head)
        states, f = family._exit_states(ref, tokens, shape, "f32", None)
        assert len(states) == 3
        lam = []
        for t, z in enumerate(states):
            np.testing.assert_allclose(exits.logits[t], f["mm"]("rsd,dv->rsv", z, ref["head"]), rtol=LOSS_RTOL, atol=2e-5)
            lam.append(jax.nn.sigmoid(z @ ref["gate_w"] + ref["gate_b"]))
            np.testing.assert_allclose(exits.gate[t], lam[-1], rtol=LOSS_RTOL)
        np.testing.assert_allclose(objective, family._losses(ref, tokens, shape, "f32", None, True), rtol=LOSS_RTOL)
        np.testing.assert_allclose(reported, family._losses(ref, tokens, shape, "f32", None, False), rtol=LOSS_RTOL)
        # the counters: every exit's mean loss and share, written out
        counted = np.arange(16) < 15
        nll = -jnp.take_along_axis(
            jax.nn.log_softmax(exits.logits[:, :, :-1]), tokens[None, :, 1:, None], axis=-1
        )[..., 0]
        np.testing.assert_allclose(read["exit_loss"], nll.mean(axis=(1, 2)), rtol=LOSS_RTOL)
        q = np.stack([lam[0], (1 - lam[0]) * lam[1], (1 - lam[0]) * (1 - lam[1])])
        np.testing.assert_allclose(read["exit_share"], q[:, :, counted].mean(axis=(1, 2)), rtol=LOSS_RTOL)
        np.testing.assert_allclose(float(np.sum(read["exit_share"])), 1.0, rtol=1e-6)
        np.testing.assert_allclose(
            read["exit_entropy"], -(q * np.log(q))[:, :, counted].sum(axis=0).mean(), rtol=LOSS_RTOL
        )

    @pytest.mark.parametrize("multiply_head", [True, False], ids=["dense", "fused"])
    def test_gradients_of_every_parameter_follow_the_reference(self, family, multiply_head):
        model, tokens = _model(family), _tokens()
        params = _seeded(model, tokens)
        shape = family.shape_of(TINY)
        with jax.default_matmul_precision("highest"):
            got = jax.grad(lambda p: _program_losses(model, p, tokens, multiply_head)[0])(params)
        want = jax.grad(lambda r: family._losses(r, tokens, shape, "f32", None, True))(_as_reference(params, family))
        got = _as_reference(got, family)
        flat_got, tree = jax.tree_util.tree_flatten_with_path(got)
        flat_want = jax.tree_util.tree_leaves(want)
        assert len(flat_got) == len(flat_want) == 5 + 11
        for (path, g), w in zip(flat_got, flat_want, strict=True):
            scale = float(jnp.max(jnp.abs(w)))
            assert scale > 0, path  # every parameter takes part, the gate's among them
            np.testing.assert_allclose(g, w, rtol=0, atol=GRAD_RTOL * scale, err_msg=str(path))

    @pytest.mark.parametrize("what", ["bfloat16-weights", "one-pass", "even-exits"])
    def test_the_tolerance_sees_a_lower_precision_and_a_dropped_pass(self, family, what):
        """What the comparison above must not let through moves the objective
        by many times ``LOSS_RTOL``."""
        model, tokens = _model(family), _tokens()
        params = _seeded(model, tokens)
        shape = family.shape_of(TINY)
        want = float(family._losses(_as_reference(params, family), tokens, shape, "f32", None, True))
        if what == "bfloat16-weights":
            rounded = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
            got = float(_program_losses(model, rounded, tokens, False)[0])
        else:
            fault = what.replace("-", "_")
            got = float(family._losses(_as_reference(params, family), tokens, shape, "f32", fault, True))
        assert abs(got - want) / want > 10 * LOSS_RTOL, (got, want)

    def test_a_shared_weights_gradient_is_the_sum_over_the_passes(self, family):
        """An untied copy of ``T x L`` layers given the same values: the ``T``
        copies' gradients add up to the shared stack's."""
        model, tokens = _model(family), _tokens()
        params = _seeded(model, tokens)["params"]
        z, attn = model.sizes, transformer._dense_causal_attention

        def untied(stacks):
            x = params["embed"]["embedding"][tokens]
            exits = []
            for stack in stacks:  # one tree of the stack's weights a pass
                x, _ = LoopedPass(z, attn, jnp.float32).apply({"params": stack}, x, None)
                exits.append(x)
            exits = jnp.stack(exits)
            lam = jax.nn.sigmoid(exits @ params["exit_gate"]["kernel"][:, 0] + params["exit_gate"]["bias"][0])
            return model.training_loss(Exits(exits @ params["head"]["kernel"], lam), tokens)[0]

        with jax.default_matmul_precision("highest"):
            shared = jax.grad(lambda s: model.training_loss(model.apply({"params": {**params, "stack": s}}, tokens), tokens)[0])(params["stack"])
            copies = jax.grad(untied)([params["stack"]] * z.ut_steps)
        summed = jax.tree_util.tree_map(lambda *g: sum(g), *copies)
        for a, b, first in zip(*map(jax.tree_util.tree_leaves, (shared, summed, copies[0])), strict=True):
            np.testing.assert_allclose(a, b, rtol=0, atol=GRAD_RTOL * float(jnp.max(jnp.abs(b))))
            assert float(jnp.max(jnp.abs(first - b))) > 0.05 * float(jnp.max(jnp.abs(b)))  # one pass is not the sum

    def test_one_pass_is_the_plain_stacks_cross_entropy(self, family):
        """``ut_steps`` 1: the one exit takes every token whatever the gate
        says, the entropy is 0, and the objective is the reported loss: the
        next-token cross entropy of the stack run once."""
        sizes = {**TINY, "total_ut_steps": 1}
        model, tokens = _model(family, sizes), _tokens()
        params = _seeded(model, tokens)
        params["params"]["exit_gate"]["kernel"] = jnp.zeros_like(params["params"]["exit_gate"]["kernel"])
        with jax.default_matmul_precision("highest"):
            exits = model.apply(params, tokens)
            objective, reported, read = _program_losses(model, params, tokens, False)
        plain = -jnp.take_along_axis(
            jax.nn.log_softmax(exits.logits[0, :, :-1]), tokens[:, 1:, None], axis=-1
        ).mean()
        np.testing.assert_allclose([objective, reported], [plain, plain], rtol=LOSS_RTOL)
        np.testing.assert_allclose(read["exit_share"], [1.0], rtol=1e-6)
        assert float(read["exit_entropy"]) == 0.0
        ref = family._losses(_as_reference(params, family), tokens, family.shape_of(sizes), "f32", None, True)
        np.testing.assert_allclose(objective, ref, rtol=LOSS_RTOL)
        np.testing.assert_allclose(
            ref, family._losses(_as_reference(params, family), tokens, family.shape_of(TINY), "f32", "one_pass", True)
        )

    def test_exit_distribution_sums_to_one(self):
        lam = jax.random.uniform(jax.random.PRNGKey(0), (4, 3, 5))
        q = exit_distribution(lam)
        np.testing.assert_allclose(q.sum(axis=0), np.ones((3, 5)), rtol=1e-6)
        np.testing.assert_allclose(q[0], lam[0])
        np.testing.assert_allclose(q[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-6)
        np.testing.assert_allclose(exit_distribution(lam[:1]), np.ones((1, 3, 5)))


# ---------------------------------------------------------------------------
# the passes are a loop in the program
# ---------------------------------------------------------------------------


def _kernel_attention(q, k, v):
    from katib_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True, interpret=False)


_kernel_attention.kernel, _kernel_attention.window = True, None


class TestProgramLoop:
    @staticmethod
    def _lowered_step(ut_steps: int) -> str:
        """The step lowered for a TPU (no chip and no compile: the text of
        what the compiler would be handed), the attention kernel in it."""
        sizes = LoopedSizes(d_model=128, n_heads=2, head_dim=128, mlp_width=256, n_layers=3, ut_steps=ut_steps)
        model = LoopedLM(vocab_size=512, sizes=sizes, attn_fn=_kernel_attention)
        programs = transformer._build_programs(model, 1.0, transformer.WEIGHT_DECAY, None)
        state = jax.eval_shape(programs.init, jax.random.PRNGKey(0), 256)
        args = (
            state, jax.ShapeDtypeStruct((1, 256), jnp.int32), jax.ShapeDtypeStruct((2,), jnp.uint32),
            jnp.float32(1e-3), jnp.int32(1), jnp.int32(12),
        )
        return programs.step_fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()

    def test_the_lowered_step_holds_the_stack_once_whatever_ut_steps_is(self):
        """Attention custom calls (the forward and the backward's one walk of
        each of the three layers: the rematerialised blocks keep the forward
        kernel's results, so the backward loop's body holds no forward kernel)
        and the products of the block bodies do not grow with the passes."""
        one, four = self._lowered_step(1), self._lowered_step(4)
        assert one.count("tpu_custom_call") == four.count("tpu_custom_call") == 2 * 3
        assert one.count("stablehlo.dot_general") == four.count("stablehlo.dot_general")
        assert four.count("stablehlo.while") >= 2  # the passes, forward and backward
        # unrolled, four passes would hold four times the block bodies
        assert len(four) < 1.5 * len(one)

    def test_attention_plan_counts_every_application_of_a_layer(self):
        cell = LoopedLM(
            vocab_size=49152, attn_fn=transformer._single_device_attention(True),
            sizes=LoopedSizes(d_model=2048, n_heads=16, head_dim=128, mlp_width=5632, n_layers=6, ut_steps=4),
        )
        attrs, counters = transformer.attention_plan(cell, 1, 4096)
        assert attrs == {
            "attn_layers": "full rope x6, 4 passes", "attn_tiles": "bfloat16 q512 k512, backward one walk", "passes": 4,
            "remat": "blocks, keeps attn out+lse",
        }
        # 8 q tiles of 512: 36 tiles hold a visible pair; forward and the one walk back; 16 heads; 6 layers x 4 passes
        assert counters == {"attn_tiles_run": 16 * 24 * 2 * 36, "attn_tiles_needed": 16 * 24 * 2 * 36}
        assert transformer.loss_path(cell, 1, 4096, None) == "fused rows=1 x 4, 4 exits"
        assert transformer.loss_path(cell, 1, 4096, object()) == "fused, 4 exits"
        once = cell.clone(sizes=dataclasses.replace(cell.sizes, ut_steps=1))
        attrs, counters = transformer.attention_plan(once, 1, 4096)
        assert attrs["attn_layers"] == "full rope x6" and "passes" not in attrs
        assert counters["attn_tiles_run"] == 16 * 6 * 2 * 36
        assert transformer.loss_path(once, 1, 4096, None) == "fused rows=1 x 1"


# ---------------------------------------------------------------------------
# the fused loss with a weight a token
# ---------------------------------------------------------------------------


def _plain_weighted(logits, tokens, weights):
    """The formula written out: every position's cross entropy but the last's,
    and their weighted sum."""
    nll = -jnp.take_along_axis(
        jax.nn.log_softmax(logits[..., :-1, :].astype(jnp.float32)), tokens[..., 1:, None], axis=-1
    )[..., 0]
    losses = jnp.pad(nll, [(0, 0)] * (nll.ndim - 1) + [(0, 1)])
    return jnp.sum(losses * weights), losses


def _loss_inputs(lead=(3, 4), s=12, d=16, v=40, bias=True):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (*lead, s, d), jnp.float32)
    w = jax.random.normal(ks[1], (d, v), jnp.float32) * 0.3
    b = jax.random.normal(ks[2], (v,), jnp.float32) * 0.1 if bias else None
    tokens = jax.random.randint(ks[3], (*lead, s), 0, v)
    weights = jax.random.uniform(ks[4], (*lead, s), jnp.float32)
    return x, w, b, tokens, weights


class TestWeightedTokenLosses:
    @pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
    @pytest.mark.parametrize("rows", [None, 1, 4, 12], ids=["from-shapes", "rows1", "rows4", "whole"])
    def test_fused_head_values_and_both_gradients(self, rows, bias):
        """The head's product inside, chunked and whole: the weighted sum, the
        tokens' losses, and the gradients of hidden states, kernel, bias AND
        weights against dense logits through autodiff."""
        x, w, b, tokens, weights = _loss_inputs(bias=bias)
        args = (0, 1, 2, 3) if bias else (0, 1, 3)

        def ours(x, w, b, weights):
            total, losses = weighted_token_losses(HeadInputs(x, w, b), tokens, weights, rows)
            return 3.0 * total, losses

        def plain(x, w, b, weights):
            logits = x @ w if b is None else x @ w + b
            total, losses = _plain_weighted(logits, tokens, weights)
            return 3.0 * total, losses

        with jax.default_matmul_precision("highest"):
            (got, got_losses), d_got = jax.jit(jax.value_and_grad(ours, args, has_aux=True))(x, w, b, weights)
            (want, want_losses), d_want = jax.value_and_grad(plain, args, has_aux=True)(x, w, b, weights)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        np.testing.assert_allclose(got_losses, want_losses, rtol=1e-5, atol=1e-6)
        assert got_losses.shape == tokens.shape and not np.any(np.asarray(got_losses[..., -1]))
        for g, x_ in zip(d_got, d_want, strict=True):
            np.testing.assert_allclose(g, x_, rtol=1e-4, atol=1e-5)
        # a weight's gradient is its token's loss (times the cotangent)
        np.testing.assert_allclose(d_got[-1], 3.0 * got_losses, rtol=1e-6)

    def test_dense_logits_values_and_both_gradients(self):
        x, w, b, tokens, weights = _loss_inputs()
        logits = x @ w + b

        def ours(logits, weights):
            total, losses = weighted_token_losses(logits, tokens, weights)
            return 2.0 * total, losses

        def plain(logits, weights):
            total, losses = _plain_weighted(logits, tokens, weights)
            return 2.0 * total, losses

        (got, got_losses), d_got = jax.value_and_grad(ours, (0, 1), has_aux=True)(logits, weights)
        (want, want_losses), d_want = jax.value_and_grad(plain, (0, 1), has_aux=True)(logits, weights)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        np.testing.assert_allclose(got_losses, want_losses, rtol=1e-5, atol=1e-6)
        for g, x_ in zip(d_got, d_want, strict=True):
            np.testing.assert_allclose(g, x_, rtol=1e-5, atol=1e-6)

    def test_even_weights_are_lm_loss(self):
        x, w, b, tokens, _ = _loss_inputs(lead=(4,))
        even = jnp.full(tokens.shape, 1.0 / (4 * 11))
        for logits in (HeadInputs(x, w, b), x @ w + b):
            np.testing.assert_allclose(weighted_token_losses(logits, tokens, even)[0], lm_loss(logits, tokens), rtol=1e-6)

    def test_no_array_as_wide_as_the_vocabulary_outlives_its_chunk(self):
        """Twelve sequences in chunks of four: the widest array of the jaxpr
        of value and gradients is a chunk's logits, never all rows'."""
        x, w, b, tokens, weights = _loss_inputs()
        jaxpr = jax.make_jaxpr(
            jax.grad(lambda x, w, wt: weighted_token_losses(HeadInputs(x, w, b), tokens, wt, 4)[0], (0, 1, 2))
        )(x, w, weights)
        text = str(jaxpr)
        assert "f32[4,12,40]" in text and "[12,12,40]" not in text and "[3,4,12,40]" not in text

    def test_rows_that_do_not_divide_are_refused(self):
        x, w, b, tokens, weights = _loss_inputs()
        with pytest.raises(ValueError, match="do not divide"):
            weighted_token_losses(HeadInputs(x, w, b), tokens, weights, 5)

    @pytest.mark.parametrize("fused", [False, True], ids=["dense", "fused"])
    def test_on_a_data_mesh(self, fused):
        """Exits first, the batch over a ``data`` axis of two devices: the
        dense path a model on a mesh takes (and the fused one beside it), the
        values and gradients of one device."""
        from jax.sharding import NamedSharding, PartitionSpec

        from katib_tpu.parallel.mesh import DATA_AXIS, make_mesh

        mesh = make_mesh({DATA_AXIS: 2}, devices=jax.devices()[:2])
        x, w, b, tokens, weights = _loss_inputs()
        rows = NamedSharding(mesh, PartitionSpec(None, DATA_AXIS))

        def loss(x, w, weights):
            logits = HeadInputs(x, w, b) if fused else x @ w + b
            total, losses = weighted_token_losses(logits, tokens, weights)
            return total, losses

        want = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(x, w, weights)
        placed = [jax.device_put(a, rows) for a in (x, weights)]
        got = jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(placed[0], w, placed[1])
        for g, x_ in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want), strict=True):
            np.testing.assert_allclose(g, x_, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the loss belongs to the model
# ---------------------------------------------------------------------------

OLDER = {
    "gpt2": {"d_model": 32, "n_heads": 2, "n_layers": 1},
    "mla_moe": {
        "d_model": 32, "n_heads": 2, "n_layers": 2, "qk_nope_dim": 8, "qk_rope_dim": 8, "v_head_dim": 8,
        "kv_lora_rank": 16, "dense_width": 48, "expert_width": 16, "n_experts": 4, "experts_per_token": 2,
    },
    "gqa_moe": {
        "d_model": 32, "n_heads": 2, "n_kv_heads": 1, "head_dim": 8, "n_layers": 2, "window": 8,
        "window_layout": "01", "rope_layout": "01", "expert_width": 16, "n_experts": 4, "experts_per_token": 2,
    },
}


class TestTheLossBelongsToTheModel:
    @pytest.mark.parametrize("block", sorted(OLDER))
    def test_the_older_blocks_answer_with_lm_loss_for_both(self, block):
        model = transformer._BLOCKS[block]({"seq_len": 16, **OLDER[block]}, 64, None)
        tokens = _tokens({"vocab_size": 64, "batch_size": 2, "seq_len": 16})
        params = model.init(jax.random.PRNGKey(0), tokens)
        for multiply in (True, False):
            outputs = model.apply(params, tokens, multiply_head=multiply)
            loss, read = model.training_loss(outputs, tokens)
            assert read == {}
            want = lm_loss(outputs, tokens)
            assert float(loss) == float(want) == float(model.reported_loss(outputs, tokens))

    @pytest.mark.parametrize("block", sorted(OLDER))
    def test_the_older_blocks_step_is_value_and_grad_of_lm_loss(self, block):
        """Their step's jaxpr is what the loss written into ``_build_programs``
        gave (sha256 of the three cells' steps, before and after: CHANGES.md,
        PR 37): here, at a test size, equal text against that closure."""
        model = transformer._BLOCKS[block]({"seq_len": 16, **OLDER[block]}, 64, None)
        tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
        programs = transformer._build_programs(model, 1.0, transformer.WEIGHT_DECAY, None)
        state = jax.eval_shape(programs.init, jax.random.PRNGKey(0), 16)

        def before(params, tokens):
            logits, sown = model.apply(params, tokens, mutable=[transformer.ROUTING], multiply_head=False)
            return lm_loss(logits, tokens), sown.get(transformer.ROUTING, {})

        def after(params, tokens):
            outputs, sown = model.apply(params, tokens, mutable=[transformer.ROUTING], multiply_head=False)
            loss, read = model.training_loss(outputs, tokens)
            return loss, {**sown.get(transformer.ROUTING, {}), **read}

        texts = [
            str(jax.make_jaxpr(jax.value_and_grad(f, has_aux=True))(state.params, tokens)) for f in (before, after)
        ]
        assert texts[0] == texts[1] and len(texts[0]) > 10_000

    def test_a_looped_trial_reports_its_objective_and_its_last_exit(self, family):
        """``train_lm``'s ``loss`` is the training objective, ``eval_loss`` the
        last exit's cross entropy: against the reference's two programs."""
        sizes = {**TINY, "n_seq": 24}
        data = transformer.markov_dataset(64, 24, 16, seed=3)
        model = _model(family, sizes)
        reports = []
        with jax.default_matmul_precision("highest"):
            transformer.train_lm(
                model, data, lr=1e-3, steps=12, batch_size=2, report_every=10,
                report=lambda step, loss, eval_loss: reports.append((step, loss, eval_loss)),
            )
        want = family.reference_series(sizes, {"steps": 12}, 3, 1e-3)
        assert [r[0] for r in reports] == [0, 10, 11]
        for step, loss, eval_loss in reports[:2]:
            np.testing.assert_allclose(loss, want["loss"][step], rtol=5e-5)
            np.testing.assert_allclose(eval_loss, want["eval_loss"][step], rtol=5e-5)
        assert reports[0][1] != reports[0][2]


# ---------------------------------------------------------------------------
# on the normal path: transformer_trial's parameters, the table of programs
# ---------------------------------------------------------------------------


class _Ctx:
    mesh = None

    def __init__(self, params):
        self.params = params
        self.reports = []

    def report(self, **metrics):
        self.reports.append(metrics)
        return True


TRIAL = {
    "block": "looped", "vocab_size": 64, "seq_len": 32, "n_seq": 40, "batch_size": 4, "steps": 3,
    "d_model": 48, "n_heads": 3, "head_dim": 16, "mlp_width": 64, "n_layers": 2, "ut_steps": 4,
    "exit_beta": 0.05, "lr": 1e-3,
}


class TestNormalPath:
    def test_fields_hash_and_equal_sizes_are_one_key(self):
        a = transformer._looped_model(dict(TRIAL), 64, None)
        b = transformer._looped_model(dict(TRIAL), 64, None)
        assert a == b and hash(a) == hash(b) and a.attn_fn is b.attn_fn
        assert a.sizes.exit_beta == 0.05 and a.sizes.ut_steps == 4
        for other in ({"ut_steps": 2}, {"exit_beta": 0.1}, {"mlp_width": 80}, {"head_dim": 8}):
            assert transformer._looped_model({**TRIAL, **other}, 64, None) != a
        p1, _ = transformer._programs_for(a, 1.0, None)
        p2, reused = transformer._programs_for(b, 1.0, None)
        assert p1 is p2 and reused

    def test_spans_of_two_trials_of_one_structure(self, tmp_path):
        """``trial.init`` carries the block, the passes, the layers, the tiles
        and the loss's path; ``trial.eval`` the exits of the step before the
        report; the second learning rate runs the first's programs."""
        path = str(tmp_path / "trace.jsonl")
        tracer = tracing.Tracer(path)
        ctxs = [_Ctx({**TRIAL, "lr": lr, "mlp_width": 72}) for lr in (1e-3, 3e-4)]
        with tracing.use_tracer(tracer):
            for i, ctx in enumerate(ctxs):
                with tracing.span("train_fn", trial=f"t{i}") as sp:
                    for counter in tracing.JIT_COUNTERS:
                        sp.add(counter, 0)
                    transformer.transformer_trial(ctx)
        tracer.close()
        records = list(tracing.read_journal(path))
        inits = [r["args"] for r in records if r["name"] == "trial.init"]
        assert [a["block"] for a in inits] == ["looped", "looped"]
        assert [a["passes"] for a in inits] == [4, 4]
        assert inits[0]["attn_layers"] == "full rope x2, 4 passes"
        assert inits[0]["attn_tiles"] == "dense" and "attn_tiles_run" not in inits[0]  # no kernel on the CPU
        assert inits[0]["loss"] == "fused rows=16 x 1, 4 exits" and "expert_buffer" not in inits[0]
        assert inits[0]["programs"] == "built" and inits[1]["programs"] == "reused"
        second = [r["args"] for r in records if r["name"] == "train_fn"][1]
        assert second["jit_programs"] == 0
        evals = [r["args"] for r in records if r["name"] == "trial.eval"]
        assert len(evals) == 4  # steps 0 and 2 of both trials
        for args in evals:
            shares = [args[f"exit_share_{t}"] for t in (1, 2, 3, 4)]
            losses = [args[f"exit_loss_{t}"] for t in (1, 2, 3, 4)]
            assert abs(sum(shares) - 1.0) < 1e-5 and all(0 < s < 1 for s in shares)
            assert all(2.0 < loss < 8.0 for loss in losses)  # near log(64)
            np.testing.assert_allclose(args["exit_step_mean"], sum(t * s for t, s in zip((1, 2, 3, 4), shares)), rtol=1e-6)
            assert 1.0 < args["exit_step_mean"] < 4.0 and 0.0 < args["exit_entropy"] <= np.log(4) + 1e-6
            assert "exit_share_5" not in args and "moe_tokens_dropped" not in args
        assert all(np.isfinite(r["eval_loss"]) and np.isfinite(r["loss"]) for ctx in ctxs for r in ctx.reports)

    def test_a_data_mesh_gives_one_devices_losses(self):
        from katib_tpu.parallel.mesh import DATA_AXIS, make_mesh

        mesh = make_mesh({DATA_AXIS: 2}, devices=jax.devices()[:2])
        data = transformer.markov_dataset(64, 40, 32, seed=5)
        series = {}
        for name, m in (("one", None), ("mesh", mesh)):
            model = transformer._looped_model(dict(TRIAL), 64, m).clone(dtype=jnp.float32)
            reported = []
            transformer.train_lm(
                model, data, lr=1e-3, steps=2, batch_size=4, mesh=m, report_every=1,
                report=lambda step, loss, eval_loss: reported.append((loss, eval_loss)),
            )
            series[name] = reported
        np.testing.assert_allclose(series["mesh"], series["one"], rtol=2e-5)

    def test_example_runs_through_the_orchestrator(self, tmp_path):
        """Orchestrator.run -> trial runner -> transformer_trial -> train_lm."""
        from katib_tpu.orchestrator.orchestrator import Orchestrator
        from katib_tpu.sdk.yaml_spec import load_experiment_yaml

        spec = load_experiment_yaml(os.path.join(REPO, "examples", "hp-tuning", "transformer-looped.yaml"))
        assert {p.name for p in spec.parameters} >= {"lr", "exit_beta", "ut_steps"}
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert exp.succeeded_count == 2 and exp.optimal is not None
        records = list(tracing.read_journal(str(tmp_path / spec.name / "trace.jsonl")))
        inits = [r["args"] for r in records if r["name"] == "trial.init"]
        assert [a["block"] for a in inits] == ["looped"] * 2 and all(a["passes"] == 4 for a in inits)
        evals = [r["args"] for r in records if r["name"] == "trial.eval"]
        assert evals and all(1.0 <= a["exit_step_mean"] <= 4.0 for a in evals)

    @pytest.mark.parametrize(
        "bad,match",
        [
            ({"dropout": 0.1}, "has no dropout"),
            ({"ut_steps": 0}, "runs at least once"),
            ({"block": "loop"}, "is neither 'gpt2', 'mla_moe', 'gqa_moe' nor 'looped'"),
        ],
    )
    def test_refusals_are_clear(self, bad, match):
        with pytest.raises(ValueError, match=match):
            transformer.transformer_trial(_Ctx({**TRIAL, **bad}))

    def test_seq_axis_is_refused(self):
        from katib_tpu.parallel.mesh import SEQ_AXIS

        class Mesh:
            shape = {SEQ_AXIS: 2}

        with pytest.raises(ValueError, match="'seq' axis"):
            transformer._looped_model(dict(TRIAL), 64, Mesh())


# ---------------------------------------------------------------------------
# the family's counts, the configuration file, the readers
# ---------------------------------------------------------------------------


class TestFamilyCounts:
    def test_counts_from_shapes(self, family, config):
        sizes = {k: config[k] for k in family.SIZE_KEYS}
        assert family.layer_params(sizes) == 4 * 2048 * 2048 + 3 * 2048 * 5632 == 51_380_224
        assert family.layer_applications(sizes) == 24
        tokens = 4096
        products = 6.0 * 24 * 51_380_224 * tokens
        heads = 6.0 * 4 * (2048 * 49152 + 2048) * tokens
        attention = 3.0 * 24 * (2.0 * 16 * 4096 * 4096 * 128)
        assert family.step_flops(sizes) == pytest.approx(products + heads + attention)
        assert 45.0e12 < family.step_flops(sizes) < 45.5e12 and 0.21 < heads / family.step_flops(sizes) < 0.23
        cost = family.flash_attention_cost(sizes)
        assert cost["calls_per_step"] == 24 and cost["flops"] == pytest.approx(attention / 24)
        tensor = 16 * 4096 * 128 * 2
        assert cost["bytes"] == 12 * tensor + 2 * 16 * 4096 * 4
        assert family.exit_loss_mark(sizes) == "4096,49152"

    def test_the_file_holds_every_published_key_and_one_is_cut(self, family, config):
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            (row,) = [r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B"]
        assert config["source"] == row["source_url"] and config["source_config"] == row["config"]
        differs = [k for k, v in row["config"].items() if config.get(k) != v]
        assert differs == config["reduced"] == ["num_hidden_layers"]
        assert (config["num_hidden_layers"], row["config"]["num_hidden_layers"]) == (6, 48)
        assert "48 published; 6 here" in config["published"]["num_hidden_layers"]
        assert config["total_ut_steps"] == 4 and config["early_exit_threshold"] == 1
        for item in ("sub_layer_output_norms", "norm_after_every_pass", "exit_gate", "objective", "exit_beta",
                     "seq_len", "batch_size", "n_seq", "data"):
            assert item in config["assumed"], item
        assert any("no early exit" in d for d in config["departures"])
        assert config["family"] == "looped" and config["block"] == "looped"

    def test_a_checkout_without_the_block_is_refused_at_once(self, family, monkeypatch):
        monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
        with pytest.raises(SystemExit, match="no block 'looped'"):
            family.experiment_doc("x", TINY, TRAFFIC, 1)

    def test_fewer_key_value_heads_are_refused(self, family):
        with pytest.raises(SystemExit, match="as many key-value heads"):
            family.experiment_doc("x", {**TINY, "num_key_value_heads": 1}, TRAFFIC, 1)


def _event(text, t0, ms):
    return (text, t0, t0 + ms / 1000.0)


class TestStepReaders:
    """``exit_loss_ms``, ``loop_pass_ms`` and ``exit_step_mean`` on a hand-made
    slice: two step executions, named as the device trace names them."""

    SIZES = {"seq_len": 4096, "vocab_size": 49152, "total_ut_steps": 4}
    # the head's forward product with the fused max (it writes a chunk's logits), and the
    # weight gradient's product, which reads them: the v5e's names (my chip run, PR 37)
    HEAD = (
        "%fusion.1848 = (f32[4096]{0:T(1024)S(1)}, f32[4096,49152]{1,0:T(8,128)}) fusion(bf16[2048,49152]{1,0:T(8,128)(2,1)} "
        "%get-tuple-element.8212, bf16[4,1,4096,2048]{2,3,1,0:T(8,128)(2,1)S(1)} %get-tuple-element.8209), kind=kOutput"
    )
    LOGITS = (
        "%fusion.1852 = f32[2048,49152]{1,0:T(8,128)} fusion(f32[4096,49152]{1,0:T(8,128)} %get-tuple-element.7427, "
        "f32[2048,49152]{1,0:T(8,128)} %get-tuple-element.8195, f32[4096]{0:T(1024)S(1)} %bitcast.3006), kind=kOutput"
    )
    LOOP = "%while.10 = (s32[], f32[2048,49152]{1,0}, f32[4096,49152]{1,0}) while((s32[], f32[2048,49152]{1,0}, f32[4096,49152]{1,0}) %tuple.1), condition=%c, body=%b"
    BLOCK = "%fusion.4 = bf16[1,4096,5632]{2,1,0} fusion(bf16[1,4096,2048]{2,1,0} %h), kind=kOutput"
    ADAM = "%fusion.5 = (f32[2048,49152]{1,0}, f32[2048,49152]{1,0}) fusion(f32[2048,49152]{1,0} %state_opt_state_0__mu__params____head____kernel__.1, f32[2048,49152]{1,0} %g), kind=kLoop"
    PREFETCH = "%copy-start.6 = (f32[2048]{0}, f32[2048]{0}, u32[]) copy-start(f32[2048]{0} %state_opt_state_0__nu__params____stack____norm____scale__.1)"

    def _slice(self):
        events = []
        for t0 in (10.0, 11.0):  # a step of 500 ms: 340 in the passes, 100 in the heads, 60 in the update
            events += [
                _event(self.PREFETCH, t0 + 0.001, 0.01),
                _event(self.BLOCK, t0 + 0.010, 150.0),
                _event(self.LOOP, t0 + 0.170, 100.0),  # the chunk loop itself: not counted again
                _event(self.HEAD, t0 + 0.170, 40.0),
                _event(self.LOGITS, t0 + 0.210, 60.0),
                _event(self.BLOCK, t0 + 0.270, 170.0),
                _event(self.ADAM, t0 + 0.440, 55.0),
            ]
        events.append(_event(self.LOGITS, 12.0, 30.0))  # an eval: outside every step
        steps = [("jit_step_fn(1)", 10.0, 10.5), ("jit_step_fn(1)", 11.0, 11.5)]
        return types.SimpleNamespace(
            t0=9.0, t1=13.0, ops=lambda: events, module_events=lambda m: steps if m == "jit_step_fn" else []
        )

    def test_step_parts(self, family):
        parts = family.step_parts(self._slice(), self.SIZES)
        assert parts["exit_loss_ms"] == pytest.approx(100.0)
        assert parts["optimizer_ms"] == pytest.approx(60.0)
        assert parts["loop_pass_ms"] == pytest.approx((500.0 - 100.0 - 60.0) / 4)
        empty = types.SimpleNamespace(ops=lambda: [], module_events=lambda m: [])
        assert family.step_parts(empty, self.SIZES) is None

    @pytest.mark.parametrize("metric,value", [("exit_loss_ms", 100.0), ("loop_pass_ms", 85.0)])
    def test_the_readers_of_the_device_trace(self, family, metric, value):
        read = _load("layer_metrics", metric).read
        cell = types.SimpleNamespace(family=family, sizes=self.SIZES)
        assert read({"cell": cell, "slice": self._slice()}) == pytest.approx(value)
        older = types.SimpleNamespace(family=types.SimpleNamespace(STEP_MODULE="jit_step_fn"), sizes={})
        assert read({"cell": older, "slice": self._slice()}) is None  # a family with no such mark

    def test_exit_step_mean_reads_the_reports_inside_the_slice(self):
        read = _load("layer_metrics", "exit_step_mean").read
        sl = types.SimpleNamespace(t0=10.0, t1=20.0)
        ev = lambda t0, **args: {"name": "trial.eval", "t0": t0, "t1": t0 + 0.1, "args": args}  # noqa: E731
        spans = [
            ev(5.0, exit_step_mean=3.9),  # the warm-up trial
            ev(11.0, exit_step_mean=1.9), ev(15.0, exit_step_mean=2.1),
            ev(19.95, exit_step_mean=3.9),  # cut by the slice's end
            {"name": "trial.init", "t0": 12.0, "t1": 12.1, "args": {"exit_step_mean": 9.0}},
        ]
        assert read({"spans": spans, "slice": sl}) == pytest.approx(2.0)
        assert read({"spans": [ev(11.0, moe_tokens_dropped=0)], "slice": sl}) is None

    def test_the_entries_name_the_new_cell(self):
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            bench = json.load(f)
        listed = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", ())}
        assert set(listed) == {"exit_loss_ms", "loop_pass_ms", "exit_step_mean"}
        for name, source in (("exit_loss_ms", "device_trace"), ("loop_pass_ms", "device_trace"), ("exit_step_mean", "program_counter")):
            entry = listed[name]
            assert (entry["layer"], entry["moves"], entry["better"], entry["source"], entry["workloads"]) == (
                "model step", "trials_per_hour", "lower", source, [CELL]
            )
            assert os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics", f"{name}.py"))
        (row,) = [w for w in bench["workloads"] if w["name"] == CELL]
        assert (row["config"], row["traffic"], row["chips"]) == ("ouro-2.6b-l6", "lr4low-steps12", 1)
        (cfg,) = [c for c in bench["configs"] if c["name"] == "ouro-2.6b-l6"]
        assert cfg["reduced"] == ["num_hidden_layers"] and cfg["file"] == "benchmark/configs/ouro-2.6b-l6.json"
        assert cfg["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
        assert len(row["why"]) <= 200 and len(cfg["why"]) <= 200
        with open(os.path.join(REPO, "benchmark", "limits", f"{CELL}.json")) as f:
            limits = json.load(f)["limits"]
        assert set(limits["trained_loss_gap"]) == {"by", "3e-05", "0.0001", "0.0003", "0.001"}
        assert 0 < limits["first_loss_gap"] < 1e-2
