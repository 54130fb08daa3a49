"""The second block family of ``transformer_trial`` (``block: mla_moe``):
latent attention with values narrower than keys, and an expert layer that is
told which routed experts it holds.

Against the benchmark's plain reference (``benchmark/families/mla_moe.py``,
loaded by path: the repo's one copy), at tiny sizes on the CPU.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import full_buffer_experts as planted
from katib_tpu.models import mla_moe, transformer
from katib_tpu.models.mla_moe import ROUTING, ExpertLayer, MlaMoeLM, MlaMoeSizes, SwiGLU, buffer_rungs
from katib_tpu.ops.flash_attention import (
    flash_attention,
    reference_attention,
)
from katib_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def family():
    path = os.path.join(REPO, "benchmark", "families", "mla_moe.py")
    spec = importlib.util.spec_from_file_location("benchmark_families_mla_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the configuration file's keys, as the family reads them
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 32, "intermediate_size": 160, "moe_intermediate_size": 32, "router_width": 16,
    "num_experts_per_tok": 3, "n_shared_experts": 2, "experts_held_first": 4, "n_routed_experts": 8,
    "vocab_size": 96, "seq_len": 32, "batch_size": 4, "n_seq": 48,
    "routed_scaling_factor": 2.448, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
}


def _model(family, config=CONFIG, dtype=jnp.float32) -> MlaMoeLM:
    """The model ``transformer_trial`` builds from the parameters the family's
    experiment document pins."""
    params = {family.PARAMS[k]: config[k] for k in family.PARAMS}
    params |= {family.FLOAT_PARAMS[k]: config[k] for k in family.FLOAT_PARAMS}
    model = transformer._mla_moe_model(params, config["vocab_size"], None)
    return model.clone(dtype=dtype)


# ---------------------------------------------------------------------------
# the model against the reference, through train_lm
# ---------------------------------------------------------------------------


class TestAgainstReference:
    def test_initial_weights_are_the_references(self, family):
        model = _model(family)
        programs, _ = transformer._programs_for(model, 1.0, None)
        got = programs.init(jax.random.PRNGKey(0), CONFIG["seq_len"]).params["params"]
        want = family.init_params(CONFIG)
        count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))  # noqa: E731
        assert count(got) == count(want)
        np.testing.assert_array_equal(got["embed"]["embedding"], want["embed"])
        np.testing.assert_array_equal(got["head"]["kernel"], want["head"])
        np.testing.assert_array_equal(
            got["layer_0"]["mlp"]["down_proj"]["kernel"], want["dense_layers"]["down"][0]
        )
        for i in (1, 2):
            moe, ref = got[f"layer_{i}"]["moe"], want["expert_layers"]
            for name in ("router", "experts_gate", "experts_up", "experts_down"):
                np.testing.assert_array_equal(moe[name], ref[name][i - 1])
            np.testing.assert_array_equal(moe["shared"]["up_proj"]["kernel"], ref["shared_up"][i - 1])
            np.testing.assert_array_equal(
                got[f"layer_{i}"]["attn"]["kv_b_proj"]["kernel"], ref["kv_b"][i - 1]
            )

    @pytest.mark.parametrize("lr", [1e-3, 1e-4])
    def test_loss_and_gradient_norm_follow_the_reference(self, family, lr):
        """float32 activations: the program and the reference then differ by
        summation order only.  Losses through ``train_lm`` (every step
        reported), gradient norms from the same programs' state."""
        steps, seed = 4, 7
        model = _model(family)
        data = family.markov_tokens(CONFIG["vocab_size"], CONFIG["n_seq"], CONFIG["seq_len"], seed)
        np.testing.assert_array_equal(
            data, transformer.markov_dataset(CONFIG["vocab_size"], CONFIG["n_seq"], CONFIG["seq_len"], seed=seed)
        )
        reported = []
        transformer.train_lm(
            model, data, lr=lr, steps=steps, batch_size=CONFIG["batch_size"], report_every=1,
            report=lambda step, loss, eval_loss: reported.append((loss, eval_loss)),
        )

        rows, eval_rows = family.batches(data, CONFIG["batch_size"], steps)
        step, eval_loss = family._programs(family.shape_of(CONFIG), CONFIG["batch_size"], "f32", None)
        params = family.init_params(CONFIG)
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
        programs, _ = transformer._programs_for(model, 1.0, None)
        state = programs.init(jax.random.PRNGKey(0), CONFIG["seq_len"])
        schedule = (jnp.float32(lr), jnp.int32(1), jnp.int32(steps))
        grad_norm = jax.jit(
            lambda p, t: optax.global_norm(
                jax.grad(lambda q: transformer.lm_loss(model.apply(q, t), t))(p)
            )
        )
        for s in range(steps):
            tokens = jnp.asarray(rows[s])
            got_norm = float(grad_norm(state.params, tokens))
            state, _, _ = programs.step_fn(state, tokens, jax.random.PRNGKey(1), *schedule)
            params, m, v, loss, want_norm = step(
                params, m, v, jnp.int32(s), jnp.float32(family.lr_at(s, lr, steps)), tokens
            )
            if s in (0, 3):  # the first step, and after 3 updates
                assert got_norm == pytest.approx(float(want_norm), rel=2e-4)
                assert reported[s][0] == pytest.approx(float(loss), rel=2e-5)
                assert reported[s][1] == pytest.approx(
                    float(eval_loss(params, jnp.asarray(eval_rows))), rel=2e-5
                )

    def test_bfloat16_program_is_near_and_fp8_is_not(self, family):
        """The program as it runs (bfloat16 activations) against the reference
        and its fp8 control, first report: the control is further away."""
        model = _model(family, dtype=jnp.bfloat16)
        data = family.markov_tokens(CONFIG["vocab_size"], CONFIG["n_seq"], CONFIG["seq_len"], 3)
        series = {"loss": {}, "eval_loss": {}}

        def report(step, loss, eval_loss):
            series["loss"][step], series["eval_loss"][step] = loss, eval_loss

        traffic = {"steps": 12}
        transformer.train_lm(model, data, lr=1e-3, steps=12, batch_size=CONFIG["batch_size"], report=report)
        reference = family.reference_series(CONFIG, traffic, 3, 1e-3)
        control = family.reference_series(CONFIG, traffic, 3, 1e-3, precision="fp8")
        got = family.compare(series, reference)
        assert got["first_loss_gap"] < 3e-3 and got["trained_loss_gap"] < 1e-2
        assert family.compare(control, reference)["trained_loss_gap"] > 3 * got["trained_loss_gap"]


# ---------------------------------------------------------------------------
# the expert layer: shares, skew, counters
# ---------------------------------------------------------------------------

SIZES = MlaMoeSizes(
    d_model=64, n_experts=16, experts_per_token=3, expert_width=32, n_shared_experts=2,
    routed_scaling=2.448, experts_held=(0, 16),
)
LAYER_CONFIG = {
    **CONFIG, "experts_held_first": 0, "n_routed_experts": 16, "num_hidden_layers": 1,
    "first_k_dense_replace": 0,
}


def _layer_weights(key, skew: float = 0.0):
    """One uncut expert layer's weights under the reference's names; ``skew``
    adds to the router's columns of experts 5 and 6."""
    d, w, n = SIZES.d_model, SIZES.expert_width, SIZES.n_experts
    ks = jax.random.split(key, 7)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])  # noqa: E731
    router = normal(ks[0], d, n)
    router = router.at[:, 5:7].add(skew / np.sqrt(d))
    return {
        "norm2": jnp.ones((d,)),
        "router": router,
        "experts_gate": normal(ks[1], n, d, w),
        "experts_up": normal(ks[2], n, d, w),
        "experts_down": normal(ks[3], n, w, d),
        "shared_gate": normal(ks[4], d, 2 * w),
        "shared_up": normal(ks[5], d, 2 * w),
        "shared_down": normal(ks[6], 2 * w, d),
    }


def _share(weights, first: int, count: int, h):
    """The program's expert layer holding experts [first, first+count), fed
    the normed stream ``h``: what it adds, and what it sowed."""
    layer = ExpertLayer(dataclasses.replace(SIZES, experts_held=(first, count)), jnp.float32)
    held = slice(first, first + count)
    params = {
        "router": weights["router"],
        "experts_gate": weights["experts_gate"][held],
        "experts_up": weights["experts_up"][held],
        "experts_down": weights["experts_down"][held],
        "shared": {
            "gate_proj": {"kernel": weights["shared_gate"]},
            "up_proj": {"kernel": weights["shared_up"]},
            "down_proj": {"kernel": weights["shared_down"]},
        },
    }
    out, sown = layer.apply({"params": params}, h, mutable=[ROUTING])
    shared = SwiGLU(2 * SIZES.expert_width, jnp.float32).apply({"params": params["shared"]}, h)
    return out, shared, sown[ROUTING]


# the ladder's tests: experts 4-7 of 32 held, 384 tokens, rungs of 384 and 1152 rows
LADDER_SIZES = MlaMoeSizes(
    d_model=64, n_experts=32, experts_per_token=3, expert_width=32, n_shared_experts=2,
    routed_scaling=2.448, experts_held=planted.HELD,
)


@pytest.fixture()
def fresh_traces():
    """The layer's arithmetic is one jitted function, traced once for equal
    shapes: a test that replaces what it calls must not find a trace made
    before, nor leave its own behind."""
    mla_moe._experts.clear_cache()
    yield
    mla_moe._experts.clear_cache()


class TestExpertLayer:
    @pytest.fixture()
    def stream(self):
        return jax.random.normal(jax.random.PRNGKey(11), (2, 24, SIZES.d_model), jnp.float32)

    def _reference(self, family, weights, x):
        f = family._layer_functions(family.shape_of(LAYER_CONFIG), "f32", None, *x.shape[:2])
        return f["moe"](x, weights), f["rms_norm"](x, weights["norm2"])

    def test_shares_add_up_to_the_uncut_reference_layer(self, family, stream):
        """16 experts in 4 shares of 4: the routed parts of all shares, plus
        the shared experts and the residual counted once, are the whole
        layer as the reference computes it."""
        weights = _layer_weights(jax.random.PRNGKey(5))
        want, h = self._reference(family, weights, stream)
        total = stream
        routed_rows = 0
        for i in range(4):
            out, shared, sown = _share(weights, 4 * i, 4, h)
            total = total + (out - shared) + (shared if i == 0 else 0.0)
            routed_rows += int(sown["expert_tokens"][0].sum())
            assert sown["assignments"][0][1] == stream.shape[0] * stream.shape[1] * 3
        np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
        # every assignment of every token was computed by exactly one share
        assert routed_rows == stream.shape[0] * stream.shape[1] * SIZES.experts_per_token

    def test_all_experts_held_is_the_whole_layer(self, family, stream):
        weights = _layer_weights(jax.random.PRNGKey(6))
        want, h = self._reference(family, weights, stream)
        out, _, sown = _share(weights, 0, 16, h)
        np.testing.assert_allclose(stream + out, want, rtol=2e-5, atol=2e-5)
        assert sown["assignments"][0].tolist() == [48 * 3, 48 * 3]

    def test_skewed_router_drops_nothing(self, family, stream):
        """Every token sends two of its three choices to experts 5 and 6: no
        capacity, so both get every token and the result is still exact."""
        weights = _layer_weights(jax.random.PRNGKey(7), skew=40.0)
        stream = jnp.abs(stream)  # every normed feature positive: the skew always wins
        reference = {**weights, **{k: weights[k][4:8] for k in ("experts_gate", "experts_up", "experts_down")}}
        f = family._layer_functions(
            family.shape_of({**LAYER_CONFIG, "experts_held_first": 4, "n_routed_experts": 4}),
            "f32", None, *stream.shape[:2],
        )
        want, h = f["moe"](stream, reference), f["rms_norm"](stream, weights["norm2"])
        out, _, sown = _share(weights, 4, 4, h)
        np.testing.assert_allclose(stream + out, want, rtol=2e-5, atol=2e-5)
        tokens = sown["expert_tokens"][0]
        assert tokens[1] == tokens[2] == 48  # experts 5 and 6: every token
        counters = MlaMoeLM.step_counters({"layer_1": {"moe": sown}})
        assert counters["moe_tokens_dropped"] == 0
        assert counters["moe_assignments_held"] == tokens.sum() >= 96
        assert counters["moe_assignments_total"] == 48 * 3
        assert counters["moe_expert_tokens_max"] == 48
        assert counters["moe_expert_tokens_mean"] == pytest.approx(tokens.sum() / 4)

    @pytest.mark.parametrize(
        "first,count,rows", [(8, 4, 128), (4, 8, 144)], ids=["short-rung", "whole-buffer"]
    )
    def test_rows_past_the_last_group_never_reach_a_sum(self, family, stream, monkeypatch, fresh_traces, first, count, rows):
        """On the TPU the grouped product's kernels leave the rows that belong
        to no group unwritten (stale memory), in the forward result and in
        the gradient of the rows; XLA's CPU lowering writes zeros there, so
        the fault has to be planted: with NaN in those rows, the share's
        result and gradients still match the reference: on a rung shorter
        than the ``T x k`` = 144 assignments, and where a share of half the
        experts has the one rung of all 144."""
        real = jax.lax.ragged_dot

        def stale_rows(x, group_sizes):
            past = jnp.arange(x.shape[0])[:, None] >= group_sizes.sum()
            return jnp.where(past, jnp.nan, x)

        @jax.custom_vjp
        def kernel_like(lhs, rhs, group_sizes):
            return stale_rows(real(lhs, rhs, group_sizes, preferred_element_type=jnp.float32), group_sizes)

        def forward(lhs, rhs, group_sizes):
            return kernel_like(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

        def backward(res, g):
            lhs, rhs, group_sizes = res
            # a kernel reads only the rows of its groups
            g = jnp.where(jnp.arange(g.shape[0])[:, None] < group_sizes.sum(), g, 0.0)
            _, vjp = jax.vjp(lambda a, b: real(a, b, group_sizes, preferred_element_type=jnp.float32), lhs, rhs)
            d_lhs, d_rhs = vjp(g)
            return stale_rows(d_lhs, group_sizes), d_rhs, None

        kernel_like.defvjp(forward, backward)
        monkeypatch.setattr(jax.lax, "ragged_dot", lambda lhs, rhs, group_sizes, **kw: kernel_like(lhs, rhs, group_sizes))

        weights = _layer_weights(jax.random.PRNGKey(9))
        held = slice(first, first + count)
        reference = {**weights, **{k: weights[k][held] for k in ("experts_gate", "experts_up", "experts_down")}}
        f = family._layer_functions(
            family.shape_of({**LAYER_CONFIG, "experts_held_first": first, "n_routed_experts": count}),
            "f32", None, *stream.shape[:2],
        )

        def program(x, w):
            out, _, sown = _share(w, first, count, f["rms_norm"](x, w["norm2"]))
            return jnp.sum(jnp.square(x + out)), sown

        (got, sown), got_grads = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(stream, weights)
        assert sown["buffer_rows"][0] == rows
        assert sown["expert_tokens"][0].sum() < rows  # there ARE rows past the last group
        want, want_grads = jax.value_and_grad(
            lambda x, w: jnp.sum(jnp.square(f["moe"](x, w))), argnums=(0, 1)
        )(stream, reference)
        assert float(got) == pytest.approx(float(want), rel=1e-5)
        np.testing.assert_allclose(got_grads[0], want_grads[0], rtol=2e-4, atol=2e-4)
        for name in ("experts_gate", "experts_up", "experts_down"):
            np.testing.assert_allclose(got_grads[1][name][held], want_grads[1][name], rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(got_grads[1]["router"], want_grads[1]["router"], rtol=2e-4, atol=2e-4)

    def test_gradients_of_the_share_match_the_reference(self, family, stream):
        weights = _layer_weights(jax.random.PRNGKey(8))
        f = family._layer_functions(family.shape_of(LAYER_CONFIG), "f32", None, *stream.shape[:2])

        def program(x, w):
            out, _, _ = _share(w, 0, 16, f["rms_norm"](x, w["norm2"]))
            return jnp.sum(jnp.square(x + out))

        got = jax.grad(program, argnums=(0, 1))(stream, weights)
        want = jax.grad(lambda x, w: jnp.sum(jnp.square(f["moe"](x, w))), argnums=(0, 1))(stream, weights)
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)

    # -- the sorted buffer follows the rows held

    @pytest.mark.parametrize("routing", sorted(planted.ROUTINGS))
    def test_every_rung_computes_what_the_full_buffer_does(self, routing):
        """Routings planted through the router's matrix (sigmoid scores): on
        each rung, a row short of the short rung's length and on it, every
        assignment held, all on one expert."""
        assert buffer_rungs(planted.TOKENS, LADDER_SIZES) == planted.RUNGS
        h, router = planted.planted_stream(routing, LADDER_SIZES.d_model)
        sown = planted.assert_matches_full_buffer(
            LADDER_SIZES, planted.layer_params(LADDER_SIZES, router), h, None,
            rows=planted.ROUTINGS[routing][1], held=planted.held_assignments(routing),
        )
        if routing == "all-on-one-held-expert":
            assert sown["expert_tokens"][0].tolist() == [planted.TOKENS - 1, 0, 0, 0]

    def test_a_rung_too_short_is_counted_as_dropped(self, monkeypatch, fresh_traces):
        """Were the choice of rung wrong, the counter says so: the products
        are given only the rows that lie inside the rung that ran."""
        monkeypatch.setattr(mla_moe, "buffer_rungs", lambda tokens, sizes: (128, 512))
        h, router = planted.planted_stream("most-assignments", LADDER_SIZES.d_model)
        layer = ExpertLayer(LADDER_SIZES, jnp.float32)
        _, sown = layer.apply({"params": planted.layer_params(LADDER_SIZES, router)}, h, mutable=[ROUTING])
        counters = MlaMoeLM.step_counters(sown[ROUTING])
        assert counters["moe_buffer_rows"] == 512
        assert counters["moe_tokens_dropped"] == planted.held_assignments("most-assignments") - 512 > 0

    @pytest.mark.parametrize("held,branches", [((0, 32), False), (planted.HELD, True)], ids=["uncut", "share"])
    def test_a_layer_that_holds_every_expert_has_no_branch(self, held, branches):
        """All experts held: every row is held, one rung, the program without
        a choice, forward and backward."""
        sizes = dataclasses.replace(LADDER_SIZES, experts_held=held)
        h, router = planted.planted_stream("short-rung", sizes.d_model)
        params = planted.layer_params(sizes, router)
        layer = ExpertLayer(sizes, jnp.float32)
        loss = lambda h, p: jnp.sum(layer.apply({"params": p}, h))  # noqa: E731
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, params))
        assert ("cond[" in text) == branches
        assert len(buffer_rungs(planted.TOKENS, sizes)) == (2 if branches else 1)

    @pytest.mark.parametrize(
        "tokens,sizes,rungs",
        [
            (8192, dict(n_experts=128, experts_per_token=6, experts_held=(0, 16)), (12288, 49152)),
            (16384, dict(n_experts=64, experts_per_token=6, experts_held=(0, 8)), (24576, 98304)),
            (48, dict(n_experts=16, experts_per_token=3, experts_held=(4, 4)), (128, 144)),
            (48, dict(n_experts=16, experts_per_token=3, experts_held=(0, 8)), (144,)),
            (100, dict(n_experts=64, experts_per_token=2, experts_held=(3, 5)), (128, 200)),
        ],
        ids=["kanana2-cell", "smallthinker-cell", "quarter", "half", "rounded-up"],
    )
    def test_rungs_come_from_the_shapes(self, tokens, sizes, rungs):
        assert buffer_rungs(tokens, MlaMoeSizes(**sizes)) == rungs


# ---------------------------------------------------------------------------
# the widened kernel
# ---------------------------------------------------------------------------

class TestWidenedKernel:
    @staticmethod
    def _qkv(d_k=48, d_v=32, s=128, dtype=jnp.float32):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 2, s, d_k), dtype)
        k = jax.random.normal(ks[1], (1, 2, s, d_k), dtype)
        v = jax.random.normal(ks[2], (1, 2, s, d_v), dtype)
        return q, k, v

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_with_narrower_values(self, causal):
        q, k, v = self._qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        assert out.shape == v.shape[:3] + (32,)
        np.testing.assert_allclose(out, reference_attention(q, k, v, causal=causal), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("argnum", [0, 1, 2], ids=["dq", "dk", "dv"])
    def test_gradients_with_narrower_values(self, argnum):
        q, k, v = self._qkv()
        weight = jax.random.normal(jax.random.PRNGKey(9), v.shape, jnp.float32)
        loss = lambda fn: lambda *a: jnp.sum(fn(*a) * weight)  # noqa: E731
        got = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, block_q=64, block_k=64)), argnum)(q, k, v)
        want = jax.grad(loss(lambda q, k, v: reference_attention(q, k, v)), argnum)(q, k, v)
        assert got.shape == (q, k, v)[argnum].shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_scale_comes_from_the_query_width(self):
        q, k, v = self._qkv()
        out = flash_attention(q, k, v, block_q=64, block_k=64)
        scaled = flash_attention(q, k, v, sm_scale=1.0 / np.sqrt(48), block_q=64, block_k=64)
        np.testing.assert_array_equal(out, scaled)


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
    "block": "mla_moe", "vocab_size": 64, "seq_len": 32, "n_seq": 40, "batch_size": 4, "steps": 3,
    "d_model": 64, "n_heads": 2, "n_layers": 2, "qk_nope_dim": 16, "qk_rope_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 24, "dense_width": 96, "expert_width": 32, "n_experts": 8, "experts_per_token": 2,
    "experts_held_first": 2, "experts_held": 4, "routed_scaling": 2.0, "lr": 1e-3,
}


class TestNormalPath:
    def test_fields_hash_and_equal_sizes_are_one_key(self):
        a = transformer._mla_moe_model(dict(TRIAL), 64, None)
        b = transformer._mla_moe_model(dict(TRIAL), 64, None)
        other = transformer._mla_moe_model({**TRIAL, "experts_held_first": 3}, 64, None)
        assert a == b and hash(a) == hash(b) and a != other
        assert a.sizes.experts_held == (2, 4) and isinstance(a.sizes.experts_held, tuple)
        assert a.sizes.routed_scaling == 2.0 and a.sizes.n_experts == 8

    def test_second_trial_of_the_structure_reuses_its_programs(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracer = tracing.Tracer(path)
        ctxs = [_Ctx({**TRIAL, "lr": lr}) for lr in (1e-3, 3e-4)]
        with tracing.use_tracer(tracer):
            for i, ctx in enumerate(ctxs):
                with tracing.span("train_fn", trial=f"t{i}") as sp:
                    for counter in tracing.JIT_COUNTERS:
                        sp.add(counter, 0)
                    transformer.transformer_trial(ctx)
        tracer.close()
        records = list(tracing.read_journal(path))
        inits = [r["args"] for r in records if r["name"] == "trial.init"]
        assert [a["block"] for a in inits] == ["mla_moe", "mla_moe"]
        assert inits[1]["programs"] == "reused"
        second = [r["args"] for r in records if r["name"] == "train_fn"][1]
        assert second["jit_programs"] == 0
        evals = [r["args"] for r in records if r["name"] == "trial.eval"]
        assert len(evals) == 4  # steps 0 and 2 of both trials
        for args in evals:
            assert args["moe_tokens_dropped"] == 0
            assert args["moe_assignments_total"] == 4 * 32 * 2  # one expert layer
            assert 0 <= args["moe_assignments_held"] <= args["moe_assignments_total"]
            assert args["moe_expert_tokens_max"] >= args["moe_expert_tokens_mean"]
            # 4 of 8 experts held: half the assignments expected, so one rung
            assert args["moe_buffer_rows"] == args["moe_assignments_total"]
        assert [a["expert_buffer"] for a in inits] == ["256", "256"]
        assert all(np.isfinite(r["eval_loss"]) for ctx in ctxs for r in ctx.reports)

    def test_example_runs_through_the_orchestrator(self, tmp_path):
        """Orchestrator.run -> trial runner -> transformer_trial -> train_lm."""
        from katib_tpu.orchestrator.orchestrator import Orchestrator
        from katib_tpu.sdk.yaml_spec import load_experiment_yaml

        spec = load_experiment_yaml(
            os.path.join(REPO, "examples", "hp-tuning", "transformer-mla-moe.yaml")
        )
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert exp.succeeded_count == 2 and exp.optimal is not None
        records = list(tracing.read_journal(str(tmp_path / spec.name / "trace.jsonl")))
        inits = [r["args"] for r in records if r["name"] == "trial.init"]
        assert [a["block"] for a in inits] == ["mla_moe"] * 2 and inits[1]["programs"] == "reused"
        evals = [r["args"] for r in records if r["name"] == "trial.eval"]
        assert evals and all(a["moe_tokens_dropped"] == 0 for a in evals)

    def test_gpt2_block_has_no_routing_counts(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracer = tracing.Tracer(path)
        params = {"vocab_size": 64, "seq_len": 32, "n_seq": 40, "batch_size": 4, "steps": 2, "d_model": 32}
        with tracing.use_tracer(tracer):
            transformer.transformer_trial(_Ctx(params))
        tracer.close()
        records = {r["name"]: r.get("args", {}) for r in tracing.read_journal(path)}
        assert records["trial.init"]["block"] == "gpt2"
        assert "expert_buffer" not in records["trial.init"]
        assert not any(k.startswith("moe_") for k in records["trial.eval"])

    def test_a_trial_of_a_small_share_runs_on_a_short_rung(self, tmp_path):
        """1 of 8 experts held, 2 a token: the ladder is on ``trial.init``,
        the rung that ran and the rows dropped on every ``trial.eval``."""
        path = str(tmp_path / "trace.jsonl")
        tracer = tracing.Tracer(path)
        params = {**TRIAL, "seq_len": 128, "batch_size": 4, "experts_held": 1, "steps": 2}
        with tracing.use_tracer(tracer):
            transformer.transformer_trial(_Ctx(params))
        tracer.close()
        records = list(tracing.read_journal(path))
        (init,) = [r["args"] for r in records if r["name"] == "trial.init"]
        assert init["expert_buffer"] == "256 / 1024"
        evals = [r["args"] for r in records if r["name"] == "trial.eval"]
        assert len(evals) == 2
        for args in evals:
            assert args["moe_tokens_dropped"] == 0
            assert args["moe_assignments_total"] == 1024
            assert args["moe_buffer_rows"] in (256, 1024)
            assert args["moe_assignments_held"] <= args["moe_buffer_rows"]

    @pytest.mark.parametrize(
        "bad,match",
        [
            ({"block": "mamba"}, "neither"),
            ({"experts_held_first": 6, "experts_held": 4}, "lie outside"),
            ({"dropout": 0.1}, "no dropout"),
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
            transformer._mla_moe_model(dict(TRIAL), 64, Mesh())


# ---------------------------------------------------------------------------
# the benchmark's reader of the buffer's counter
# ---------------------------------------------------------------------------


class TestBufferShareReader:
    @pytest.fixture(scope="class")
    def read(self):
        path = os.path.join(REPO, "benchmark", "layer_metrics", "moe_buffer_share.py")
        spec = importlib.util.spec_from_file_location("moe_buffer_share", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    @staticmethod
    def _ctx(*spans):
        class Slice:
            t0, t1 = 10.0, 20.0

        return {"slice": Slice, "spans": list(spans)}

    @staticmethod
    def _eval(t0, **args):
        return {"name": "trial.eval", "t0": t0, "t1": t0 + 0.1, "args": args}

    def test_averages_the_reports_inside_the_slice(self, read):
        ctx = self._ctx(
            self._eval(5.0, moe_buffer_rows=400, moe_assignments_total=400),  # the warm-up trial
            self._eval(11.0, moe_buffer_rows=100, moe_assignments_total=400),
            self._eval(15.0, moe_buffer_rows=200, moe_assignments_total=400),
            self._eval(19.95, moe_buffer_rows=400, moe_assignments_total=400),  # ends past the slice
            {"name": "trial.init", "t0": 12.0, "t1": 12.1, "args": {"moe_buffer_rows": 400, "moe_assignments_total": 400}},
        )
        assert read(ctx) == pytest.approx(0.375)

    @pytest.mark.parametrize(
        "args",
        [dict(moe_assignments_total=400, moe_tokens_dropped=0), dict(moe_buffer_rows=0, moe_assignments_total=0), dict(loss=1.0)],
        ids=["the-parent", "no-assignments", "no-experts"],
    )
    def test_none_where_the_program_has_no_such_counter(self, read, args):
        assert read(self._ctx(self._eval(11.0, **args))) is None

    def test_the_entry_names_both_expert_cells(self):
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            bench = json.load(f)
        (entry,) = [m for m in bench["per_layer"] if m["name"] == "moe_buffer_share"]
        assert entry == {
            "name": "moe_buffer_share", "unit": "ratio", "better": "lower", "source": "program_counter",
            "layer": "experts", "moves": "trials_per_hour",
            "workloads": ["kanana2-ep8-lr4low-steps12", "smallthinker-ep8-lr4low-steps12"],
        }
