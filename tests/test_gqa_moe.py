"""The third block family of ``transformer_trial`` (``block: gqa_moe``):
grouped-query attention in a period of layer kinds (a window with rotary
positions, the whole prefix with none), experts routed from the layer's input,
and what it asked of the flash kernel: a window and fewer key-value heads.

Against ``reference_attention_with_lse`` and the benchmark's plain reference
(``benchmark/families/gqa_moe.py``, loaded by path: the repo's one copy), at
tiny sizes on the CPU.
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
from katib_tpu.models import transformer
from katib_tpu.models.gqa_moe import GqaMoeLM, GqaMoeSizes
from katib_tpu.models.mla_moe import ROUTING, ExpertLayer, buffer_rungs, rotary
from katib_tpu.ops import flash_attention as fa
from katib_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def family():
    path = os.path.join(REPO, "benchmark", "families", "gqa_moe.py")
    spec = importlib.util.spec_from_file_location("benchmark_families_gqa_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the kernel: a window, and fewer key-value heads than query heads
# ---------------------------------------------------------------------------

SEQ, TILE = 128, 32
#: none; smaller than a tile; not a multiple of a tile; the sequence; longer
WINDOWS = {"none": None, "sub-tile": 20, "off-tile": 72, "sequence": SEQ, "longer": SEQ + 40}
HEADS = {"equal": (2, 2), "seven-to-one": (7, 1)}
WIDTHS = {"128-128": (128, 128), "192-128": (192, 128)}


def _f32(x):
    return x.astype(jnp.float32)


def _attention_inputs(h_q, h_kv, d_k, d_v, sq=SEQ, sk=SEQ, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(17), 5)
    q = jax.random.normal(ks[0], (1, h_q, sq, d_k), dtype)
    k = jax.random.normal(ks[1], (1, h_kv, sk, d_k), dtype)
    v = jax.random.normal(ks[2], (1, h_kv, sk, d_v), dtype)
    w_o = jax.random.normal(ks[3], (1, h_q, sq, d_v), jnp.float32)
    w_lse = jax.random.normal(ks[4], (1, h_q, sq), jnp.float32)
    return q, k, v, w_o, w_lse


def _outputs_and_grads(attn, q, k, v, w_o, w_lse):
    """Output, log-sum-exp, and dq, dk, dv of a loss that weighs both; rows
    that see no key leave the loss."""

    def loss(q, k, v):
        o, lse = attn(q, k, v)
        seen = lse > -1e20
        return jnp.sum(_f32(o) * w_o) + jnp.sum(jnp.where(seen, lse, 0.0) * w_lse), (o, lse)

    (_, (o, lse)), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (o, lse) + grads


class TestWindowedGroupedKernel:
    @pytest.mark.parametrize("widths", sorted(WIDTHS))
    @pytest.mark.parametrize("heads", sorted(HEADS))
    @pytest.mark.parametrize("window", sorted(WINDOWS))
    def test_forward_dq_dk_dv_match_the_reference(self, window, heads, widths):
        """Interpret mode, float32: the kernel and the dense reference differ
        by summation order only."""
        w = WINDOWS[window]
        q, k, v, w_o, w_lse = _attention_inputs(*HEADS[heads], *WIDTHS[widths])
        got = _outputs_and_grads(
            lambda q, k, v: fa.flash_attention_with_lse(q, k, v, True, None, TILE, TILE, True, w),
            q, k, v, w_o, w_lse,
        )
        want = _outputs_and_grads(
            lambda q, k, v: fa.reference_attention_with_lse(q, k, v, True, None, w), q, k, v, w_o, w_lse
        )
        assert got[3].shape == k.shape and got[4].shape == v.shape  # dk, dv at the key-value heads
        for name, g, x in zip(("o", "lse", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(g, x, rtol=0, atol=3e-5, err_msg=name)

    @pytest.mark.parametrize("window", [None, 24, 100])
    @pytest.mark.parametrize("sq,sk", [(64, 128), (128, 64)])
    def test_cross_lengths_keep_the_bottom_right_alignment(self, sq, sk, window):
        q, k, v, w_o, w_lse = _attention_inputs(4, 2, 16, 16, sq, sk)
        got = _outputs_and_grads(
            lambda q, k, v: fa.flash_attention_with_lse(q, k, v, True, None, 32, 16, True, window),
            q, k, v, w_o, w_lse,
        )
        want = _outputs_and_grads(
            lambda q, k, v: fa.reference_attention_with_lse(q, k, v, True, None, window), q, k, v, w_o, w_lse
        )
        seen = np.asarray(want[1]) > -1e20
        assert seen.any() and (sq <= sk or not seen.all())
        np.testing.assert_allclose(np.asarray(got[1])[seen], np.asarray(want[1])[seen], atol=3e-5)
        for g, x in zip((got[0],) + got[2:], (want[0],) + want[2:]):
            np.testing.assert_allclose(g, x, rtol=0, atol=3e-5)

    def test_bfloat16_operands_stay_within_their_rounding(self):
        q, k, v, w_o, w_lse = _attention_inputs(7, 1, 128, 128, dtype=jnp.bfloat16)
        got = _outputs_and_grads(
            lambda q, k, v: fa.flash_attention_with_lse(q, k, v, True, None, TILE, TILE, True, 72),
            q, k, v, w_o, w_lse,
        )
        want = _outputs_and_grads(
            lambda q, k, v: fa.reference_attention_with_lse(q, k, v, True, None, 72),
            _f32(q), _f32(k), _f32(v), w_o, w_lse,
        )
        assert got[0].dtype == got[3].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
        for name, g, x in zip(("o", "lse", "dq", "dk", "dv"), got, want):
            top = max(float(jnp.max(jnp.abs(x))), 1.0)
            # 2**-6 of the largest value (test_attention_transformer.py says
            # why); dk and dv sum 7 heads' bfloat16-rounded parts in float32
            tol = 1e-5 * top if name == "lse" else 2.0**-6 * top
            np.testing.assert_allclose(_f32(g), x, rtol=0, atol=tol, err_msg=name)

    @pytest.mark.parametrize("backward", ["one walk", "dq+dkv"])
    def test_the_window_is_one_more_static_argument_of_the_same_kernels(self, backward, monkeypatch):
        """No window and equal heads: the forward's three-axis grid and the
        walk's four, a key-value head's query heads (one) and the q tiles in
        order; where dq and dkv run (here: ``one_walk`` told to refuse), three
        three-axis grids and no scratch, the program the kernel lowered to
        before it learned of windows, groups or the walk."""
        walk = backward == "one walk"
        monkeypatch.setattr(fa, "one_walk", lambda *shape_and_tiles: walk)
        q, k, v, _, _ = _attention_inputs(2, 2, 16, 16)
        grad = jax.grad(lambda q, k, v: fa.flash_attention(q, k, v, block_q=32, block_k=32, interpret=True).sum(), (0, 1, 2))
        plain = str(jax.make_jaxpr(grad)(q, k, v))
        q7 = jnp.tile(q[:, :1], (1, 14, 1, 1))
        grouped = str(jax.make_jaxpr(grad)(q7, k, v))
        if walk:
            assert plain.count("grid=(1, 2, 4)") == 1 and plain.count("grid=(1, 2, 1, 4)") == 1
            assert grouped.count("grid=(1, 14, 4)") == 1  # forward at the query heads
            assert "grid=(1, 2, 7, 4)" in grouped  # the walk: a key-value head's 7 query heads, their q tiles innermost
            # the heads of a group and the q tiles add into one pair of accumulators
            assert plain.count("'arbitrary'") == grouped.count("'arbitrary'") == 2
            return
        assert plain.count("grid=(1, 2, 4)") == 3 and "arbitrary" not in plain  # nothing summed over a grid axis
        assert "grid=(1, 2, 4, 7)" in grouped  # dkv: a key-value head's 7 query heads innermost
        assert grouped.count("grid=(1, 14, 4)") == 2  # forward and dq at the query heads
        assert grouped.count("'arbitrary'") == 1  # that axis alone is summed over

    @pytest.mark.parametrize(
        "bad,match",
        [
            (dict(window=0), "window"),
            (dict(window=8, causal=False), "window"),
        ],
    )
    def test_refusals(self, bad, match):
        q, k, v, _, _ = _attention_inputs(2, 2, 16, 16)
        with pytest.raises(ValueError, match=match):
            fa.flash_attention(q, k, v, interpret=True, **bad)
        with pytest.raises(ValueError, match=match):
            fa.reference_attention(q, k, v, **bad)

    def test_heads_that_do_not_divide_are_refused(self):
        q, k, v, _, _ = _attention_inputs(5, 2, 16, 16)
        with pytest.raises(ValueError, match="not a multiple"):
            fa.flash_attention(q, k, v, interpret=True)


def _tiles_by_mask(sq, sk, bq, bk, window):
    """Tiles that hold a visible pair, from the dense mask itself."""
    r, c = np.arange(sq)[:, None], np.arange(sk)[None, :]
    seen = c <= r + (sk - sq)
    if window is not None:
        seen &= c > r + (sk - sq) - window
    return seen.reshape(sq // bq, bq, sk // bk, bk).any(axis=(1, 3))


class TestTileBounds:
    @pytest.mark.parametrize("window", [None, 1, 20, 32, 33, 72, 128, 500])
    @pytest.mark.parametrize("sq,sk,bq,bk", [(128, 128, 32, 32), (128, 128, 64, 16), (64, 128, 16, 32), (128, 64, 32, 32)])
    def test_loop_bounds_are_exactly_the_tiles_that_hold_a_visible_pair(self, sq, sk, bq, bk, window):
        holds = _tiles_by_mask(sq, sk, bq, bk, window)
        n_qb, n_kb, shift = sq // bq, sk // bk, sk - sq
        for tile_range, n_tiles, n_across, live_of in (
            (fa._k_tile_range, n_qb, n_kb, lambda i: np.flatnonzero(holds[i])),
            (fa._q_tile_range, n_kb, n_qb, lambda i: np.flatnonzero(holds[:, i])),
        ):
            first, end = tile_range(jnp.arange(n_tiles), bq, bk, n_across, shift, True, window)
            first, end = np.broadcast_to(first, (n_tiles,)), np.broadcast_to(end, (n_tiles,))
            for i in range(n_tiles):
                live = live_of(i)
                if live.size:  # contiguous, and the loop walks exactly them
                    assert (first[i], end[i]) == (live[0], live[-1] + 1) and live.size == end[i] - first[i]
                else:
                    assert end[i] <= first[i]
        # forward and the one walk back; forward, dq and dkv where those run
        assert fa.tile_visits(sq, sk, bq, bk, True, window, walk=True) == (2 * int(holds.sum()),) * 2
        assert fa.tile_visits(sq, sk, bq, bk, True, window, walk=False) == (3 * int(holds.sum()),) * 2

    def test_counts_at_the_benchmark_cell(self):
        """16384 positions in 512 x 512 tiles: 528 tiles under the diagonal,
        252 of them inside a 4096-key window (9 a q tile from the ninth on)."""
        assert fa.plan_tiles(16384, 16384, 128, 128, jnp.bfloat16) == (512, 512)
        assert fa.one_walk(16384, 16384, 128, 128, jnp.bfloat16, 512, 512)  # two walks a step: forward, backward
        assert fa.tile_visits(16384, 16384, 512, 512, True, None, walk=True) == (2 * 528, 2 * 528)
        assert fa.tile_visits(16384, 16384, 512, 512, True, 4096, walk=True) == (2 * 252, 2 * 252)
        assert fa.tile_visits(16384, 16384, 512, 512, True, 4096, walk=False) == (3 * 252, 3 * 252)
        assert fa.tile_visits(1024, 1024, 512, 512, False, None, walk=True) == (8, 8)
        # window layers that walked the whole triangle would read 1.64
        assert 4 * 528 / (528 + 3 * 252) == pytest.approx(1.645, abs=1e-3)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

# the configuration file's keys, as the family reads them: one period, layer 0
# full without positions, layers 1-3 a window of 8 (shorter than the 32
# positions) with rotary; 6 query heads over 2 key-value heads
CONFIG = {
    "hidden_size": 48, "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 4, "sliding_window_size": 8, "moe_ffn_hidden_size": 32, "router_width": 16,
    "moe_num_active_primary_experts": 3, "experts_held_first": 4, "moe_num_primary_experts": 8,
    "vocab_size": 96, "seq_len": 32, "batch_size": 4, "n_seq": 48,
    "rope_theta": 1500000, "rms_norm_eps": 1e-06,
    "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1], "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1],
}


def _trial_params(family, config=CONFIG) -> dict:
    """The parameters the family's experiment document pins, as a trial gets them."""
    doc = family.experiment_doc("t", config, {"parameters": [], "steps": 3, "algorithm": "random", "parallelTrialCount": 1, "maxTrialCount": 1}, 0)
    out = {}
    for p in doc["spec"]["parameters"]:
        space = p["feasibleSpace"]
        value = space["list"][0] if "list" in space else space["min"]
        out[p["name"]] = {"int": int, "discrete": float, "categorical": str}[p["parameterType"]](value)
    return out


def _model(family, config=CONFIG, dtype=jnp.float32) -> GqaMoeLM:
    model = transformer._gqa_moe_model(_trial_params(family, config), config["vocab_size"], None)
    return model.clone(dtype=dtype)


def _as_reference(params) -> dict:
    """The program's parameter tree (or a gradient of its shape) under the
    reference's names."""
    tree = params["params"]
    layers = []
    for i in range(sum(k.startswith("layer_") for k in tree)):
        layer = tree[f"layer_{i}"]
        layers.append(
            {
                "router": layer["router"],
                "norm1": layer["input_norm"]["scale"],
                "norm2": layer["post_attn_norm"]["scale"],
                **{n: layer["attn"][f"{n}_proj"]["kernel"] for n in ("q", "k", "v", "o")},
                **layer["moe"],
            }
        )
    return {
        "embed": tree["embed"]["embedding"], "norm": tree["norm"]["scale"],
        "head": tree["head"]["kernel"], "layers": layers,
    }


def _seeded(family, config=CONFIG, seed=3):
    """Seeded weights away from the initial ones (norm scales not 1), in the
    program's tree."""
    model = _model(family, config)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, config["seq_len"]), jnp.int32))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    return model, jax.tree_util.tree_unflatten(treedef, leaves)


class TestAgainstReference:
    def test_the_model_is_the_one_the_config_describes(self, family):
        model = _model(family)
        z = model.sizes
        assert (z.n_heads, z.n_kv_heads, z.head_dim, z.window) == (6, 2, 16, 8)
        assert z.window_layout == z.rope_layout == (0, 1, 1, 1) and z.experts_held == (4, 8)
        assert [z.layer_kind(i) for i in range(4)] == [(False, False)] + [(True, True)] * 3
        assert model.attn_kinds == [(None, "nope", 1), (8, "rope", 3)]
        assert (z.scoring, z.expert_act, z.n_shared_experts, z.routed_scaling) == ("softmax", "relu", 0, 1.0)

    def test_initial_weights_are_the_references(self, family):
        model = _model(family)
        programs, _ = transformer._programs_for(model, 1.0, None)
        got = _as_reference(programs.init(jax.random.PRNGKey(0), CONFIG["seq_len"]).params)
        want = family.init_params(CONFIG)
        assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(g, w)
        assert "router" not in programs.init(jax.random.PRNGKey(0), 32).params["params"]["layer_1"]["moe"]

    def test_logits_follow_the_reference(self, family):
        model, params = _seeded(family)
        tokens = jnp.asarray(family.markov_tokens(96, 4, 32, 5))
        got = model.apply(params, tokens)
        x, f = family._stream(_as_reference(params), tokens, family.shape_of(CONFIG), "f32", None)
        want = f["mm"]("rsd,dv->rsv", x, params["params"]["head"]["kernel"])
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_gradients_follow_the_reference(self, family):
        model, params = _seeded(family)
        tokens = jnp.asarray(family.markov_tokens(96, 4, 32, 6))
        loss, grads = jax.value_and_grad(lambda p: transformer.lm_loss(model.apply(p, tokens), tokens))(params)
        want_loss, want = jax.value_and_grad(family._forward)(
            _as_reference(params), tokens, family.shape_of(CONFIG), "f32", None
        )
        assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
        got = _as_reference(grads)
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
            scale = float(jnp.max(jnp.abs(w))) + 1e-8
            np.testing.assert_allclose(g, w, rtol=0, atol=3e-4 * scale, err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("fault", ["no_window", "no_routed"])
    def test_a_planted_fault_moves_the_reference(self, family, fault):
        """The window shorter than the sequence and the routed sum both reach
        the loss: the reference with either left out is another function."""
        _, params = _seeded(family)
        tokens = jnp.asarray(family.markov_tokens(96, 4, 32, 6))
        shape = family.shape_of(CONFIG)
        good = float(family._forward(_as_reference(params), tokens, shape, "f32", None))
        bad = float(family._forward(_as_reference(params), tokens, shape, "f32", fault))
        assert abs(bad - good) > 1e-3 * abs(good)

    @pytest.mark.parametrize("windowed,rope", [(False, False), (True, True), (True, False), (False, True)])
    def test_a_layer_of_each_kind_follows_the_reference(self, family, windowed, rope):
        """One layer, logits: full or windowed, with or without positions."""
        config = {
            **CONFIG, "num_hidden_layers": 1,
            "sliding_window_layout": [int(windowed)], "rope_layout": [int(rope)],
        }
        model, params = _seeded(family, config, seed=11)
        assert model.sizes.layer_kind(0) == (windowed, rope)
        tokens = jnp.asarray(family.markov_tokens(96, 2, 32, 9))
        x, f = family._stream(_as_reference(params), tokens, family.shape_of(config), "f32", None)
        want = f["mm"]("rsd,dv->rsv", x, params["params"]["head"]["kernel"])
        np.testing.assert_allclose(model.apply(params, tokens), want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("lr", [1e-3, 1e-4])
    def test_loss_and_gradient_norm_follow_the_reference_through_train_lm(self, family, lr):
        steps, seed = 4, 7
        model = _model(family)
        data = family.markov_tokens(CONFIG["vocab_size"], CONFIG["n_seq"], CONFIG["seq_len"], seed)
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
            lambda p, t: optax.global_norm(jax.grad(lambda q: transformer.lm_loss(model.apply(q, t), t))(p))
        )
        for s in range(steps):
            tokens = jnp.asarray(rows[s])
            got_norm = float(grad_norm(state.params, tokens))
            state, _, _ = programs.step_fn(state, tokens, jax.random.PRNGKey(1), *schedule)
            params, m, v, loss, want_norm = step(
                params, m, v, jnp.int32(s), jnp.float32(family.lr_at(s, lr, steps)), tokens
            )
            if s in (0, 3):
                assert got_norm == pytest.approx(float(want_norm), rel=2e-4)
                assert reported[s][0] == pytest.approx(float(loss), rel=2e-5)
                assert reported[s][1] == pytest.approx(float(eval_loss(params, jnp.asarray(eval_rows))), rel=2e-5)

    def test_rotary_halves_paired(self):
        """x[i] turns with x[i + R/2] by pos * theta**(-2i/R)."""
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 2, 8), jnp.float32)
        got = np.asarray(rotary(x, 100.0, interleaved=False))
        for pos in range(5):
            for i in range(4):
                a, b = float(x[0, pos, 1, i]), float(x[0, pos, 1, i + 4])
                angle = pos * 100.0 ** (-2 * i / 8)
                assert got[0, pos, 1, i] == pytest.approx(a * np.cos(angle) - b * np.sin(angle), abs=1e-5)
                assert got[0, pos, 1, i + 4] == pytest.approx(b * np.cos(angle) + a * np.sin(angle), abs=1e-5)


# ---------------------------------------------------------------------------
# the expert layer, shared with block mla_moe: softmax router, ReLU gate,
# logits handed in, no shared expert
# ---------------------------------------------------------------------------

LAYER_SIZES = GqaMoeSizes(d_model=48, n_experts=16, experts_per_token=3, expert_width=32, experts_held=(0, 16))
LAYER_CONFIG = {**CONFIG, "experts_held_first": 0, "moe_num_primary_experts": 16, "num_hidden_layers": 1}


def _expert_weights(key):
    d, w, n = LAYER_SIZES.d_model, LAYER_SIZES.expert_width, LAYER_SIZES.n_experts
    ks = jax.random.split(key, 4)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])  # noqa: E731
    return {
        "norm2": jnp.ones((d,)), "router": normal(ks[0], d, n),
        "experts_gate": normal(ks[1], n, d, w), "experts_up": normal(ks[2], n, d, w),
        "experts_down": normal(ks[3], n, w, d),
    }


def _share(weights, first, count, h, logits):
    layer = ExpertLayer(dataclasses.replace(LAYER_SIZES, experts_held=(first, count)), jnp.float32)
    held = slice(first, first + count)
    params = {k: weights[k][held] for k in ("experts_gate", "experts_up", "experts_down")}
    out, sown = layer.apply({"params": params}, h, logits, mutable=[ROUTING])
    return out, sown[ROUTING]


class TestSharedExpertLayer:
    @pytest.fixture()
    def stream(self):
        return jax.random.normal(jax.random.PRNGKey(11), (2, 24, LAYER_SIZES.d_model), jnp.float32)

    def test_eight_shares_add_up_to_the_uncut_reference_layer(self, family, stream):
        """16 experts in 8 shares of 2, the router's logits handed to every
        share alike: their parts, and the residual once, are the whole layer
        as the reference computes it."""
        weights = _expert_weights(jax.random.PRNGKey(5))
        f = family._layer_functions(family.shape_of(LAYER_CONFIG), "f32", None, *stream.shape[:2])
        logits = f["router"](stream, weights)
        want = f["moe"](stream, weights, logits)
        h = f["rms_norm"](stream, weights["norm2"])
        total, rows = stream, 0
        for i in range(8):
            out, sown = _share(weights, 2 * i, 2, h, logits)
            total = total + out
            rows += int(sown["expert_tokens"][0].sum())
            assert sown["assignments"][0][1] == 48 * 3
        np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
        assert rows == 48 * 3  # every assignment computed by exactly one share

    def test_gradients_of_a_share_match_the_reference(self, family, stream):
        weights = _expert_weights(jax.random.PRNGKey(8))
        config = {**LAYER_CONFIG, "experts_held_first": 4, "moe_num_primary_experts": 8}
        f = family._layer_functions(family.shape_of(config), "f32", None, *stream.shape[:2])
        cut = lambda w: {**w, **{k: w[k][4:12] for k in ("experts_gate", "experts_up", "experts_down")}}  # noqa: E731

        def program(x, w):
            out, _ = _share(w, 4, 8, f["rms_norm"](x, w["norm2"]), f["router"](x, w))
            return jnp.sum(jnp.square(x + out))

        def reference(x, w):
            return jnp.sum(jnp.square(f["moe"](x, cut(w), f["router"](x, w))))

        got = jax.grad(program, argnums=(0, 1))(stream, weights)
        want = jax.grad(reference, argnums=(0, 1))(stream, weights)
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("routing", sorted(planted.ROUTINGS))
    def test_every_rung_computes_what_the_full_buffer_does(self, routing):
        """The logits handed in, planted (softmax over the chosen, ReLU gate,
        no shared expert): on each rung, a row short of the short rung's
        length and on it, every assignment held, all on one expert; the
        logits' gradient among those compared."""
        sizes = dataclasses.replace(LAYER_SIZES, n_experts=32, experts_held=planted.HELD)
        assert buffer_rungs(planted.TOKENS, sizes) == planted.RUNGS
        h = jax.random.normal(jax.random.PRNGKey(12), (2, planted.TOKENS // 2, sizes.d_model), jnp.float32)
        logits = jnp.asarray(planted.planted_logits(routing)).reshape(2, planted.TOKENS // 2, 32)
        planted.assert_matches_full_buffer(
            sizes, planted.layer_params(sizes), h, logits,
            rows=planted.ROUTINGS[routing][1], held=planted.held_assignments(routing),
        )

    def test_weights_are_a_softmax_over_the_chosen_logits(self, stream):
        """With every expert's down projection the identity-like sum of its
        hidden units replaced by ones, the layer's output reads the weights:
        they sum to 1 over a token's chosen experts (no scaling)."""
        sizes = dataclasses.replace(LAYER_SIZES, expert_width=1)
        d, n = sizes.d_model, sizes.n_experts
        params = {
            "experts_gate": jnp.zeros((n, d, 1)).at[:, 0, 0].set(1.0),
            "experts_up": jnp.zeros((n, d, 1)).at[:, 1, 0].set(1.0),
            "experts_down": jnp.ones((n, 1, d)),
        }
        h = jnp.ones_like(stream)  # relu(1) * 1 = 1 from every expert
        logits = jax.random.normal(jax.random.PRNGKey(4), (2, 24, n))
        out = ExpertLayer(sizes, jnp.float32).apply({"params": params}, h, logits)
        np.testing.assert_allclose(out, jnp.ones_like(out), rtol=1e-5)

    def test_an_unknown_scoring_is_refused(self, stream):
        class Sizes(GqaMoeSizes):
            scoring = "tanh"

        with pytest.raises(ValueError, match="neither 'sigmoid' nor 'softmax'"):
            ExpertLayer(Sizes(d_model=48), jnp.float32).init(
                jax.random.PRNGKey(0), stream, jnp.zeros((2, 24, 16))
            )


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
    "block": "gqa_moe", "vocab_size": 64, "seq_len": 32, "n_seq": 40, "batch_size": 4, "steps": 3,
    "d_model": 48, "n_heads": 6, "n_kv_heads": 2, "head_dim": 8, "n_layers": 4, "window": 8,
    "window_layout": "0111", "rope_layout": "0111", "expert_width": 32, "n_experts": 8,
    "experts_per_token": 2, "experts_held_first": 2, "experts_held": 4, "lr": 1e-3,
}


class TestNormalPath:
    def test_fields_hash_and_equal_sizes_are_one_key(self):
        a = transformer._gqa_moe_model(dict(TRIAL), 64, None)
        b = transformer._gqa_moe_model(dict(TRIAL), 64, None)
        assert a == b and hash(a) == hash(b)
        assert a.attn_fn is b.attn_fn and a.window_attn_fn is b.window_attn_fn
        assert a.window_attn_fn.window == 8 and a.attn_fn.window is None
        for other in ({"window": 16}, {"window_layout": "0101"}, {"rope_layout": "1111"}, {"n_kv_heads": 3}):
            assert transformer._gqa_moe_model({**TRIAL, **other}, 64, None) != a
        p1, reused1 = transformer._programs_for(a, 1.0, None)
        p2, reused2 = transformer._programs_for(b, 1.0, None)
        assert p1 is p2 and reused2

    def test_second_trial_of_the_structure_reuses_its_programs(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracer = tracing.Tracer(path)
        ctxs = [_Ctx({**TRIAL, "lr": lr, "window": 7}) for lr in (1e-3, 3e-4)]
        with tracing.use_tracer(tracer):
            for i, ctx in enumerate(ctxs):
                with tracing.span("train_fn", trial=f"t{i}") as sp:
                    for counter in tracing.JIT_COUNTERS:
                        sp.add(counter, 0)
                    transformer.transformer_trial(ctx)
        tracer.close()
        records = list(tracing.read_journal(path))
        inits = [r["args"] for r in records if r["name"] == "trial.init"]
        assert [a["block"] for a in inits] == ["gqa_moe", "gqa_moe"]
        assert inits[0]["attn_layers"] == "full nope x1, window7 rope x3"
        assert inits[0]["attn_tiles"] == "dense" and "attn_tiles_run" not in inits[0]  # no kernel on the CPU
        assert inits[0]["programs"] == "built" and inits[1]["programs"] == "reused"
        second = [r["args"] for r in records if r["name"] == "train_fn"][1]
        assert second["jit_programs"] == 0
        evals = [r["args"] for r in records if r["name"] == "trial.eval"]
        assert len(evals) == 4  # steps 0 and 2 of both trials
        for args in evals:
            assert args["moe_tokens_dropped"] == 0
            assert args["moe_assignments_total"] == 4 * 4 * 32 * 2  # four expert layers
            assert 0 <= args["moe_assignments_held"] <= args["moe_assignments_total"]
            assert args["moe_expert_tokens_max"] >= args["moe_expert_tokens_mean"]
        assert all(np.isfinite(r["eval_loss"]) for ctx in ctxs for r in ctx.reports)

    @pytest.mark.parametrize("held", [1, 4], ids=["ladder", "one-rung"])
    def test_a_data_mesh_chooses_the_rung_every_device_alike(self, tmp_path, held):
        """Over a ``data`` axis the step is one GSPMD program: the rows held
        are a global count, so every device takes the same branch, and the
        losses are those of one device."""
        from katib_tpu.parallel.mesh import DATA_AXIS, make_mesh

        trial = {**TRIAL, "seq_len": 64, "experts_held": held, "n_layers": 2, "steps": 2}
        mesh = make_mesh({DATA_AXIS: 2}, devices=jax.devices()[:2])
        data = transformer.markov_dataset(64, 40, 64, seed=5)
        series = {}
        for name, m in (("one", None), ("mesh", mesh)):
            model = transformer._gqa_moe_model(dict(trial), 64, m).clone(dtype=jnp.float32)
            path = str(tmp_path / f"{name}.jsonl")
            tracer, reported = tracing.Tracer(path), []
            with tracing.use_tracer(tracer):
                transformer.train_lm(
                    model, data, lr=1e-3, steps=2, batch_size=4, mesh=m, report_every=1,
                    report=lambda step, loss, eval_loss: reported.append((loss, eval_loss)),
                )
            tracer.close()
            records = list(tracing.read_journal(path))
            series[name] = (reported, [r["args"] for r in records if r["name"] == "trial.eval"])
            (init,) = [r["args"] for r in records if r["name"] == "trial.init"]
            assert init["expert_buffer"] == ("128 / 512" if held == 1 else "512")
        np.testing.assert_allclose(series["mesh"][0], series["one"][0], rtol=2e-5)
        for got, want in zip(series["mesh"][1], series["one"][1]):
            assert got["moe_tokens_dropped"] == 0
            assert got["moe_buffer_rows"] == want["moe_buffer_rows"]
            assert got["moe_assignments_held"] == want["moe_assignments_held"]

    def test_example_runs_through_the_orchestrator(self, tmp_path):
        """Orchestrator.run -> trial runner -> transformer_trial -> train_lm."""
        from katib_tpu.orchestrator.orchestrator import Orchestrator
        from katib_tpu.sdk.yaml_spec import load_experiment_yaml

        spec = load_experiment_yaml(os.path.join(REPO, "examples", "hp-tuning", "transformer-gqa-moe.yaml"))
        exp = Orchestrator(workdir=str(tmp_path)).run(spec)
        assert exp.succeeded_count == 2 and exp.optimal is not None
        records = list(tracing.read_journal(str(tmp_path / spec.name / "trace.jsonl")))
        inits = [r["args"] for r in records if r["name"] == "trial.init"]
        assert [a["block"] for a in inits] == ["gqa_moe"] * 2 and inits[1]["programs"] == "reused"
        assert inits[0]["attn_layers"] == "full nope x1, window8 rope x3"
        evals = [r["args"] for r in records if r["name"] == "trial.eval"]
        assert evals and all(a["moe_tokens_dropped"] == 0 for a in evals)

    def test_trial_init_counts_the_tiles_where_the_kernel_runs(self, tmp_path):
        """On the chip ``make_attention_fn`` gives the kernel: at the cell's
        sizes the loops walk exactly the tiles that hold a visible pair."""
        kernel = transformer._single_device_attention
        cell = GqaMoeLM(
            vocab_size=64,
            sizes=GqaMoeSizes(n_heads=28, n_kv_heads=4, head_dim=128, n_layers=4, window=4096),
            attn_fn=kernel(True), window_attn_fn=kernel(True, 4096),
        )
        attrs, counters = transformer.attention_plan(cell, 1, 16384)
        assert attrs == {
            "attn_layers": "full nope x1, window4096 rope x3", "attn_tiles": "bfloat16 q512 k512, backward one walk",
            "remat": "blocks, keeps attn out+lse",
        }
        assert counters == {"attn_tiles_run": 28 * 2 * (528 + 3 * 252), "attn_tiles_needed": 28 * 2 * (528 + 3 * 252)}
        small = transformer.TransformerLM(vocab_size=50257, d_model=768, n_heads=12, n_layers=12, attn_fn=kernel(True))
        attrs, counters = transformer.attention_plan(small, 8, 1024)
        assert attrs["attn_layers"] == "full learned x12" and counters["attn_tiles_run"] == counters["attn_tiles_needed"] == 8 * 12 * 12 * 6
        # and they reach the span as counters, the enclosing spans too
        path = str(tmp_path / "trace.jsonl")
        tracer = tracing.Tracer(path)
        tiny = GqaMoeLM(
            vocab_size=64, dtype=jnp.float32,
            sizes=GqaMoeSizes(d_model=32, n_heads=2, n_kv_heads=1, head_dim=128, n_layers=2, window=128, n_experts=4, experts_held=(0, 4)),
            attn_fn=kernel(True), window_attn_fn=kernel(True, 128),
        )
        data = transformer.markov_dataset(64, 12, 256, seed=1)
        with tracing.use_tracer(tracer), tracing.span("train_fn", trial="t0"):
            transformer.train_lm(tiny, data, lr=1e-3, steps=1, batch_size=1)
        tracer.close()
        records = {r["name"]: r["args"] for r in tracing.read_journal(path)}
        init = records["trial.init"]
        assert init["attn_tiles"] == "float32 q256 k256, backward one walk" and init["attn_layers"] == "full nope x1, window128 rope x1"
        assert init["attn_tiles_run"] == init["attn_tiles_needed"] == 2 * 2 * 2
        assert records["train_fn"]["attn_tiles_run"] == init["attn_tiles_run"]

    @pytest.mark.parametrize(
        "bad,match",
        [
            ({"window_layout": "0121"}, "not a string of 0 and 1"),
            ({"rope_layout": ""}, "not a string of 0 and 1"),
            ({"n_kv_heads": 4}, "not a multiple"),
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
            transformer._gqa_moe_model(dict(TRIAL), 64, Mesh())
        with pytest.raises(ValueError, match="no windowed attention over a mesh"):
            transformer.make_attention_fn(Mesh(), window=8)


# ---------------------------------------------------------------------------
# the family's counts, and the configuration file
# ---------------------------------------------------------------------------


class TestFamilyCounts:
    @pytest.fixture(scope="class")
    def config(self):
        with open(os.path.join(REPO, "benchmark", "configs", "smallthinker-21b-a3b-ep8.json")) as f:
            return json.load(f)

    def test_counts_from_shapes(self, family, config):
        sizes = {k: config[k] for k in family.SIZE_KEYS}
        assert family.layer_kinds(sizes) == [(False, False)] + [(True, True)] * 3
        attention = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
        expert = 3 * 2560 * 768
        per_token = 4 * (attention + 2560 * 64 + 0.75 * expert) + 2560 * 18992
        assert family.matmul_params(sizes) == per_token
        full, window = 16384 * 16385 // 2, 4096 * 4097 // 2 + (16384 - 4096) * 4096
        assert family.visible_pairs(16384, None) == full and family.visible_pairs(16384, 4096) == window
        assert family.visible_pairs(64, 4096) == 64 * 65 // 2
        assert window / full == pytest.approx(0.4375, abs=1e-3)  # 44% of a full layer's pairs
        cost = family.flash_attention_cost(sizes)
        assert cost["calls_per_step"] == 4
        assert cost["flops"] * 4 == 3 * 4.0 * 28 * 128 * (full + 3 * window)
        row_q, row_kv = 28 * 16384 * 128 * 2, 4 * 16384 * 128 * 2
        assert cost["bytes"] == 6 * row_q + 6 * row_kv + 2 * 28 * 16384 * 4
        assert family.step_flops(sizes) == 6.0 * per_token * 16384 + 4 * cost["flops"]
        assert 28.0e12 < family.step_flops(sizes) < 28.4e12
        held = 4 * 16384 * 6 / 8
        assert family.expert_product_cost(sizes, held)["flops"] == 6.0 * expert * held

    def test_the_file_holds_every_published_key_and_three_are_cut(self, family, config):
        changed = {k for k, v in config["source_config"].items() if config[k] != v}
        assert changed == set(config["reduced"]) == {"num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
        assert set(config["published"]) == changed
        for key in ("deployment", "expert_load", "assumed", "departures", "precision"):
            assert config[key]
        assert len(config["rope_layout"]) == len(config["sliding_window_layout"]) == 52
        catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
        if os.path.exists(catalog):
            with open(catalog) as f:
                (row,) = [r for r in map(json.loads, f) if r["name"] == "SmallThinker-21BA3B-Instruct"]
            assert config["source_config"] == row["config"] and config["source"] == row["source_url"]

    def test_parameters_as_run(self, family, config):
        sizes = {k: config[k] for k in family.SIZE_KEYS}
        shapes = jax.eval_shape(family._init_program(family.shape_of(sizes)))
        assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 370547200
        model = transformer._gqa_moe_model(_trial_params(family, sizes), sizes["vocab_size"], None)
        assert model.sizes == GqaMoeSizes(
            d_model=2560, n_heads=28, n_kv_heads=4, head_dim=128, n_layers=4, window=4096,
            window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1), expert_width=768, n_experts=64,
            experts_per_token=6, experts_held=(0, 8), rope_theta=1.5e6, eps=1e-6,
        )

    def test_a_checkout_without_the_block_is_refused_at_once(self, family, monkeypatch):
        monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
        with pytest.raises(SystemExit, match="no block 'gqa_moe'"):
            _trial_params(family)


# ---------------------------------------------------------------------------
# the benchmark's reader of the tile counters
# ---------------------------------------------------------------------------


class TestTileOverrunReader:
    @pytest.fixture(scope="class")
    def read(self):
        path = os.path.join(REPO, "benchmark", "layer_metrics", "attn_tile_overrun.py")
        spec = importlib.util.spec_from_file_location("attn_tile_overrun", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    @staticmethod
    def _init(t0, **args):
        return {"name": "trial.init", "t0": t0, "t1": t0 + 0.5, "args": args}

    def test_reads_the_trials_that_started_inside_the_window(self, read):
        spans = [
            self._init(5.0, attn_tiles_run=900, attn_tiles_needed=100),  # the warm-up trial
            self._init(11.0, attn_tiles_run=150, attn_tiles_needed=100),
            self._init(21.0, attn_tiles_run=150, attn_tiles_needed=100),
            self._init(29.8, attn_tiles_run=900, attn_tiles_needed=100),  # past the last completed trial
            {"name": "trial.eval", "t0": 12.0, "t1": 12.1, "args": {"attn_tiles_run": 7, "attn_tiles_needed": 1}},
        ]
        assert read({"t0": 10.0, "last_end": 30.0, "spans": spans}) == 1.5

    @pytest.mark.parametrize(
        "args", [dict(block="gpt2", attn_tiles="dense"), dict(attn_tiles_needed=0, attn_tiles_run=0), dict(attn_tiles_needed=5)],
        ids=["no-counters", "no-tiles", "half"],
    )
    def test_none_where_the_program_has_no_such_counters(self, read, args):
        assert read({"t0": 10.0, "last_end": 30.0, "spans": [self._init(11.0, **args)]}) is None

    def test_the_entry_names_the_new_cell(self):
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            bench = json.load(f)
        (entry,) = [m for m in bench["per_layer"] if m["name"] == "attn_tile_overrun"]
        cell = "smallthinker-ep8-lr4low-steps12"
        assert entry == {
            "name": "attn_tile_overrun", "unit": "ratio", "better": "lower", "source": "program_counter",
            "layer": "kernel", "moves": "trials_per_hour", "workloads": [cell],
        }
        listed = {m["name"] for m in bench["per_layer"] if cell in m.get("workloads", ())}
        assert listed == {
            "attn_tile_overrun", "moe_load_imbalance", "moe_tokens_dropped", "expert_product_roofline",
            "moe_buffer_share",  # PR 36
        }
        (row,) = [w for w in bench["workloads"] if w["name"] == cell]
        assert (row["config"], row["traffic"], row["chips"]) == ("smallthinker-21b-a3b-ep8", "lr4low-steps12", 1)
        assert os.path.exists(os.path.join(REPO, "benchmark", "limits", f"{cell}.json"))
