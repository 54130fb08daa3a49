"""The fifth block family of ``transformer_trial`` (``block: dsa_moe``):
grouped-query attention over the keys a learned indexer selects, the indexer
trained by its KL loss, beside sparse experts; and what it asked of the ops:
an exact selection pass, the flash kernels with a selection, the indexer's
loss and its gradients.

Against the dense reference of ``ops/sparse_attention.py`` and the benchmark's
plain reference (``benchmark/families/dsa_moe.py``, loaded by path), at tiny
sizes on the CPU with the kernels interpreted.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.models import transformer
from katib_tpu.models.dsa_moe import SELECTION_COUNTS, DsaMoeLM, DsaMoeSizes
from katib_tpu.models.mla_moe import ROUTING
from katib_tpu.ops import flash_attention as fa
from katib_tpu.ops import sparse_attention as sa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def family():
    path = os.path.join(REPO, "benchmark", "families", "dsa_moe.py")
    spec = importlib.util.spec_from_file_location("benchmark_families_dsa_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _inputs(b=1, h=4, g=2, s=256, d=32, h_i=2, d_i=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(ks[0], (b, h, s, d), dtype),
        jax.random.normal(ks[1], (b, g, s, d), dtype),
        jax.random.normal(ks[2], (b, g, s, d), dtype),
        jax.random.normal(ks[3], (b, s, h_i, d_i), dtype),
        jax.random.normal(ks[4], (b, s, d_i), dtype),
        jax.random.normal(ks[5], (b, s, h_i), jnp.float32),
    )


def _kernel(topk, tiles):
    def run(*args):
        return sa.sparse_attention(*args, topk, None, None, tiles, tiles, True)

    return run


def _selection_of(bits, s, tile):
    """The bitmask unpacked to ``[B, S, S]`` (queries, keys)."""
    words = tile // 32
    out = np.zeros((bits.shape[0], s, s), bool)
    for b in range(bits.shape[0]):
        for j in range(s // tile):
            out[b, :, j * tile : (j + 1) * tile] = np.asarray(fa.unpack_tile(bits[b, j * words : (j + 1) * words])).T
    return out


# ---------------------------------------------------------------------------
# the selection pass
# ---------------------------------------------------------------------------

#: (batch, positions, tile, indexer heads, topk): two indexer heads leave a
#: quarter of the scores exactly 0 (both ReLUs closed), so ties at a row's
#: threshold are common and are taken in order of position, as lax.top_k takes
#: them; a topk beyond every row; a topk of one
SELECTIONS = {
    "ties": (1, 256, 128, 2, 64),
    "small-tiles": (2, 256, 64, 4, 40),
    "all-visible": (1, 128, 64, 2, 200),
    "one-key": (1, 128, 64, 4, 1),
}


@pytest.mark.parametrize("case", sorted(SELECTIONS))
def test_selection_is_each_rows_top_k_exactly(case):
    b, s, tile, h_i, topk = SELECTIONS[case]
    _, _, _, qi, ki, w = _inputs(b=b, s=s, h_i=h_i, seed=3)
    bits, lse_i = sa.select_keys(
        qi.transpose(0, 2, 3, 1), ki.transpose(0, 2, 1), w.transpose(0, 2, 1),
        topk=topk, block_q=tile, block_k=tile, scale=0.25, interpret=True,
    )
    got = _selection_of(bits, s, tile)
    scores = sa.index_scores(qi, ki, w, 0.25)
    want = np.asarray(sa.reference_selection(scores, topk))
    np.testing.assert_array_equal(got.sum(-1), np.minimum(np.arange(1, s + 1), topk)[None].repeat(b, 0))
    np.testing.assert_array_equal(got, want)
    # the scores' log-sum-exp over each row's selection
    masked = jnp.where(jnp.asarray(want), scores, -jnp.inf)
    np.testing.assert_allclose(lse_i[:, 0], jax.scipy.special.logsumexp(masked, axis=-1), rtol=1e-5, atol=1e-5)
    if case == "ties":
        assert int(jnp.sum(scores == 0)) > s  # the tie-breaking ran


def test_tile_counts_and_loop_bounds_follow_the_bitmask():
    _, _, _, qi, ki, w = _inputs(s=256, seed=4)
    bits, _ = sa.select_keys(
        qi.transpose(0, 2, 3, 1), ki.transpose(0, 2, 1), w.transpose(0, 2, 1),
        topk=48, block_q=64, block_k=64, scale=0.25, interpret=True,
    )
    chosen = _selection_of(bits, 256, 64)
    counts = sa.tile_counts(bits, 64, 64)
    np.testing.assert_array_equal(counts, chosen.reshape(1, 4, 64, 4, 64).sum(axis=(2, 4)))
    bounds = np.asarray(sa.loop_bounds(counts))
    for qt in range(4):
        held = np.nonzero(np.asarray(counts)[0, qt])[0]
        assert tuple(bounds[0, qt]) == (held.min(), held.max() + 1)
    # planted: a q tile whose selection lies in its second k tile alone
    planted = jnp.zeros((1, 2, 2), jnp.int32).at[0, 1, 1].set(5).at[0, 0, 0].set(3)
    np.testing.assert_array_equal(sa.loop_bounds(planted), [[[0, 1], [1, 2]]])
    stats = sa.selection_counts(planted, sa.loop_bounds(planted), 64, 64)
    np.testing.assert_array_equal(stats, [8, 128 * 129 // 2, 2, 3])


# ---------------------------------------------------------------------------
# the op against its dense reference
# ---------------------------------------------------------------------------

#: (query heads, key-value heads, positions, tile, topk)
OPS = {
    "group-2": (4, 2, 256, 128, 64),
    "group-1": (2, 2, 256, 64, 100),
    "group-4-small-tiles": (4, 1, 128, 64, 24),
}


def _weighted(attn):
    """A loss that weighs the output and L_I alike, and the counts."""

    def loss(*args):
        o, l_i, *stats = attn(*args)
        return jnp.sum(o.astype(jnp.float32) * jnp.cos(jnp.arange(o.shape[-1]))) + 3.0 * l_i, stats

    return loss


@pytest.mark.parametrize("case", sorted(OPS))
def test_sparse_attention_and_its_gradients_follow_the_reference(case):
    h, g, s, tile, topk = OPS[case]
    args = _inputs(h=h, g=g, s=s, seed=5)
    (got, stats), grads = jax.value_and_grad(_weighted(_kernel(topk, tile)), argnums=range(6), has_aux=True)(*args)

    def reference(*a):
        return sa.dense_sparse_attention(*a, topk)

    want, ref_grads = jax.value_and_grad(lambda *a: _weighted(reference)(*a)[0], argnums=range(6))(*args)
    # float32 throughout: the kernels' products at float32 accumulate in
    # another order than the dense einsums, ~1e-6 of the largest value
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, a, b in zip(("q", "k", "v", "qI", "kI", "w"), grads, ref_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(b))), err_msg=name)
    selected = sum(min(t + 1, topk) for t in range(s))
    assert int(stats[0][0]) == selected and int(stats[0][1]) == s * (s + 1) // 2


def test_the_indexers_gradient_comes_from_its_loss_alone():
    """The output reaches no indexer input (the selection is discrete) and
    L_I reaches no input of the attention (its probabilities are detached)."""
    args = _inputs(seed=6)
    attn = _kernel(64, 128)
    of_output = jax.grad(lambda *a: jnp.sum(attn(*a)[0]), argnums=range(6))(*args)
    of_loss = jax.grad(lambda *a: attn(*a)[1], argnums=range(6))(*args)
    for grad in of_output[3:] + of_loss[:3]:
        assert float(jnp.max(jnp.abs(grad))) == 0.0
    for grad in of_output[:3] + of_loss[3:]:
        assert float(jnp.max(jnp.abs(grad))) > 0.0


def test_every_key_selected_is_dense_grouped_attention():
    """With ``topk`` at least the positions the op is causal GQA: the flash
    kernel's output, and every causal tile walked."""
    q, k, v, qi, ki, w = _inputs(s=128, seed=7)
    o, _l_i, stats = _kernel(128, 64)(q, k, v, qi, ki, w)
    np.testing.assert_allclose(o, fa.reference_attention(q, k, v, causal=True), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o, fa.flash_attention(q, k, v, causal=True, block_q=64, block_k=64), rtol=1e-5, atol=1e-6)
    assert int(stats[0]) == int(stats[1]) == 128 * 129 // 2
    assert int(stats[2]) == int(stats[3]) == 3


def test_bfloat16_operands_follow_the_reference():
    """bfloat16 in, as the model runs it: the output to bfloat16's rounding of
    ``p`` (as the flash kernel), L_I to a part in a thousand."""
    args = _inputs(dtype=jnp.bfloat16, seed=8)
    o, l_i, _ = _kernel(64, 128)(*args)
    ro, rl, _ = sa.dense_sparse_attention(*args, 64)
    np.testing.assert_allclose(o.astype(jnp.float32), ro.astype(jnp.float32), atol=3e-2)
    np.testing.assert_allclose(l_i, rl, rtol=1e-3)


def test_the_rematerialised_gradient_runs_no_kernel_again():
    """Under ``remat_sparse_block`` the backward reads the forward's kept
    results: four kernels (selection, forward, indexer's loss, the walk), not
    seven."""
    import flax.linen as nn

    args = _inputs(s=128, seed=9)

    class Block(nn.Module):
        @nn.compact
        def __call__(self, q, k, v, qi, ki, w):
            o, l_i, _ = _kernel(24, 64)(q, k, v, qi, ki, w)
            return jnp.sum(o) + l_i

    def count(block):
        model = block()
        grad = jax.grad(lambda *a: model.apply({}, *a), argnums=range(6))
        return str(jax.make_jaxpr(grad)(*args)).count("pallas_call")

    assert count(sa.remat_sparse_block(Block)) == 4
    assert count(nn.remat(Block)) == 7


# ---------------------------------------------------------------------------
# the model against the benchmark's plain reference
# ---------------------------------------------------------------------------

CONFIG = {
    "hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "moe_intermediate_size": 32, "router_width": 16, "num_experts_per_tok": 3,
    "experts_held_first": 4, "num_experts": 8, "vocab_size": 96, "seq_len": 128, "batch_size": 2, "n_seq": 48,
    "rope_theta": 10000000, "rms_norm_eps": 1e-06,
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4, "indexer_num_kv_heads": 1, "topk": 40},
    "hidden_act": "silu", "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "use_sliding_window": False, "tie_word_embeddings": False, "attention_bias": False,
}


def _trial_params(family, config=CONFIG) -> dict:
    """The parameters the family's experiment document pins, as a trial gets them."""
    traffic = {"parameters": [], "steps": 3, "algorithm": "random", "parallelTrialCount": 1, "maxTrialCount": 1}
    doc = family.experiment_doc("t", config, traffic, 0)
    out = {}
    for p in doc["spec"]["parameters"]:
        space = p["feasibleSpace"]
        out[p["name"]] = space["min"] if "min" in space else space["list"][0]
    return out


def _model(family, kernel: bool, dtype=jnp.float32):
    model = transformer._BLOCKS["dsa_moe"](_trial_params(family), CONFIG["vocab_size"], None)
    topk = CONFIG["sa_config"]["topk"]
    return dataclasses.replace(model, dtype=dtype, attn_fn=transformer.sparse_attention_fn(kernel, topk))


def _tokens():
    return jnp.asarray(transformer.markov_dataset(CONFIG["vocab_size"], CONFIG["batch_size"], CONFIG["seq_len"], seed=2))


def test_the_trial_builds_the_block_from_its_parameters(family):
    model = transformer._BLOCKS["dsa_moe"](_trial_params(family), 96, None)
    assert model.sizes == DsaMoeSizes(
        d_model=48, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=2, index_heads=4, index_head_dim=16,
        index_topk=40, expert_width=32, n_experts=16, experts_per_token=3, experts_held=(4, 8),
        rope_theta=1e7, eps=1e-6,
    )
    # off the TPU the dense reference runs; both callables are shared
    assert model.attn_fn is transformer.sparse_attention_fn(False, 40)
    with pytest.raises(ValueError, match="not a multiple"):
        transformer._BLOCKS["dsa_moe"]({**_trial_params(family), "n_kv_heads": "3"}, 96, None)


def test_the_weights_are_the_references(family):
    """The reference's skeleton draws the program's weights: same names,
    shapes, initialisers and order (to float32's last bit: the two draws are
    compiled apart, and a scale's product may round either way)."""
    params = _model(family, False).init(jax.random.PRNGKey(0), _tokens()[:1, :8])["params"]
    want = family.init_params(CONFIG)
    got = family.from_program_tree(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-7, atol=1e-7, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("kernel", [True, False], ids=["kernels", "dense"])
def test_the_model_follows_the_reference(family, kernel):
    """Logits, the cross entropy, every layer's L_I and the gradient of ``CE +
    sum L_I`` to every parameter, the indexer's among them, at float32 (the
    kernels interpreted): to float32's rounding of two orders of summation."""
    model, tokens = _model(family, kernel), _tokens()
    params = model.init(jax.random.PRNGKey(0), tokens[:1])
    shape = family.shape_of(CONFIG)

    def program(p):
        out, sown = model.apply(p, tokens, mutable=[ROUTING])
        loss, read = model.training_loss(out, tokens)
        return loss, (out, sown, read)

    (loss, (out, sown, read)), grads = jax.value_and_grad(program, has_aux=True)(params)

    def reference(p):
        ce, kl = family._forward(p, tokens, shape, "f32", None)
        return ce + jnp.sum(kl), (ce, kl)

    ref = family.from_program_tree(params["params"])
    (ref_loss, (ce, kl)), ref_grads = jax.value_and_grad(reference, has_aux=True)(ref)
    np.testing.assert_allclose(model.reported_loss(out, tokens), ce, rtol=2e-5)
    np.testing.assert_allclose(out.indexer_losses, kl, rtol=2e-4)
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-5)
    np.testing.assert_allclose(read["indexer_kl"], jnp.mean(kl), rtol=2e-4)
    got = family.from_program_tree(grads["params"])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4 * scale, err_msg=jax.tree_util.keystr(path))
    counters = DsaMoeLM.step_counters(jax.device_get({**sown[ROUTING], **read}))
    s, topk, layers = CONFIG["seq_len"], CONFIG["sa_config"]["topk"], CONFIG["num_hidden_layers"]
    b = CONFIG["batch_size"]
    assert counters["dsa_selected_pairs"] == layers * b * sum(min(t + 1, topk) for t in range(s))
    assert counters["dsa_visible_pairs"] == layers * b * s * (s + 1) // 2
    assert counters["dsa_tiles_visited"] == counters["dsa_tiles_causal"] == layers * b * 1
    assert set(SELECTION_COUNTS) <= set(counters) and "moe_assignments_held" in counters


def test_the_references_selection_is_each_rows_top_k_exactly(family):
    """The reference's selection mask, from ``lax.top_k``'s last value and the
    keys tied with it in order of position, is the set of ``top_k``'s indices:
    ``min(t + 1, topk)`` keys a row.  Every key is there eight times, so every
    row's threshold is tied."""
    r, s, topk = CONFIG["batch_size"], CONFIG["seq_len"], CONFIG["sa_config"]["topk"]
    heads, width = CONFIG["sa_config"]["indexer_num_heads"], CONFIG["sa_config"]["indexer_head_dim"]
    f = family._layer_functions(family.shape_of(CONFIG), "f32", None, r, s)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    qi = jax.random.normal(ks[0], (r, s, heads, width))
    w = jax.random.normal(ks[1], (r, s, heads))
    ki = jnp.tile(jax.random.normal(ks[2], (r, s // 8, width)), (1, 8, 1))
    got = np.asarray(f["select"](ki, (qi, w, 0)))
    scores = jnp.einsum("rqh,rqhs->rqs", w, jax.nn.relu(jnp.einsum("rqhd,rsd->rqhs", qi, ki))) / np.sqrt(width)
    seen = np.arange(s)[None, :] <= np.arange(s)[:, None]
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)
    want = np.zeros((r, s, s), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    want &= seen
    np.testing.assert_array_equal(got.sum(-1), np.minimum(np.arange(1, s + 1), topk)[None].repeat(r, 0))
    np.testing.assert_array_equal(got, want)


def test_query_blocks_and_key_segments_change_no_number_of_the_reference(family, monkeypatch):
    """The reference's attention in one block against every key, and in eight
    blocks of 16 queries in four segments, each against the keys up to its
    end: the same losses and gradients, to float32's rounding of two orders
    of summation."""
    tokens, shape = _tokens(), family.shape_of(CONFIG)
    params = family.init_params(CONFIG)

    def loss(p):
        ce, kl = family._forward(p, tokens, shape, "f32", None)
        return ce + jnp.sum(kl), (ce, kl)

    (want, (ce, kl)), want_grads = jax.value_and_grad(loss, has_aux=True)(params)
    monkeypatch.setattr(family, "SCORE_BYTES", 16 * 2 * 4 * 128 * 4)
    (got, (got_ce, got_kl)), grads = jax.value_and_grad(loss, has_aux=True)(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_ce, ce, rtol=1e-6)
    np.testing.assert_allclose(got_kl, kl, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * scale, err_msg=jax.tree_util.keystr(path))


def test_the_expert_rows_are_every_token_weighted_by_its_gate(family):
    """The reference's held experts, each over its routed tokens gathered
    where they fit into an eighth of the tokens and over every token where
    they do not, against every held expert over every token weighted by its
    gate (0 where not routed): the same layer and gradients.  Two experts
    every token chooses take the second way, some of the others the first."""
    r, s = CONFIG["batch_size"], CONFIG["seq_len"]
    f = family._layer_functions(family.shape_of(CONFIG), "f32", None, r, s)
    w = jax.tree_util.tree_map(lambda a: a[0], family.init_params(CONFIG)["layers"])
    w["router"] = w["router"].at[:, 4:6].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (r, s, CONFIG["hidden_size"])) + 1.0
    first, held = CONFIG["experts_held_first"], CONFIG["num_experts"]

    def every_token(x, w):
        h = f["rms_norm"](x, w["norm2"])
        logits, chosen = jax.lax.top_k(h @ w["router"], CONFIG["num_experts_per_tok"])
        gates = jnp.sum(jax.nn.one_hot(chosen - first, held) * jax.nn.softmax(logits)[..., None], axis=-2)
        hidden = jax.nn.silu(jnp.einsum("rsd,edf->ersf", h, w["experts_gate"])) * jnp.einsum(
            "rsd,edf->ersf", h, w["experts_up"]
        )
        return x + jnp.einsum("rse,ersf,efd->rsd", gates, hidden, w["experts_down"]), chosen

    want, chosen = every_token(x, w)
    routed = np.bincount(np.asarray(chosen).ravel(), minlength=CONFIG["router_width"])[first : first + held]
    room = r * s // 8
    assert routed[0] == routed[1] == r * s and ((0 < routed) & (routed <= room)).any(), routed
    np.testing.assert_allclose(f["moe"](x, w), want, rtol=1e-5, atol=1e-5)

    def total(layer):
        return lambda x, w: jnp.sum(jnp.square(layer(x, w)))

    got = jax.grad(total(f["moe"]), argnums=(0, 1))(x, w)
    ref = jax.grad(total(lambda x, w: every_token(x, w)[0]), argnums=(0, 1))(x, w)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(ref)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * scale, err_msg=jax.tree_util.keystr(path))


def test_the_cross_entropy_reaches_no_indexer_weight(family):
    model, tokens = _model(family, True), _tokens()
    params = model.init(jax.random.PRNGKey(0), tokens[:1])

    def part(which):
        def loss(p):
            out = model.apply(p, tokens, mutable=[ROUTING])[0]
            return model.reported_loss(out, tokens) if which == "ce" else jnp.sum(out.indexer_losses)

        return jax.grad(loss)(params)["params"]["layer_0"]["attn"]

    indexer = ("index_q_proj", "index_k_proj", "index_k_norm", "index_weights_proj")
    ce, kl = part("ce"), part("kl")
    for name in ce:
        a = max(float(jnp.max(jnp.abs(x))) for x in jax.tree_util.tree_leaves(ce[name]))
        b = max(float(jnp.max(jnp.abs(x))) for x in jax.tree_util.tree_leaves(kl[name]))
        assert (a == 0.0) == (name in indexer) and (b > 0.0) == (name in indexer), (name, a, b)


@pytest.mark.parametrize("fault", ["no_select", "indexer_frozen"])
def test_a_planted_fault_moves_the_reference(family, fault):
    """The faults the benchmark's controls plant change what the reference
    reports: ``no_select`` from the first step, ``indexer_frozen`` after
    updates."""
    config = {**CONFIG, "n_seq": 24}
    traffic = {"steps": 12}
    want = family.reference_series(config, traffic, 1, 1e-3)
    got = family.reference_series(config, traffic, 1, 1e-3, fault=fault)
    gaps = family.compare(got, want)
    if fault == "no_select":
        assert gaps["first_loss_gap"] > 1e-3 and gaps["first_indexer_gap"] > 1e-2
    else:
        assert gaps["first_loss_gap"] == 0.0 and gaps["trained_indexer_gap"] > 1e-3


def test_attention_plan_names_the_selection(family):
    model = _model(family, True, jnp.bfloat16)
    attrs, counters = transformer.attention_plan(model, 2, 128)
    assert attrs["attn_layers"] == "full rope top40 x2"
    assert attrs["attn_tiles"] == "bfloat16 q128 k128, backward one walk, selected top40"
    assert attrs["remat"] == "blocks, keeps attn out+lse"
    assert counters == {"attn_tiles_run": 2 * 4 * 2 * 2, "attn_tiles_needed": 2 * 4 * 2 * 2}


def test_the_step_reports_the_indexers_loss(family):
    """``train_lm`` hands a model's ``REPORTED_COUNTERS`` to the report beside
    the losses."""
    model = _model(family, False, jnp.bfloat16)
    data = transformer.markov_dataset(96, 24, 128, seed=1)
    reports = []
    transformer.train_lm(
        model, data, lr=1e-3, steps=2, batch_size=2, report=lambda **kw: reports.append(kw), report_every=1
    )
    assert [sorted(r) for r in reports] == [["eval_loss", "indexer_kl", "loss", "step"]] * 2
    assert all(0.0 < r["indexer_kl"] < r["loss"] for r in reports)


def test_example_runs_through_the_orchestrator(tmp_path):
    """Orchestrator.run -> trial runner -> transformer_trial -> train_lm: the
    store gets ``indexer_kl`` beside the losses, the spans the selection's
    counts."""
    from katib_tpu.orchestrator.orchestrator import Orchestrator
    from katib_tpu.sdk.yaml_spec import load_experiment_yaml
    from katib_tpu.utils import tracing

    spec = load_experiment_yaml(os.path.join(REPO, "examples", "hp-tuning", "transformer-dsa-moe.yaml"))
    orch = Orchestrator(workdir=str(tmp_path))
    exp = orch.run(spec)
    assert exp.succeeded_count == 2 and exp.optimal is not None
    for name in exp.trials:
        metrics = {m.metric_name for m in orch.store.get(name)}
        assert {"loss", "eval_loss", "indexer_kl"} <= metrics
    records = list(tracing.read_journal(str(tmp_path / spec.name / "trace.jsonl")))
    inits = [r["args"] for r in records if r["name"] == "trial.init"]
    assert [a["block"] for a in inits] == ["dsa_moe"] * 2 and inits[1]["programs"] == "reused"
    assert inits[0]["attn_layers"] == "full rope top16 x2"
    evals = [r["args"] for r in records if r["name"] == "trial.eval"]
    assert evals and all(a["dsa_selected_pairs"] == 2 * 4 * (16 * 17 // 2 + 48 * 16) for a in evals)
    assert all(a["dsa_visible_pairs"] == 2 * 4 * 64 * 65 // 2 for a in evals)


# ---------------------------------------------------------------------------
# the benchmark's readers of the new metrics
# ---------------------------------------------------------------------------


def _reader(name):
    path = os.path.join(REPO, "benchmark", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_layer_metrics_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Slice:
    """What a reader asks of ``trace_reduce.Slice``: two step executions of
    100 ms, and in each a selection pass of 4 ms and another attention kernel."""

    t0, t1 = 0.0, 10.0

    def module_events(self, module):
        return [("jit_step_fn(1)", 1.0, 1.1), ("jit_step_fn(2)", 2.0, 2.1)] if module == "jit_step_fn" else []

    def kernel_events(self, mark):
        select = "%c = (s32[1,512,16384], f32[1,1,16384]) custom-call(bf16[1,16,64,16384] %a), operand_layout_constraints={bf16["
        other = "%d = (bf16[1,32,16384,128], f32[1,32,1,16384]) custom-call(), operand_layout_constraints={bf16["
        events = [(select, 1.01, 1.014), (other, 1.02, 1.05), (select, 2.01, 2.014), (select, 3.0, 3.004)]
        return [ev for ev in events if mark in ev[0]]


def _context(family, spans=()):
    """What ``run.py`` hands a reader, for the cell's sizes and a made-up slice."""

    class Cell:
        pass

    cell = Cell()
    cell.family, cell.sizes = family, json_config()
    return {"slice": _Slice(), "cell": cell, "spans": list(spans), "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def json_config():
    """The cell's configuration file, as ``run.Cell`` reads its sizes."""
    import json

    with open(os.path.join(REPO, "benchmark", "configs", "keye-vl-2.0-30b-a3b-ep8.json")) as f:
        return json.load(f)


def test_the_selection_readers_read_the_selection_pass(family):
    ctx = _context(family)
    # the two events inside step executions, not the one outside
    assert _reader("dsa_select_ms")(ctx) == pytest.approx(4.0)
    cost = family.select_cost(ctx["cell"].sizes)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9) * cost["calls_per_step"]
    assert _reader("dsa_select_roofline")(ctx) == pytest.approx(100 * least / 0.004)
    # 0.275 TFLOP a layer: 1.40 ms at the bf16 peak, the compute bound
    assert cost["flops"] == pytest.approx(2 * 16 * 64 * 16384 * 16385 / 2)


def test_the_counter_readers_and_a_program_without_them(family):
    def span(t, **args):
        return {"name": "trial.eval", "t0": t, "t1": t + 0.1, "args": args}

    spans = [
        span(1.5, dsa_selected_pairs=30, dsa_visible_pairs=100, dsa_tiles_visited=9, dsa_tiles_causal=10, indexer_kl=0.3),
        span(2.5, dsa_selected_pairs=10, dsa_visible_pairs=100, dsa_tiles_visited=10, dsa_tiles_causal=10, indexer_kl=0.1),
        span(20.0, dsa_selected_pairs=99, dsa_visible_pairs=100, dsa_tiles_visited=1, dsa_tiles_causal=10, indexer_kl=9.0),
    ]
    ctx = _context(family, spans)
    assert _reader("dsa_selected_share")(ctx) == pytest.approx(0.2)
    assert _reader("dsa_tile_share")(ctx) == pytest.approx(0.95)
    assert _reader("indexer_kl")(ctx) == pytest.approx(0.2)
    bare = _context(family, [span(1.5, moe_tokens_dropped=0)])
    for name in ("dsa_selected_share", "dsa_tile_share", "indexer_kl"):
        assert _reader(name)(bare) is None
    ctx["slice"].kernel_events = lambda mark: []
    assert _reader("dsa_select_ms")(ctx) is None and _reader("dsa_select_roofline")(ctx) is None


def test_the_family_counts_only_the_selected_work(family):
    """At the cell's shapes (ISSUE 41's arithmetic): 31,458,304 of the
    134,225,920 visible pairs selected a layer; 21.9 TFLOP a step."""
    sizes = json_config()
    assert family.selected_pairs(16384, 2048) == 31458304
    assert family.visible_pairs(16384) == 134225920
    parts = family.layer_flops(sizes)
    assert 4 * parts["attention"] == pytest.approx(6.18e12, rel=1e-3)
    assert 4 * (parts["index_forward"] + parts["index_backward"]) == pytest.approx(1.61e12, rel=1e-2)
    assert family.step_flops(sizes) == pytest.approx(21.9e12, rel=5e-3)
    assert family.flash_attention_cost(sizes)["calls_per_step"] == 4


def test_the_expert_products_are_counted_as_the_other_expert_families_count_them(family):
    """``expert_product_cost`` at the cell's shapes: each token's 8 of 128
    experts, 16 held, so 16384 x 8 x 16/128 assignments a layer; the held
    experts' weights read twice in bfloat16 and their float32 gradients
    written, a layer (the rule of ``families/mla_moe.py``)."""
    sizes = json_config()
    expert = 3 * 2048 * 768
    held = 4 * 16384 * 8 * 16 / 128
    cost = family.expert_product_cost(sizes, held)
    assert family.expert_params(sizes) == expert
    assert cost["flops"] == 6.0 * expert * held
    rows = (2048 * 2 + 2 * 768 * 4) + (768 * 2 + 2048 * 4)
    assert cost["bytes"] == 4 * 16 * expert * 8 + 3 * rows * held
    assert family.EXPERT_PRODUCT_MARK == "operand_layout_constraints={s32["
