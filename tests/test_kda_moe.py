"""The sixth block family of ``transformer_trial`` (``block: kda_moe``): Kimi
delta attention (the gated delta rule with one decay a channel and a step in
(0, 2)) in a period with gated grouped-query attention, beside sparse experts
and a shared one; and what it asked of the ops: the chunked scan as Pallas
kernels, forward and backward.

Against the token-by-token recurrence of ``ops/delta_rule.py`` and the
benchmark's plain reference (``benchmark/families/kda_moe.py``, loaded by
path), at tiny sizes on the CPU with the kernels interpreted.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.models import transformer
from katib_tpu.models.kda_moe import KdaMoeLM, KdaMoeSizes
from katib_tpu.models.mla_moe import ROUTING
from katib_tpu.ops import delta_rule as dr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def family():
    path = os.path.join(REPO, "benchmark", "families", "kda_moe.py")
    spec = importlib.util.spec_from_file_location("benchmark_families_kda_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the scan against the recurrence
# ---------------------------------------------------------------------------


def _operands(seed=0, b=1, h=2, s=128, d=32, decay=1.0, beta=(0.0, 2.0)):
    """q and k as the layer hands them (unit keys, queries over sqrt(d)), a log
    decay uniform in [-decay, 0] a channel, a step uniform in ``beta``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, h, s, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, h, s, d))
    g = -decay * jax.random.uniform(ks[3], (b, h, s, d))
    return q, k, v, g, jax.random.uniform(ks[4], (b, h, s), minval=beta[0], maxval=beta[1])


def _kernel(*args):
    return dr.delta_rule(*args, interpret=True)


# (positions, width, decay, step range): one chunk; several; a length that is
# not a whole number of chunks; channels that forget 30 nats a token (a
# chunk's decay far past float32's range as e^{-G}); steps near 2
SCAN_CASES = {
    "one-chunk": (64, 32, 1.0, (0.0, 2.0)),
    "three-chunks": (192, 32, 1.0, (0.0, 2.0)),
    "ragged": (100, 32, 0.5, (0.0, 2.0)),
    "strong-decay": (128, 32, 30.0, (0.0, 2.0)),
    "beta-near-2": (128, 128, 0.01, (1.9, 2.0)),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_scan_and_its_gradients_follow_the_recurrence(case):
    """Output, and the gradients to q, k, v, g and beta, of the kernels
    (interpreted) and of the same chunks in plain XLA, against the recurrence
    token by token: all float32 at ``HIGHEST``, so to float32's rounding of
    two orders of summation (the chunk form sums a chunk's tokens in one
    product, the recurrence one token after another)."""
    s, d, decay, beta = SCAN_CASES[case]
    args = _operands(seed=len(case), s=s, d=d, decay=decay, beta=beta)
    want = dr.reference_delta_rule(*args)
    for fn in (_kernel, dr.chunked_delta_rule):
        got = fn(*args)
        assert got.shape == want.shape and got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    w = jax.random.normal(jax.random.PRNGKey(7), want.shape)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(5))(*args)

    for name, got, ref in zip("q k v g beta".split(), grads(_kernel), grads(dr.reference_delta_rule)):
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 0 and bool(jnp.all(jnp.isfinite(got))), name
        np.testing.assert_allclose(got, ref, atol=5e-5 * scale, err_msg=name)


@pytest.mark.parametrize("control", ["beta-without-2", "decay-per-head", "state-bf16"])
def test_a_changed_rule_fails_the_scans_comparison(control):
    """What the comparison above must catch: the recurrence with the step
    halved (no factor 2: no negative eigenvalue), one decay a head (the mean
    of its channels'), or the state rounded to bfloat16 after every token,
    lies outside the kernels' tolerance by more than tenfold."""
    q, k, v, g, beta = _operands(seed=3, s=128, d=32, decay=1.0)
    got = _kernel(q, k, v, g, beta)
    if control == "beta-without-2":
        want = dr.reference_delta_rule(q, k, v, g, beta / 2)
    elif control == "decay-per-head":
        want = dr.reference_delta_rule(q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape), beta)
    else:

        def step(state, xs):
            qt, kt, vt, gt, bt = xs
            decayed = jnp.exp(gt)[..., None] * state
            pred = jnp.einsum("bhd,bhde->bhe", kt, decayed)
            state = decayed + bt[..., None, None] * kt[..., None] * (vt - pred)[..., None, :]
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return state, jnp.einsum("bhd,bhde->bhe", qt, state)

        xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
        _, o = jax.lax.scan(step, jnp.zeros((1, 2, 32, 32)), xs)
        want = jnp.moveaxis(o, 0, 2)
    assert float(jnp.max(jnp.abs(got - want))) > 10 * 2e-5 * float(jnp.max(jnp.abs(want)))


def test_the_rematerialised_gradient_runs_no_scan_kernel_again():
    """Under ``remat_kda_block`` the backward reads the forward's kept output
    and chunk start states: two kernels (the forward, the reverse walk), not
    three."""
    import flax.linen as nn

    args = _operands(s=128)

    class Block(nn.Module):
        @nn.compact
        def __call__(self, q, k, v, g, beta):
            return jnp.sum(_kernel(q, k, v, g, beta))

    def count(block):
        model = block()
        grad = jax.grad(lambda *a: model.apply({}, *a), argnums=range(5))
        return str(jax.make_jaxpr(grad)(*args)).count("pallas_call")

    assert count(dr.remat_kda_block(Block)) == 2
    assert count(nn.remat(Block)) == 3


def test_the_kernels_carry_the_benchmarks_mark():
    """The device trace names an operation by its HLO text; the family's
    ``KDA_MARK`` is the program's, and it is no other kernel's: the attention
    kernels take bfloat16 first, the grouped products int32, the expert
    layer's way back a two-dimensional float32 array."""
    mark = re.compile(dr.KDA_MARK)
    assert mark.search("custom-call(...), operand_layout_constraints={f32[1,8,4096,128]{3,2,1,0}, bf16[1,8")
    for other in ("{bf16[1,8,4096,128]{3,2,1,0}", "{s32[1]{0}, s32[9]{0}", "{f32[8,4096]{1,0}, s32[8,4096]"):
        assert not mark.search("operand_layout_constraints=" + other)


# ---------------------------------------------------------------------------
# the model against the benchmark's plain reference
# ---------------------------------------------------------------------------

CONFIG = {
    "hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 4, "moe_intermediate_size": 32, "router_width": 16, "num_experts_per_tok": 3,
    "n_shared_experts": 1, "experts_held_first": 4, "n_routed_experts": 8, "vocab_size": 96, "seq_len": 128,
    "batch_size": 2, "n_seq": 48, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 2, "num_kv_heads": None},
    "gqa_interval": 3, "gqa_layers": [0, 4, 8], "use_rope": False, "first_k_dense_replace": 0, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "tie_word_embeddings": False,
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
    model = transformer._BLOCKS["kda_moe"](_trial_params(family), CONFIG["vocab_size"], None)
    return dataclasses.replace(model, dtype=dtype, scan_fn=transformer.kda_scan_fn(kernel))


def _tokens():
    return jnp.asarray(transformer.markov_dataset(CONFIG["vocab_size"], CONFIG["batch_size"], CONFIG["seq_len"], seed=2))


def test_the_trial_builds_the_block_from_its_parameters(family):
    model = transformer._BLOCKS["kda_moe"](_trial_params(family), 96, None)
    assert model.sizes == KdaMoeSizes(
        d_model=48, n_heads=4, n_kv_heads=2, head_dim=16, kda_heads=2, kda_head_dim=16, conv_size=4, n_layers=4,
        gqa_period=4, expert_width=32, n_experts=16, experts_per_token=3, n_shared_experts=1, experts_held=(4, 8),
        eps=1e-5,
    )
    assert [model.sizes.is_gqa(i) for i in range(4)] == [True, False, False, False]
    # off the TPU the chunks run in plain XLA; both callables are shared
    assert model.scan_fn is transformer.kda_scan_fn(False) and model.scan_fn.kernel is False
    with pytest.raises(ValueError, match="not a multiple"):
        transformer._BLOCKS["kda_moe"]({**_trial_params(family), "n_kv_heads": "3"}, 96, None)
    with pytest.raises(SystemExit, match="gqa_layers"):
        family.experiment_doc("x", {**CONFIG, "gqa_layers": [0, 3]}, {"parameters": [], "steps": 1}, 0)


def test_the_weights_are_the_references(family):
    """The reference's skeleton draws the program's weights: same names,
    shapes, initialisers and order (``A_log`` and ``dt_bias`` by Kimi
    Linear's draws, written in both)."""
    params = _model(family, False).init(jax.random.PRNGKey(0), _tokens()[:1, :8])["params"]
    want = family.init_params(CONFIG)
    got = family.from_program_tree(params)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-7, atol=1e-7, err_msg=jax.tree_util.keystr(path))
    a_log = np.asarray(got["kda"]["A_log"])
    assert np.all((a_log >= 0.0) & (a_log <= np.log(16.0)))


def _program_and_reference(family, kernel):
    """The program's training loss and gradients at float32, and the
    reference's, from the same weights; and the reference's weights."""
    model, tokens = _model(family, kernel), _tokens()
    params = model.init(jax.random.PRNGKey(0), tokens[:1])

    def program(p):
        out, sown = model.apply(p, tokens, mutable=[ROUTING])
        return model.training_loss(out, tokens)[0], sown

    (loss, sown), grads = jax.value_and_grad(program, has_aux=True)(params)
    ref = family.from_program_tree(params["params"])
    shape = family.shape_of(CONFIG)
    ref_loss, ref_grads = jax.value_and_grad(lambda p: family._forward(p, tokens, shape, "f32", None))(ref)
    return (loss, sown, grads), (ref_loss, ref_grads, ref)


@pytest.fixture(scope="module")
def chunks_run(family):
    return _program_and_reference(family, False)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernels", "chunks"])
def test_the_model_follows_the_reference(family, kernel, chunks_run):
    """Logits (through the loss), the cross entropy and the gradient to every
    parameter, at float32 (the kernels interpreted), against the reference's
    token-by-token recurrence: the loss to 2e-6 (it read 1e-7: the two sum a
    chunk's tokens in other orders), every gradient to 1e-4 of its largest
    element (it read 1.3e-5)."""
    (loss, sown, grads), (ref_loss, ref_grads, _) = _program_and_reference(family, True) if kernel else chunks_run
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-6)
    got = family.from_program_tree(grads["params"])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(a, b, atol=1e-4 * scale, err_msg=jax.tree_util.keystr(path))
    counters = KdaMoeLM.step_counters(jax.device_get(sown[ROUTING]))
    pairs = 3 * CONFIG["batch_size"] * CONFIG["seq_len"] * 2  # three delta-rule layers of 2 heads
    assert counters["kda_pairs"] == pairs and 0 < counters["kda_neg_eig_pairs"] < pairs
    assert counters["kda_neg_eig_share"] == counters["kda_neg_eig_pairs"] / pairs
    assert "moe_assignments_held" in counters


@pytest.mark.parametrize("fault", ["beta_one", "decay_per_head", "no_gate", "state_bf16"])
def test_a_planted_fault_fails_the_models_comparison(family, fault, chunks_run):
    """The reference with a fault planted (the step without its factor 2, one
    decay a head, neither output gate, the state in bfloat16) is told from the
    program by more than ten times the loss's tolerance above."""
    (loss, _, _), (_, _, ref) = chunks_run
    faulty = jax.jit(lambda p: family._forward(p, _tokens(), family.shape_of(CONFIG), "f32", fault))(ref)
    assert abs(float(loss) - float(faulty)) > 10 * 2e-6 * abs(float(faulty)), fault


def test_the_shares_add_up_to_the_uncut_layer(family):
    """The cut's arithmetic: a layer's heads shared out over two chips (each
    holding half the delta-rule heads, or half the query heads with their
    key-value head) and its experts over two (each holding half; the router
    and the shared expert whole on both, the shared expert counted once) add
    up to the uncut reference layer."""
    r, s = 2, 64
    shape = family.shape_of(CONFIG)
    full = family.init_params(CONFIG)
    x = jax.random.normal(jax.random.PRNGKey(4), (r, s, CONFIG["hidden_size"]))

    def functions(**changes):
        config = {**CONFIG, **changes}
        return family._layer_functions(family.shape_of(config), "f32", None, r, s)

    f = functions()
    kd = CONFIG["linear_attn_config"]["head_dim"]

    def first(kind):
        return jax.tree_util.tree_map(lambda a: a[0], full[kind])

    # the delta rule: 2 heads, one a share
    w = first("kda")
    kda_share = functions(linear_attn_config={**CONFIG["linear_attn_config"], "num_heads": 1})
    total = 0.0
    for head in range(2):
        cols = slice(head * kd, (head + 1) * kd)
        part = dict(w)
        for n in ("q", "k", "v", "f_b", "g_b"):
            part[n] = w[n][:, cols]
        for n in ("q_conv", "k_conv", "v_conv"):
            part[n] = w[n][:, cols]
        part.update(dt_bias=w["dt_bias"][cols], A_log=w["A_log"][head : head + 1], b=w["b"][:, head : head + 1], o=w["o"][cols])
        total = total + kda_share["kda"](x, part) - x
    np.testing.assert_allclose(total, f["kda"](x, w) - x, atol=1e-5)

    # grouped-query attention: 4 query heads over 2 key-value heads, 2 and 1 a share
    w = first("gqa")
    gqa_share = functions(num_attention_heads=2, num_key_value_heads=1)
    hd = CONFIG["head_dim"]
    total = 0.0
    for kv in range(2):
        q_cols, kv_cols = slice(2 * kv * hd, 2 * (kv + 1) * hd), slice(kv * hd, (kv + 1) * hd)
        part = {**w, "q": w["q"][:, q_cols], "gate": w["gate"][:, q_cols], "o": w["o"][q_cols],
                "k": w["k"][:, kv_cols], "v": w["v"][:, kv_cols]}
        total = total + gqa_share["attention"](x, part) - x
    np.testing.assert_allclose(total, f["attention"](x, w) - x, atol=1e-5)

    # experts 4-11 of 16: held 4-7 on one chip, 8-11 on the other
    h = f["rms_norm"](x, w["norm2"])
    shared = f["mm"]("rse,ed->rsd", jax.nn.silu(f["mm"]("rsd,de->rse", h, w["shared_gate"])) * f["mm"]("rsd,de->rse", h, w["shared_up"]), w["shared_down"])
    total = shared
    for first in (4, 8):
        share = functions(experts_held_first=first, n_routed_experts=4)
        idx = slice(first - 4, first)
        part = {**w, **{n: w[n][idx] for n in ("experts_gate", "experts_up", "experts_down")}}
        total = total + share["moe"](x, part) - x - shared
    np.testing.assert_allclose(total, f["moe"](x, w) - x, atol=1e-5)


def test_attention_plan_counts_the_grouped_query_layers_alone(family):
    model = _model(family, True, jnp.bfloat16)
    attrs, _ = transformer.attention_plan(model, 2, 128)
    assert attrs["attn_layers"] == "full nope x1"
    assert attrs["remat"] == "blocks"  # off the TPU no attention kernel runs


def test_example_runs_through_the_orchestrator(tmp_path):
    """Orchestrator.run -> trial runner -> transformer_trial -> train_lm: the
    ``trial.eval`` spans carry the step counts of the delta-rule layers."""
    from katib_tpu.orchestrator.orchestrator import Orchestrator
    from katib_tpu.sdk.yaml_spec import load_experiment_yaml
    from katib_tpu.utils import tracing

    spec = load_experiment_yaml(os.path.join(REPO, "examples", "hp-tuning", "transformer-kda-moe.yaml"))
    orch = Orchestrator(workdir=str(tmp_path))
    exp = orch.run(spec)
    assert exp.succeeded_count == 2 and exp.optimal is not None
    records = list(tracing.read_journal(str(tmp_path / spec.name / "trace.jsonl")))
    inits = [r["args"] for r in records if r["name"] == "trial.init"]
    assert [a["block"] for a in inits] == ["kda_moe"] * 2 and inits[1]["programs"] == "reused"
    evals = [r["args"] for r in records if r["name"] == "trial.eval"]
    assert evals and all(a["kda_pairs"] == 3 * 4 * 64 * 2 for a in evals)
    assert all(0.0 <= a["kda_neg_eig_share"] <= 1.0 for a in evals)


# ---------------------------------------------------------------------------
# the older blocks' programs, and the benchmark's readers
# ---------------------------------------------------------------------------

#: sha256 of each older block's train-step jaxpr at a tiny size on the CPU,
#: object addresses left out, as the parent of the block's PR computed them
#: (commit 924e46c): adding the sixth block changed none of them
OLDER_STEP_JAXPRS = {
    "gpt2": "6defdf994085f90cf2a7b205a2666ea08ded2c5faa851e618b37182a11e0f0ba",
    "mla_moe": "5204d011e59b955495c72e6facd1b917ee9a0f8e0af4e06af69555c0d8aa1b0a",
    "gqa_moe": "8de4d781d850e24cca7cbc9aca968ed9c06724355c8af21050ef1b7461cc7e59",
    "looped": "7101a18e047550167bbf5bc3f9e8ee1b594e85e0e3537a3040da8f9cf81cc45c",
    "dsa_moe": "8a5143f42c87302437db288f7d9234810776215a230f128060f195d3114d91e2",
}
_OLDER_SIZES = {
    "gpt2": {},
    "mla_moe": {"qk_nope_dim": 16, "qk_rope_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32, "dense_width": 96,
                "expert_width": 32, "n_experts": 8, "experts_per_token": 2},
    "gqa_moe": {"n_kv_heads": 2, "head_dim": 16, "window": 16, "expert_width": 32, "n_experts": 8,
                "experts_per_token": 2, "experts_held": 4},
    "looped": {"head_dim": 16, "mlp_width": 96, "ut_steps": 2},
    "dsa_moe": {"n_kv_heads": 2, "head_dim": 16, "index_heads": 2, "index_head_dim": 16, "index_topk": 16,
                "expert_width": 32, "n_experts": 8, "experts_per_token": 2},
}


@pytest.mark.parametrize("block", sorted(OLDER_STEP_JAXPRS))
def test_the_older_blocks_step_programs_are_unchanged(block):
    params = {"d_model": 64, "n_heads": 4, "n_layers": 2, "vocab_size": 96, "seq_len": 64, **_OLDER_SIZES[block], "block": block}
    model = transformer._BLOCKS[block](params, 96, None)
    programs = transformer._build_programs(model, 1.0, transformer.WEIGHT_DECAY, None)
    state = jax.eval_shape(programs.init, jax.random.PRNGKey(0), 64)
    text = str(jax.make_jaxpr(programs.step_fn)(
        state, jax.ShapeDtypeStruct((2, 64), jnp.int32), jax.random.PRNGKey(1), jnp.float32(1e-3), jnp.int32(1), jnp.int32(4)
    ))
    assert hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest() == OLDER_STEP_JAXPRS[block]


def _reader(name):
    path = os.path.join(REPO, "benchmark", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_layer_metrics_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Slice:
    """What a reader asks of ``trace_reduce.Slice``: two step executions of
    100 ms, in each a scan kernel's forward (3 ms) and backward (5 ms), an
    attention kernel and the expert layer's way back."""

    t0, t1 = 0.0, 10.0

    def module_events(self, module):
        return [("jit_step_fn(1)", 1.0, 1.1), ("jit_step_fn(2)", 2.0, 2.1)] if module == "jit_step_fn" else []

    def kernel_events(self, mark):
        fwd = "%kda_scan.6 = (f32[1,8,4096,128], f32[1,8,64,128,128]) custom-call(...), custom_call_target=\"tpu_custom_call\", operand_layout_constraints={f32[1,8,4096,128]{3,2,1,0}, bf16[1,8,4096,128]"
        bwd = fwd.replace("%kda_scan.6", "%kda_scan.9")
        attn = "%attn.2 = (bf16[1,8,4096,128]) custom-call(), custom_call_target=\"tpu_custom_call\", operand_layout_constraints={bf16["
        back = "%expert_way_back.3 = bf16[4096,4096] custom-call(), custom_call_target=\"tpu_custom_call\", operand_layout_constraints={f32[8,4096]{1,0}, s32["
        events = [(fwd, 1.01, 1.013), (bwd, 1.05, 1.055), (attn, 1.02, 1.03), (back, 1.06, 1.07),
                  (fwd, 2.01, 2.013), (bwd, 2.05, 2.055), (fwd, 3.0, 3.003)]
        return [ev for ev in events if mark in ev[0]]


def json_config():
    """The cell's configuration file, as ``run.Cell`` reads its sizes."""
    with open(os.path.join(REPO, "benchmark", "configs", "solar-open2-250b-tp8ep40.json")) as f:
        return json.load(f)


def _context(family, spans=()):
    class Cell:
        pass

    cell = Cell()
    cell.family, cell.sizes = family, json_config()
    return {"slice": _Slice(), "cell": cell, "spans": list(spans), "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_the_scan_readers_read_the_scan_kernels(family):
    ctx = _context(family)
    # forward and backward inside the two step executions; not the event outside
    assert _reader("kda_scan_ms")(ctx) == pytest.approx(8.0)
    cost = family.kda_scan_cost(ctx["cell"].sizes)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9) * cost["calls_per_step"]
    assert _reader("kda_scan_roofline")(ctx) == pytest.approx(100 * least / 0.008)
    ctx["slice"].kernel_events = lambda mark: []
    assert _reader("kda_scan_ms")(ctx) is None and _reader("kda_scan_roofline")(ctx) is None


def test_the_step_reader_and_a_program_without_its_counters(family):
    def span(t, **args):
        return {"name": "trial.eval", "t0": t, "t1": t + 0.1, "args": args}

    spans = [span(1.5, kda_neg_eig_pairs=30, kda_pairs=100), span(2.5, kda_neg_eig_pairs=10, kda_pairs=100),
             span(20.0, kda_neg_eig_pairs=99, kda_pairs=100)]
    assert _reader("kda_neg_eig_share")(_context(family, spans)) == pytest.approx(0.2)
    assert _reader("kda_neg_eig_share")(_context(family, [span(1.5, moe_tokens_dropped=0)])) is None


def test_the_family_counts_the_cells_work(family):
    """At the cell's shapes: the chunk algorithm's operations counted once,
    16.78M a chunk of a head forward, 25.8 GFLOP a layer forward and
    backward; three delta-rule layers and one grouped-query layer; 6.31 TFLOP
    a step (249.4M product parameters a token, 6.13 TFLOP; attention 0.10;
    the scans 0.077)."""
    sizes = json_config()
    chunk = 4 * 64 * 64 * 128 + 20 * 64**3 + 2 * 64 * 64 * 256 + 6 * 64 * 128 * 128 + 2 * 64 * 64 * 128
    assert chunk == 16_777_216
    cost = family.kda_scan_cost(sizes)
    assert cost["flops"] == 3.0 * chunk * 8 * 64 and cost["calls_per_step"] == 3
    assert family.flash_attention_cost(sizes)["calls_per_step"] == 1
    assert family.gqa_layers(sizes) == [True, False, False, False]
    assert family.matmul_params(sizes) == 249_397_248
    assert family.step_flops(sizes) == pytest.approx(6.3105e12, rel=1e-4)
    assert family.EXPERT_PRODUCT_MARK == "operand_layout_constraints={s32["
    assert family.KDA_MARK == dr.KDA_MARK
