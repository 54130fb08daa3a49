"""Family ``dsa_moe``: grouped-query attention over the keys a learned indexer
selects (DeepSeek Sparse Attention) and sparse experts, as
``Keye-VL-2.0-30B-A3B``'s language model configures them, trained by
``transformer_trial`` with ``block: dsa_moe``.

What the family adds to the benchmark, beside what the four older families
have:

- a configuration file whose top-level keys are the published ``config.json``'s
  own (``SIZE_KEYS``; the indexer's sizes are in its nested ``sa_config``):
  ``experiment_doc`` turns them into the trial's parameters.  ``num_experts``
  there counts the experts HELD by this chip (the deployment's share:
  ``reduced``), ``router_width`` the experts routed over;
- the plain reference (``_layer_functions``), from the equations, ``RN`` an
  RMSNorm with weights only, no bias on any product:
  ``h = RN_1(x)``; ``q = rope(RN_q(h W_q))``, ``k = rope(RN_k(h W_k))``, ``v =
  h W_v`` (32 query heads over 4 key-value heads of 128; rotary over the whole
  head, the halves paired, theta 1e7);
  the indexer on ``stop_gradient(h)``: ``qI = h W_qI`` (16 heads of 64),
  ``kI = LN(h W_kI)`` (one key, LayerNorm with weight and bias), ``w = h W_w /
  sqrt(16)``, rotary on the first half of each indexer head (the halves of that
  half paired); ``I[t, s] = sum_j w_j relu(qI_j[t] . kI[s]) / sqrt(64)``;
  ``S_t``: ``lax.top_k`` of ``I[t]`` over the keys ``s <= t`` (every one where
  there are fewer than ``topk``);
  attention: a softmax over ``S_t`` of ``q . k / sqrt(128)``, in blocks of
  queries under a ``lax.map``, the blocks in eight segments, each against the
  keys up to its end (every later key is masked for all its queries);
  ``L_I = mean_t sum_{S_t} p (log p - log softmax_{S_t}(I[t]))``, ``p`` the
  mean over the query heads of the detached probabilities;
  experts: the router on ``RN_2(x')`` over all ``router_width`` experts in
  float32, the 8 largest logits, a softmax over the chosen, SiLU-gated experts
  as a ``lax.scan`` over the experts held, each over its routed tokens
  (gathered where they fit into an eighth of the tokens, else over every
  token with the unrouted ones weighted 0, as ``families/gqa_moe.py``
  computes the share; no sort, no grouped product);
  the layers as a ``lax.scan`` over their stacked weights, each rematerialised
  in its backward but for its selection and its attention's output, which
  it keeps (so ``top_k`` runs once a layer and a step); an untied head in
  blocks of positions.  The trial's ``loss`` is ``CE + sum_layers L_I``, its
  ``eval_loss`` the cross entropy, its ``indexer_kl`` the layers' mean
  ``L_I``.  The segments, the kept selection and the gathered rows change
  no number (``tests/test_dsa_moe.py``): they keep a ``run.py`` run of the
  cell inside the driver's time limit (a series was 138-156 s on the chip
  with all three left out).  It imports nothing of ``katib_tpu``;
- faults ``half_batch``, ``state_unchanged`` and, new here, ``no_select``
  (every visible key attended to: the selection is every ``s <= t``) and
  ``indexer_frozen`` (``L_I`` left out of the update);
- ``compare`` holds ``indexer_kl`` beside the losses (``first_indexer_gap``,
  ``trained_indexer_gap``);
- ``step_flops`` and ``flash_attention_cost`` count only the work the selection
  requires: attention over the SELECTED pairs, the indexer's forward over every
  visible pair and its backward over the selected ones; ``select_cost`` the
  selection pass's; ``SELECT_MARK`` finds that pass in the device trace;
  ``expert_product_cost`` and ``EXPERT_PRODUCT_MARK`` as ``families/mla_moe.py``
  has them (no metric reads them in this family's cell yet: PERF.md 7 (I)).

Departures from the published model, reproduced here: no vision tower (text
only, so mRoPE is rotary over the whole head); no auxiliary loss; flax's
default initialisers from ``PRNGKey(0)``.  The program computes in bfloat16
with float32 parameters, indexer scores, router logits, logits and losses;
the reference computes in float32 with ``highest`` matmul precision.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os


def _sibling(name: str):
    """Another family's file, loaded by path as ``run.py`` loads this one."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_families_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# All families train through ``transformer_trial`` and ``train_lm``: the
# trial's data, batches and schedule and the precision controls are the gpt2
# family's, not copies of them.
_gpt2 = _sibling("gpt2")
COMPARE_STEPS, TRAIN_FN = _gpt2.COMPARE_STEPS, _gpt2.TRAIN_FN
STEP_MODULE, EVAL_MODULE = _gpt2.STEP_MODULE, _gpt2.EVAL_MODULE
lr_values = _gpt2.lr_values
markov_tokens, batches, lr_at, _matmul = _gpt2.markov_tokens, _gpt2.batches, _gpt2.lr_at, _gpt2._matmul

#: the series a trial reports that the reference follows
METRICS = ("loss", "eval_loss", "indexer_kl")

BLOCK = "dsa_moe"
#: a ``tpu_custom_call`` names its operands' layouts, first operand first:
#: every kernel of the sparse attention takes a bfloat16 operand first (the
#: selection pass the indexer's queries, the others q), the grouped products
#: int32 group metadata
FLASH_KERNEL_MARK = "operand_layout_constraints={bf16["
#: and of those, the selection pass alone has an int32 first result (the bitmask)
SELECT_MARK = "= (s32["
#: the grouped products of ``ExpertLayer`` take their int32 group metadata
#: first, as in ``families/mla_moe.py``
EXPERT_PRODUCT_MARK = "operand_layout_constraints={s32["

#: the trial's integer parameters, by the configuration file's key
PARAMS = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
    "moe_intermediate_size": "expert_width",
    "router_width": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "experts_held_first": "experts_held_first",
    "num_experts": "experts_held",
    "vocab_size": "vocab_size",
    "seq_len": "seq_len",
    "batch_size": "batch_size",
    "n_seq": "n_seq",
}
#: those that are not integers
FLOAT_PARAMS = {"rope_theta": "rope_theta", "rms_norm_eps": "eps"}
#: the indexer's, from ``sa_config``
INDEX_PARAMS = {"indexer_num_heads": "index_heads", "indexer_head_dim": "index_head_dim", "topk": "index_topk"}
#: read and checked, and no parameter of the trial: what the block builds and
#: nothing else
CHECKED = {
    "hidden_act": "silu",
    "norm_topk_prob": True,
    "decoder_sparse_step": 1,
    "mlp_only_layers": [],
    "use_sliding_window": False,
    "tie_word_embeddings": False,
    "attention_bias": False,
}
SIZE_KEYS = tuple(PARAMS) + tuple(FLOAT_PARAMS) + ("sa_config",) + tuple(CHECKED)
_NOT_SHAPE = ("seq_len", "batch_size", "n_seq")


def index_sizes(sizes: dict) -> dict:
    """The indexer's sizes under the trial's names."""
    return {name: int(sizes["sa_config"][key]) for key, name in INDEX_PARAMS.items()}


# ---------------------------------------------------------------------------
# the experiment document
# ---------------------------------------------------------------------------


def experiment_doc(
    name: str, sizes: dict, traffic: dict, seed: int, *, lr_values=None, max_trials=None
) -> dict:
    """The experiment a user of this sweep submits.  A program that has no
    such block would take ``block`` for a parameter it does not know and train
    GPT-2 blocks of these sizes: refuse it here, at once."""
    if importlib.util.find_spec("katib_tpu.models.dsa_moe") is None:
        raise SystemExit(
            "families/dsa_moe.py: this checkout's transformer_trial has no block 'dsa_moe' "
            "(katib_tpu/models/dsa_moe.py is missing): the configuration cannot run here"
        )
    wrong = {k: sizes[k] for k, want in CHECKED.items() if sizes[k] != want}
    if wrong or int(sizes["sa_config"]["indexer_num_kv_heads"]) != 1:
        raise SystemExit(f"families/dsa_moe.py: the block builds {CHECKED} and one indexer key; the file has {wrong}")

    def pinned(pname: str, value: int) -> dict:
        return {
            "name": pname,
            "parameterType": "int",
            "feasibleSpace": {"min": str(value), "max": str(value)},
        }

    def one_of(pname: str, kind: str, values) -> dict:
        return {"name": pname, "parameterType": kind, "feasibleSpace": {"list": [str(v) for v in values]}}

    params = []
    for p in traffic["parameters"]:
        p = dict(p)
        if p["name"] == "lr" and lr_values is not None:
            p["feasibleSpace"] = {"list": [str(v) for v in lr_values]}
        params.append(p)
    params.append(one_of("block", "categorical", [BLOCK]))
    params += [pinned(PARAMS[k], int(sizes[k])) for k in PARAMS]
    params += [pinned(name, value) for name, value in index_sizes(sizes).items()]
    params += [one_of(FLOAT_PARAMS[k], "discrete", [float(sizes[k])]) for k in FLOAT_PARAMS]
    params += [pinned("steps", int(traffic["steps"])), pinned("data_seed", int(seed))]
    return {
        "apiVersion": "kubeflow.org/v1beta1",
        "kind": "Experiment",
        "metadata": {"name": name},
        "spec": {
            "objective": {
                "type": "minimize",
                "objectiveMetricName": "eval_loss",
                "additionalMetricNames": ["loss", "indexer_kl"],
            },
            "algorithm": {
                "algorithmName": traffic["algorithm"],
                "algorithmSettings": [{"name": "random_state", "value": str(int(seed))}],
            },
            "parallelTrialCount": int(traffic["parallelTrialCount"]),
            "maxTrialCount": int(max_trials or traffic["maxTrialCount"]),
            "maxFailedTrialCount": 0,
            "parameters": params,
            "trialTemplate": {"trainFn": TRAIN_FN},
        },
    }


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_product_cost(sizes: dict, assignments_held: float) -> dict:
    """Operations and HBM bytes of one step's grouped products over the
    experts held, forward + backward, from the COUNTED assignments to them
    (every layer has experts): as ``families/mla_moe.py`` counts them."""
    d, w = sizes["hidden_size"], sizes["moe_intermediate_size"]
    weights = sizes["num_hidden_layers"] * sizes["num_experts"] * expert_params(sizes)
    rows_forward = (d * 2 + 2 * w * 4) + (w * 2 + d * 4)
    return {
        "flops": 6.0 * expert_params(sizes) * assignments_held,
        "bytes": float(weights * (2 + 2 + 4) + 3 * rows_forward * assignments_held),
    }


def matmul_params(sizes: dict) -> float:
    """Parameters that take part in a product for one token: a layer's
    attention (q and the output at the query heads, k and v at the key-value
    heads), the indexer's three projections, the router, and the routed
    experts a token EXPECTS here (``num_experts_per_tok`` times the share held:
    8 x 16/128 = 1); the head."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    ix = index_sizes(sizes)
    attention = 2 * d * nh * hd + 2 * d * nkv * hd
    indexer = d * (ix["index_heads"] * ix["index_head_dim"] + ix["index_head_dim"] + ix["index_heads"])
    routed = sizes["num_experts_per_tok"] * sizes["num_experts"] / sizes["router_width"]
    layer = attention + indexer + d * sizes["router_width"] + routed * expert_params(sizes)
    return sizes["num_hidden_layers"] * layer + d * sizes["vocab_size"]


def visible_pairs(seq_len: int) -> int:
    """(query, key) pairs a causal layer sees."""
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len: int, topk: int) -> int:
    """Pairs the selection keeps: ``min(t + 1, topk)`` keys a query."""
    k = min(topk, seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


def layer_flops(sizes: dict) -> dict:
    """Operations of one layer's attention and indexer, forward and backward,
    that the selection requires: attention over the SELECTED pairs (QK^T and
    PV forward, twice that backward), the indexer's scores over every visible
    pair (its forward: one product a head) and their gradient over the
    selected pairs (to ``qI`` and ``kI``: two products a head)."""
    b, s = sizes["batch_size"], sizes["seq_len"]
    ix = index_sizes(sizes)
    chosen = selected_pairs(s, ix["index_topk"])
    index_width = ix["index_heads"] * ix["index_head_dim"]
    return {
        "attention": 3.0 * 4 * b * sizes["num_attention_heads"] * sizes["head_dim"] * chosen,
        "index_forward": 2.0 * b * index_width * visible_pairs(s),
        "index_backward": 4.0 * b * index_width * chosen,
    }


def step_flops(sizes: dict) -> float:
    """Operations one train step requires, forward and backward: 6 per product
    parameter and token, plus each layer's ``layer_flops``.  Rematerialised
    blocks, the kernels' recomputation and the pairs a kernel walks but the
    selection left out are not counted."""
    tokens = sizes["batch_size"] * sizes["seq_len"]
    return 6.0 * matmul_params(sizes) * tokens + sizes["num_hidden_layers"] * sum(layer_flops(sizes).values())


def flash_attention_cost(sizes: dict) -> dict:
    """Operations and HBM bytes of one layer's sparse attention, forward +
    backward, over the four kernels (selection, forward, the indexer's loss,
    the backward's walk): ``layer_flops``.  Bytes: q, o, do, dq at the query
    heads and k, v, dk, dv at the key-value heads as ``families/gqa_moe.py``
    counts them, the indexer's queries, key and weights read twice and their
    gradients written (float32), the bitmask written once and read by the
    three later kernels, the log-sum-exps."""
    b, s, hd = sizes["batch_size"], sizes["seq_len"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    ix = index_sizes(sizes)
    row_q, row_kv = b * nh * s * hd * 2, b * nkv * s * hd * 2
    lse = b * nh * s * 4
    index_in = b * s * (ix["index_heads"] * ix["index_head_dim"] + ix["index_head_dim"]) * 2 + b * s * ix["index_heads"] * 4
    bits = b * s * s // 8
    attention = (2 * row_q + 2 * row_kv) + (4 * row_q + 4 * row_kv) + 2 * lse
    return {
        "flops": sum(layer_flops(sizes).values()),
        "bytes": float(attention + 2 * index_in + 2 * index_in + 4 * bits),
        "calls_per_step": sizes["num_hidden_layers"],
    }


def select_cost(sizes: dict) -> dict:
    """Operations and HBM bytes of one layer's selection pass: the indexer's
    scores over every visible pair (one product a head); the indexer's queries,
    key and weights read, the bitmask and a log-sum-exp a row written."""
    b, s = sizes["batch_size"], sizes["seq_len"]
    ix = index_sizes(sizes)
    index_in = b * s * (ix["index_heads"] * ix["index_head_dim"] + ix["index_head_dim"]) * 2 + b * s * ix["index_heads"] * 4
    return {
        "flops": layer_flops(sizes)["index_forward"],
        "bytes": float(index_in + b * s * s // 8 + b * s * 4),
        "calls_per_step": sizes["num_hidden_layers"],
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

_SHAPE_KEYS = [k for k in tuple(PARAMS) + tuple(FLOAT_PARAMS) if k not in _NOT_SHAPE]


def shape_of(sizes: dict) -> tuple:
    """What shapes the weights and the reference's programs, hashable."""
    return tuple(sizes[k] for k in _SHAPE_KEYS) + tuple(index_sizes(sizes).values())


def _named(shape: tuple) -> dict:
    return dict(zip(_SHAPE_KEYS + list(INDEX_PARAMS.values()), shape, strict=True))


def init_params(sizes: dict):
    return _init_program(shape_of(sizes))()


@functools.lru_cache(maxsize=None)
def _init_program(shape: tuple):
    """Initial weights as flax draws them from ``PRNGKey(0)`` for modules of
    the program's names, shapes, initialisers and order of declaration (the
    trial's seed never reaches its weights).  The skeleton below only declares
    the parameters; the reference's arithmetic is ``_layer_functions``.  The
    layers' weights are stacked on a leading axis."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    z = _named(shape)
    d, hd = z["hidden_size"], z["head_dim"]
    nh, nkv = z["num_attention_heads"], z["num_key_value_heads"]
    n_i, d_i = z["index_heads"], z["index_head_dim"]
    width, held = z["moe_intermediate_size"], z["num_experts"]
    dense = functools.partial(nn.Dense, use_bias=False)

    class Norm(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.initializers.ones, (x.shape[-1],))
            return x

    class Attention(nn.Module):
        @nn.compact
        def __call__(self, x):
            dense(nh * hd, name="q_proj")(x)
            dense(nkv * hd, name="k_proj")(x)
            dense(nkv * hd, name="v_proj")(x)
            Norm(name="q_norm")(jnp.zeros((hd,)))
            Norm(name="k_norm")(jnp.zeros((hd,)))
            dense(n_i * d_i, name="index_q_proj")(x)
            nn.LayerNorm(name="index_k_norm")(dense(d_i, name="index_k_proj")(x))
            dense(n_i, name="index_weights_proj")(x)
            return dense(d, name="o_proj")(jnp.zeros(x.shape[:-1] + (nh * hd,)))

    class Experts(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("router", nn.initializers.lecun_normal(), (d, z["router_width"]))
            stacked = nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal", batch_axis=(0,))
            self.param("experts_gate", stacked, (held, d, width))
            self.param("experts_up", stacked, (held, d, width))
            self.param("experts_down", stacked, (held, width, d))
            return x

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x):
            Norm(name="input_norm")(x)
            Attention(name="attn")(x)
            Norm(name="post_attn_norm")(x)
            return Experts(name="moe")(x)

    class LM(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(z["vocab_size"], d, name="embed")(tokens)
            for i in range(z["num_hidden_layers"]):
                x = Layer(name=f"layer_{i}")(x)
            Norm(name="norm")(x)
            return dense(z["vocab_size"], name="head")(x)

    @jax.jit
    def make():
        return from_program_tree(LM().init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])

    return make


def from_program_tree(tree: dict) -> dict:
    """The reference's weights from a parameter tree of the program's names
    (``DsaMoeLM``'s ``params``, or the skeleton's): the layers stacked."""
    import jax
    import jax.numpy as jnp

    layers = []
    i = 0
    while f"layer_{i}" in tree:
        layer, attn = tree[f"layer_{i}"], tree[f"layer_{i}"]["attn"]
        layers.append(
            {
                "norm1": layer["input_norm"]["scale"],
                "norm2": layer["post_attn_norm"]["scale"],
                **{n: attn[f"{n}_proj"]["kernel"] for n in ("q", "k", "v", "o")},
                "q_norm": attn["q_norm"]["scale"],
                "k_norm": attn["k_norm"]["scale"],
                "iq": attn["index_q_proj"]["kernel"],
                "ik": attn["index_k_proj"]["kernel"],
                "ik_scale": attn["index_k_norm"]["scale"],
                "ik_bias": attn["index_k_norm"]["bias"],
                "iw": attn["index_weights_proj"]["kernel"],
                **layer["moe"],
            }
        )
        i += 1
    return {
        "embed": tree["embed"]["embedding"],
        "norm": tree["norm"]["scale"],
        "head": tree["head"]["kernel"],
        "layers": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers),
    }


def _divisor_at_most(n: int, limit: float) -> int:
    r = max(1, min(n, int(limit)))
    while n % r:
        r -= 1
    return r


#: the bytes of one query block's attention scores, float32 at every head
SCORE_BYTES = 0.27e9
#: the query blocks' segments, each against the keys up to its end
KEY_SEGMENTS = 8
#: the reference's eval compiled with the least effort on its speed: about 11 s
#: on the chip's host, not about 30, and it runs as fast (1.8 s; my chip runs,
#: PR 41).  The train step keeps the default effort: at the least it compiled in
#: 42 s, not about 70, but ran 7.9 s a step, not 5.6
EVAL_COMPILER_OPTIONS = {"exec_time_optimization_effort": -1.0}


def _layer_functions(shape: tuple, precision: str, fault: str | None, r: int, s: int) -> dict:
    """The layer for ``r`` rows of ``s`` positions as plain functions of the
    residual stream ``x`` [R, S, D] and one layer's weights ``w``:
    ``attention(x, w) -> (x + attention, L_I)``, ``moe(x, w)`` (x + the held
    experts' part of the normed x), ``layer`` the two in turn; and
    ``rms_norm``, ``select`` (one query block's selection), ``mm``."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    z = _named(shape)
    mm = _matmul(precision)
    hd, nh, nkv = z["head_dim"], z["num_attention_heads"], z["num_key_value_heads"]
    n_i, d_i, topk = z["index_heads"], z["index_head_dim"], min(z["index_topk"], s)
    group = nh // nkv
    first, held, top = z["experts_held_first"], z["num_experts"], z["num_experts_per_tok"]
    eps = z["rms_norm_eps"]

    def rms_norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g

    def layer_norm(x, g, b):
        mean = jnp.mean(x, -1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(jnp.mean(jnp.square(x - mean), -1, keepdims=True) + eps) * g + b

    def rotation(width):
        """Rotary over ``width``, the halves paired: (x[i], x[i + width/2])
        turns by pos * theta^(-2i/width)."""
        inv_freq = z["rope_theta"] ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
        angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
        cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]  # [S, 1, width/2]

        def rotate(x):  # [R, S, H, width]
            a, b = x[..., : width // 2], x[..., width // 2 :]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

        return rotate

    rotate, rotate_index = rotation(hd), rotation(d_i // 2)

    def index_rotate(x):  # rotary on the first half of each indexer head
        return jnp.concatenate([rotate_index(x[..., : d_i // 2]), x[..., d_i // 2 :]], axis=-1)

    # queries whose scores against all keys are live at once: about 0.27 GB
    q_block = _divisor_at_most(s, SCORE_BYTES // (r * nh * s * 4))
    # every key after a segment's end is masked for every query of it
    segments = _divisor_at_most(s // q_block, KEY_SEGMENTS)

    def index_scores(qi_blk, w_blk, ki):  # [R, qb, keys]
        z_i = mm("rqhd,rsd->rqhs", qi_blk, ki)
        return mm("rqh,rqhs->rqs", w_blk, jax.nn.relu(z_i)) / math.sqrt(d_i)

    def select(ki, block):
        """Each query's ``topk`` visible keys by the indexer's scores (all of
        them where fewer are visible), as a [R, qb, keys] mask: the keys above
        ``lax.top_k``'s last value, and of the keys equal to it the first by
        position, as ``top_k`` takes them."""
        qi_blk, w_blk, t0 = block
        seen = jnp.arange(ki.shape[1])[None, :] <= t0 + jnp.arange(q_block)[:, None]
        if fault == "no_select":
            return jnp.broadcast_to(seen, (r, *seen.shape))
        scores = jnp.where(seen, index_scores(qi_blk, w_blk, ki), -jnp.inf)
        kth = jax.lax.top_k(scores, min(topk, ki.shape[1]))[0][..., -1:]
        above, tied = scores > kth, scores == kth
        room = min(topk, ki.shape[1]) - jnp.sum(above, axis=-1, keepdims=True)
        return (above | (tied & (jnp.cumsum(tied, axis=-1) <= room))) & seen

    @jax.checkpoint
    def attend(k, v, ki, block):
        q_blk, qi_blk, w_blk, chosen = block  # [R, qb, Hkv, group, hd], [R, qb, H_I, d_I], [R, qb, H_I], [R, qb, keys]
        scores = index_scores(qi_blk, w_blk, ki)
        logits = mm("rqngd,rknd->rngqk", q_blk, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(chosen[:, None, None], logits, -jnp.inf), axis=-1)
        o = mm("rngqk,rknd->rqngd", probs, v)
        p = jnp.mean(jax.lax.stop_gradient(probs), axis=(1, 2))  # [R, qb, keys]
        log_q = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
        terms = jnp.where(chosen & (p > 0), p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0)
        return o, jnp.sum(terms)

    def project(x, w):
        """The queries, keys and values of the attention and of the indexer."""
        h = rms_norm(x, w["norm1"])
        q = rotate(rms_norm(mm("rsd,de->rse", h, w["q"]).reshape(r, s, nh, hd), w["q_norm"]))
        k = rotate(rms_norm(mm("rsd,de->rse", h, w["k"]).reshape(r, s, nkv, hd), w["k_norm"]))
        v = mm("rsd,de->rse", h, w["v"]).reshape(r, s, nkv, hd)
        hs = jax.lax.stop_gradient(h)
        qi = index_rotate(mm("rsd,de->rse", hs, w["iq"]).reshape(r, s, n_i, d_i))
        ki = index_rotate(layer_norm(mm("rsd,de->rse", hs, w["ik"]), w["ik_scale"], w["ik_bias"])[:, :, None])[:, :, 0]
        wi = mm("rsd,de->rse", hs, w["iw"]) / math.sqrt(n_i)
        return q, k, v, qi, ki, wi

    def attention(x, w):
        q, k, v, qi, ki, wi = project(x, w)
        split = lambda a: jnp.moveaxis(a.reshape(r, s // q_block, q_block, *a.shape[2:]), 1, 0)  # noqa: E731
        blocks = (split(q.reshape(r, s, nkv, group, hd)), split(qi), split(wi), jnp.arange(0, s, q_block))
        per = s // q_block // segments
        o, kl = [], []
        for g in range(segments):
            end = (g + 1) * per * q_block
            mine = tuple(a[g * per : (g + 1) * per] for a in blocks)
            # k, v and kI closed over, not carried: a scan's carry is kept once a
            # block for the backward (128 copies of k and v: 8 GB at the cell's
            # sizes, my chip run, PR 41), a closed-over array once
            keys = k[:, :end], v[:, :end], ki[:, :end]
            sg = jax.lax.stop_gradient
            chosen = jax.lax.map(lambda block: select(sg(ki[:, :end]), block), sg(mine[1:]))
            chosen = checkpoint_name(chosen, "selected")
            o_g, kl_g = jax.lax.map(lambda block: attend(*keys, block), (*mine[:3], chosen))
            o.append(o_g)
            kl.append(kl_g)
        o = checkpoint_name(jnp.moveaxis(jnp.concatenate(o), 0, 1).reshape(r, s, nh * hd), "attended")
        return x + mm("rse,ed->rsd", o, w["o"]), jnp.sum(jnp.concatenate(kl)) / (r * s)

    def silu(x):
        return x * jax.nn.sigmoid(x)

    def moe(x, w):
        h = rms_norm(x, w["norm2"])
        logits = mm("rsd,de->rse", h, w["router"])  # over ALL experts, from the normed stream
        chosen_logits, chosen = jax.lax.top_k(logits, top)
        weights = jax.nn.softmax(chosen_logits, axis=-1)
        # [R, S, held]: a chosen expert's weight where it is held here, else 0
        per_expert = jnp.sum(jax.nn.one_hot(chosen - first, held) * weights[..., None], axis=-2)

        tokens = r * s
        flat_h = h.reshape(tokens, -1)
        routed_to = jnp.sum(jax.nn.one_hot(chosen - first, held), axis=-2).reshape(tokens, held) > 0
        # an expert's rows: its routed tokens, gathered into an eighth of the
        # tokens where they fit (an even router sends it a sixteenth here),
        # else every token; each weighted by its gate, 0 where not routed
        room = max(1, tokens // 8)

        @jax.checkpoint
        def add_expert(acc, e):
            gate, up, down, weight, mine = e  # [D, F], [D, F], [F, D], [T], [T] bool

            def product(rows):
                return mm("td,de->te", silu(mm("td,de->te", rows, gate)) * mm("td,de->te", rows, up), down)

            def gathered(acc):
                order = jax.lax.top_k(mine.astype(jnp.float32), room)[1]  # its routed tokens first
                return acc.at[order].add(weight[order][:, None] * product(flat_h[order]))

            def every_token(acc):
                return acc + weight[:, None] * product(flat_h)

            return jax.lax.cond(jnp.sum(mine) <= room, gathered, every_token, acc), None

        routed, _ = jax.lax.scan(
            add_expert,
            jnp.zeros((tokens, x.shape[-1]), x.dtype),
            (w["experts_gate"], w["experts_up"], w["experts_down"], per_expert.reshape(tokens, held).T, routed_to.T),
        )
        return x + routed.reshape(x.shape)

    def layer(x, w):
        x, kl = attention(x, w)
        return moe(x, w), kl

    return {"rms_norm": rms_norm, "select": select, "attention": attention, "moe": moe, "layer": layer, "mm": mm}


def _forward(params, tokens, shape: tuple, precision: str, fault: str | None):
    """``(CE, L_I of every layer)`` of ``tokens`` [R, S]: the head and the
    loss in blocks of positions (a block's float32 logits near 0.3 GB)."""
    import jax
    import jax.numpy as jnp

    r, s = tokens.shape
    f = _layer_functions(shape, precision, fault, r, s)
    x = params["embed"][tokens]
    # a layer's backward computes its forward again, but for the selection
    # and the attention's output, which it keeps
    keep = jax.checkpoint_policies.save_only_these_names("selected", "attended")
    layer = jax.checkpoint(f["layer"], policy=keep)
    x, kl = jax.lax.scan(layer, x, params["layers"])
    x = f["rms_norm"](x, params["norm"])
    # position t predicts token t+1; the last position predicts nothing
    targets = jnp.concatenate([tokens[:, 1:], jnp.zeros((r, 1), tokens.dtype)], axis=1)
    counted = (jnp.arange(s) < s - 1).astype(jnp.float32)
    block = _divisor_at_most(s, 0.3e9 // (r * params["head"].shape[1] * 4))

    @jax.checkpoint
    def block_nll(args):
        x_blk, target, weight = args  # [R, block, D], [R, block], [block]
        logits = f["mm"]("rsd,dv->rsv", x_blk, params["head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * weight)

    split = lambda a: jnp.moveaxis(a.reshape(r, s // block, block, *a.shape[2:]), 1, 0)  # noqa: E731
    sums = jax.lax.map(block_nll, (split(x), split(targets), counted.reshape(-1, block)))
    return jnp.sum(sums) / (r * (s - 1)), kl


@functools.lru_cache(maxsize=None)
def _programs(shape: tuple, precision: str, fault: str | None):
    """The reference's two jitted programs: one train step (loss ``CE + sum
    L_I`` and its gradient, then clip and AdamW; it returns the loss and the
    mean ``L_I``) and the eval loss (``CE``).  ``fault`` plants one of the
    faults the correctness tests must see (never set by a benchmark run).
    One row of the batch at a time is not needed: the cell's batch is one
    sequence."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, compiler_options=EVAL_COMPILER_OPTIONS)
    def eval_loss(params, tokens):
        return _forward(params, tokens, shape, precision, fault)[0]

    def loss_of(params, tokens):
        ce, kl = _forward(params, tokens, shape, precision, fault)
        trained = jax.lax.stop_gradient(kl) if fault == "indexer_frozen" else kl
        return ce + jnp.sum(trained), (ce + jnp.sum(kl), jnp.mean(kl))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, lr, tokens):
        if fault == "half_batch":
            # half the rows; of a single row, the first half of its positions
            rows, s = tokens.shape
            tokens = tokens[: rows // 2] if rows > 1 else tokens[:, : s // 2]
        (_, (loss, kl)), grads = jax.value_and_grad(loss_of, has_aux=True)(params, tokens)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads)))
        clip = jnp.minimum(1.0, 1.0 / (gnorm + 1e-6))
        t = (count + 1).astype(jnp.float32)
        c1, c2 = 1.0 - 0.9**t, 1.0 - 0.999**t

        def adamw(p, g, m, v):
            g = g * clip
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * jnp.square(g)
            p_new = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + 1e-8) + 0.01 * p)
            return p_new, m, v

        out = jax.tree_util.tree_map(adamw, params, grads, m, v)
        pick = lambda i: jax.tree_util.tree_map(lambda _, o: o[i], params, out)  # noqa: E731
        new_params = params if fault == "state_unchanged" else pick(0)
        return new_params, pick(1), pick(2), loss, kl

    return step, eval_loss


def reference_series(
    sizes: dict, traffic: dict, seed: int, lr: float, *, precision: str = "f32", fault: str | None = None
) -> dict:
    """``{"loss": {step: value}, "eval_loss": ..., "indexer_kl": ...}`` of one
    trial's first reports, computed by the plain reference."""
    import jax
    import jax.numpy as jnp

    steps = int(traffic["steps"])
    last = max(COMPARE_STEPS)
    data = markov_tokens(sizes["vocab_size"], sizes["n_seq"], sizes["seq_len"], seed)
    rows, eval_rows = batches(data, sizes["batch_size"], last + 1)
    step, eval_loss = _programs(shape_of(sizes), precision, fault)
    params = init_params(sizes)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    eval_tokens = jnp.asarray(eval_rows)
    out = {name: {} for name in METRICS}
    for s in range(last + 1):
        params, m, v, loss, kl = step(
            params, m, v, jnp.int32(s), jnp.float32(lr_at(s, lr, steps)), jnp.asarray(rows[s])
        )
        if s in COMPARE_STEPS:
            out["loss"][s] = float(loss)
            out["indexer_kl"][s] = float(kl)
            out["eval_loss"][s] = float(eval_loss(params, eval_tokens))
    del params, m, v
    return out


# ---------------------------------------------------------------------------
# what is compared
# ---------------------------------------------------------------------------


def compare(series: dict, reference: dict) -> dict:
    """Gaps between a trial's reported series and the reference's, relative to
    the reference, at the reports of step 0 (forward and loss; the schedule's
    first update has rate 0) and step 10 (ten updates): ``*_loss_gap`` over
    the training loss (``CE + sum L_I``) and ``eval_loss`` (``CE``),
    ``*_indexer_gap`` over ``indexer_kl`` (the layers' mean ``L_I``, which a
    program that trains no indexer or selects otherwise moves most)."""
    gaps = {}
    groups = {"loss": ("loss", "eval_loss"), "indexer": ("indexer_kl",)}
    for when, step in (("first", COMPARE_STEPS[0]), ("trained", COMPARE_STEPS[1])):
        for group, metrics in groups.items():
            worst = 0.0
            for metric in metrics:
                got = series.get(metric, {}).get(step)
                want = reference[metric][step]
                if got is None or not math.isfinite(got) or not math.isfinite(want):
                    worst = math.inf
                else:
                    worst = max(worst, abs(got - want) / abs(want))
            gaps[f"{when}_{group}_gap"] = worst
    return gaps
